"""Multi-device CPU serve smoke — the distributed-serving CI contract.

Two assertions, both fatal (nonzero exit), on a 4-virtual-device CPU
mesh (`--xla_force_host_platform_device_count=4`, the same stand-in
the tier-1 suite uses for a TPU pod slice):

  1. TP EXACTNESS — a TP=2 engine (params in the Megatron layout, KV
     page pool sharded on its head dim, every step under shard_map)
     produces token streams IDENTICAL to the TP=1 engine for a burst
     of varied-length prompts spanning the page-geometry edges.
  2. SHARED-PREFIX + STREAMING BARS — N concurrent requests over one
     system prompt against a pool too small for N unshared copies must fit
     ≥ 2× the concurrent sequences of the sharing-off pool at equal
     page budget, and the first STREAMED token must land before full
     retire (p50).

Usage: python tools/serve_smoke.py          (ci_check.sh stage 8)
"""

import concurrent.futures as cf
import os
import sys
import threading
import time

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
import numpy as np            # noqa: E402

PS = 16
# the shared-prefix scenario's shape: the pool sizing must agree with
# the traffic the scenario generates, or the >=2x bar measures a wrong
# page budget
PREFIX_TAIL_LEN = 8        # per-request tokens after the system prompt
PREFIX_BUDGET = 24         # per-request max_new_tokens


def prefix_pool_pages(batch: int, sys_pages: int, page_size: int) -> int:
    """Total pool pages (incl. scratch) sized so ONE full prompt copy
    plus per-request tails fit, but `batch` unshared copies cannot."""
    tail_pages = (-(-(sys_pages * page_size + PREFIX_TAIL_LEN
                      + PREFIX_BUDGET) // page_size) - sys_pages)
    return 1 + (sys_pages + tail_pages) + (batch - 1) * tail_pages


def shared_prefix_scenario(model, params, *, batch: int, seq: int,
                           requests: int, kv_page_size: int,
                           kv_pool_pages: int, sys_pages: int,
                           prefix_sharing: bool):
    """N concurrent requests sharing one system prompt, against a pool
    deliberately too small to hold N unshared copies.

    The warm request writes + registers the system prefix (sharing
    arm) and compiles every shape; the burst then admits with
    ``sys_pages`` of each prompt shared — so concurrency is bounded by
    the per-request TAIL pages, not the full prompt.  Every handle is
    consumed through its token STREAM by a client thread, recording
    first-streamed-token latency next to full-retire latency.

    Returns (max_concurrent, ttft_stream_p50, full_latency_p50)."""
    from dtf_tpu.serve import ServeEngine
    eng = ServeEngine(model, params, max_batch=batch, max_seq_len=seq,
                      max_delay_s=0.0, queue_size=max(64, 2 * requests),
                      kv_page_size=kv_page_size,
                      kv_pool_pages=kv_pool_pages,
                      prefix_sharing=prefix_sharing)
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(0, model.vocab_size,
                              (sys_pages * kv_page_size,)).astype(np.int32)
    eng.submit(sys_prompt, max_new_tokens=2).result(timeout=600)
    eng.reset_measurement()
    first_times = {}
    lock = threading.Lock()

    def _consume(rid, handle, t_submit):
        for _ in handle.stream(timeout=600):
            with lock:
                if rid not in first_times:
                    first_times[rid] = time.perf_counter() - t_submit

    handles = []
    with cf.ThreadPoolExecutor(max_workers=requests) as ex:
        consumers = []
        for r in range(requests):
            tail = rng.integers(0, model.vocab_size,
                                (PREFIX_TAIL_LEN,)).astype(np.int32)
            h = eng.submit(np.concatenate([sys_prompt, tail]),
                           max_new_tokens=PREFIX_BUDGET)
            handles.append(h)
            consumers.append(ex.submit(_consume, r, h,
                                       time.perf_counter()))
        results = [h.result(timeout=600) for h in handles]
        for c in consumers:
            c.result()       # propagate consumer-thread failures loudly
    maxc = eng.max_concurrent
    eng.stop()
    lat = sorted(r.latency_s for r in results)
    ttft = sorted(first_times.values())
    if not ttft:
        # a 0.0 default would pass the ttft < full-retire bar VACUOUSLY
        raise SystemExit(
            "shared-prefix scenario: no first-token times recorded — "
            "the streaming path produced no tokens")
    return maxc, ttft[len(ttft) // 2], lat[len(lat) // 2]


def main() -> int:
    from dtf_tpu.models.transformer import TransformerLM
    from dtf_tpu.serve import ServeEngine, place_for_serving, serving_mesh

    assert jax.device_count() >= 4, (
        f"expected 4 virtual CPU devices, got {jax.device_count()}")
    model = TransformerLM(vocab_size=256, num_layers=2, d_model=64,
                          num_heads=4, d_ff=128, max_seq_len=256)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 256), jnp.int32))["params"]

    # -- 1. TP=2 token-exact vs TP=1 ------------------------------------
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (1, PS - 1, PS, 3 * PS + 7, 40, 9)]
    mesh = serving_mesh(2)
    tp_params = place_for_serving({"params": params}, mesh=mesh,
                                  model_parallelism=2)["params"]
    streams = {}
    for name, p, m in [("tp1", params, None), ("tp2", tp_params, mesh)]:
        eng = ServeEngine(model, p, max_batch=4, max_seq_len=256,
                          kv_page_size=PS, max_delay_s=0.0, mesh=m)
        try:
            hs = [eng.submit(pr, max_new_tokens=8) for pr in prompts]
            streams[name] = [h.result(timeout=600).tokens for h in hs]
        finally:
            eng.stop(drain=False)
    if streams["tp1"] != streams["tp2"]:
        print("serve smoke FAILED: TP=2 decode diverged from TP=1:\n"
              f"  tp1: {streams['tp1']}\n  tp2: {streams['tp2']}",
              file=sys.stderr)
        return 1
    print(f"serve smoke: TP=2 token-exact vs TP=1 over {len(prompts)} "
          f"prompts ({sum(len(t) for t in streams['tp1'])} tokens)")

    # -- 2. shared-prefix + streaming bars ------------------------------
    sys_pages = 8
    pool = prefix_pool_pages(8, sys_pages, PS)
    c_share, ttft, full = shared_prefix_scenario(
        model, params, batch=8, seq=256, requests=8, kv_page_size=PS,
        kv_pool_pages=pool, sys_pages=sys_pages, prefix_sharing=True)
    c_noshare, _, _ = shared_prefix_scenario(
        model, params, batch=8, seq=256, requests=8, kv_page_size=PS,
        kv_pool_pages=pool, sys_pages=sys_pages, prefix_sharing=False)
    if c_share < 2 * c_noshare:
        print(f"serve smoke FAILED: prefix sharing fits {c_share} "
              f"concurrent sequences vs {c_noshare} without — below the "
              f"2x bar at {pool - 1} usable pages", file=sys.stderr)
        return 1
    if ttft >= full:
        print(f"serve smoke FAILED: first streamed token p50 {ttft:.3f}s "
              f"not below full-retire p50 {full:.3f}s", file=sys.stderr)
        return 1
    print(f"serve smoke: prefix sharing {c_share} vs {c_noshare} "
          f"concurrent (>=2x bar), stream ttft p50 {ttft:.3f}s < "
          f"full-retire p50 {full:.3f}s")
    print("serve smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
