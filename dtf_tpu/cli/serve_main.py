"""Serving entry point — checkpoint → KV-cache decode → batched traffic.

The training mains end at a checkpoint; this main is its consumer: it
loads train-format (or --export_dir-format) variables through the
serve bridge, stands up the dynamic batching engine, drives it with
synthetic traffic — every request consumed through its token STREAM —
and reports latency percentiles + tokens/s in the BenchmarkMetric
format (--benchmark_log_dir writes metric.log).

`--serve_tp N` serves tensor-parallel: an N-chip 'model' mesh, params
restored DIRECTLY into the Megatron layout (no replicated
intermediate) and the KV page pool sharded on its head dim — a model
that trains sharded never has to fit on one chip to serve.
`--serve_prefix_sharing` (default on, paged cache) makes a shared
system prompt cost one physical page copy across the batch.

Examples:
  # serve a trained LM checkpoint:
  python -m dtf_tpu.cli.serve_main --model_dir /tmp/lm_run \
      --model transformer_small --serve_requests 32

  # no checkpoint yet?  --serve_random_init stands up the engine on
  # fresh params (pipeline smoke test; answers are noise):
  python -m dtf_tpu.cli.serve_main --serve_random_init \
      --model transformer_small --serve_requests 8
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dtf_tpu.config import parse_flags

log = logging.getLogger("dtf_tpu")

SERVE_DEFAULTS = dict(
    model="transformer_small",
    dataset="lm",
    skip_eval=True,
)


def build_serving_engine(cfg, random_init: bool = False,
                         replica_rank=None):
    """Model + params + ServeEngine from a Config — shared by this
    main and the replica-tier entry (cli/replica_main.py).

    The engine gets an obs HEARTBEAT when the launcher (or the serving
    router) exported DTF_HEARTBEAT_DIR: the engine loop rewrites
    ``heartbeat_rank{N}.json`` once per iteration, so launch.py's hang
    watchdog — and the router's health probe — cover serving exactly
    like they cover train ranks."""
    from dtf_tpu.models import build_model
    from dtf_tpu.obs.watchdog import Heartbeat
    from dtf_tpu.serve import (ServeEngine, load_for_serving,
                               serving_memory_plan, serving_mesh)
    from dtf_tpu.serve.bridge import place_for_serving

    if not cfg.model.startswith(("transformer", "routed_decoder")):
        raise ValueError(
            f"serving is implemented for the plain transformer LM family "
            f"and the routed decoder, not {cfg.model!r}")
    model, _ = build_model(cfg.model, num_classes=cfg.num_classes,
                           dtype=cfg.compute_dtype)
    max_seq = cfg.serve_max_seq_len or model.max_seq_len
    # --serve_tp N: an N-chip 'model'-axis mesh; the bridge restores
    # DIRECTLY into the Megatron layout (no replicated intermediate)
    # and the engine's Decoder runs every step under shard_map
    mesh = serving_mesh(cfg.serve_tp) if cfg.serve_tp > 1 else None
    if random_init:
        log.warning("--serve_random_init: serving FRESH parameters — "
                    "pipeline smoke test only, outputs are noise")
        variables = {"params": model.init(
            jax.random.key(cfg.seed),
            jnp.zeros((1, max_seq), jnp.int32))["params"]}
        variables = place_for_serving(variables, mesh=mesh,
                                      model_parallelism=cfg.serve_tp)
    else:
        variables = load_for_serving(model_dir=cfg.model_dir,
                                     export_dir=cfg.export_dir, mesh=mesh,
                                     model_parallelism=cfg.serve_tp)

    # the memory plan makes pool sizing a logged decision
    serving_memory_plan(model, num_slots=cfg.serve_max_batch,
                        max_seq_len=max_seq,
                        kv_page_size=cfg.kv_page_size,
                        kv_pool_pages=cfg.kv_pool_pages,
                        model_parallelism=cfg.serve_tp,
                        params=variables["params"])
    engine = ServeEngine(
        model, variables["params"],
        max_batch=cfg.serve_max_batch, max_seq_len=max_seq,
        max_delay_s=cfg.serve_max_delay_ms / 1000.0,
        queue_size=cfg.serve_queue_size, seed=cfg.seed,
        kv_page_size=cfg.kv_page_size,
        kv_pool_pages=cfg.kv_pool_pages or None,
        prefill_chunk=cfg.serve_prefill_chunk,
        prefix_sharing=cfg.serve_prefix_sharing,
        mesh=mesh,
        heartbeat=Heartbeat.from_env(rank=replica_rank,
                                     interval_s=cfg.heartbeat_secs))
    return model, engine


def synthetic_prompts(cfg, vocab: int) -> list:
    """The demo's traffic: ``serve_requests`` prompts of varied length
    (1 … ``serve_prompt_len`` random token ids) — a pure function of
    ``cfg.seed``, so a caller can rebuild exactly what was served."""
    rng = np.random.default_rng(cfg.seed)
    prompts = []
    for _ in range(cfg.serve_requests):
        plen = int(rng.integers(1, cfg.serve_prompt_len + 1))
        prompts.append(rng.integers(0, vocab, (plen,)).astype(np.int32))
    return prompts


def serve(cfg, random_init: bool = False) -> dict:
    """Build model + params + engine from a Config; run the synthetic
    traffic demo; return the stats dict.  Library entry for tests."""
    from dtf_tpu.serve import collect_stats

    model, engine = build_serving_engine(cfg, random_init=random_init)

    # --metrics_port: the engine registry (queue depth, prefix hits,
    # decode-step MFU ledger gauges) live over Prometheus + /healthz
    metrics_server = None
    if cfg.metrics_port:
        from dtf_tpu.obs.prom import MetricsServer
        metrics_server = MetricsServer(
            cfg.metrics_port, registry_fn=lambda: engine.metrics,
            health_fn=lambda: {"ok": not engine.draining,
                               "draining": engine.draining,
                               "outstanding": engine.outstanding})

    # serve drain: SIGTERM (the preemption signal) stops admissions —
    # new submits shed with retry_after — finishes in-flight decodes,
    # and the process exits 0 (a drained replica is a clean exit the
    # supervisor does not classify as a crash).  The handler body is
    # async-signal-minimal: one lock-free engine call + one os.write.
    drained = {"signaled": False}

    def _on_sigterm(signum, frame):
        drained["signaled"] = True
        engine.begin_drain()
        os.write(2, b"serve: SIGTERM - draining (admissions shed, "
                    b"in-flight finishing)\n")

    old_handler = None
    try:
        old_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (library/test use)
        pass

    from dtf_tpu.serve.engine import Backpressure
    handles = []
    shed_by_drain = 0
    streamed_tokens = 0
    t0 = time.time()

    def _consume(handle):
        # the streaming client shape: render each token as its decode
        # step retires (first-token latency, not full-retire latency).
        # Tokens counted here flowed through the per-token path; the
        # engine's serve_stream_lag_s histogram records consumer lag
        n = 0
        for _ in handle.stream(timeout=600):
            n += 1
        return n

    try:
        import concurrent.futures as cf

        # synthetic traffic: varied-length prompts, all submitted up
        # front (a burst — the shape that exercises batching + queue),
        # each consumed through its token STREAM by a client thread
        with cf.ThreadPoolExecutor(max_workers=8) as ex:
            consumers = []
            for prompt in synthetic_prompts(cfg, model.vocab_size):
                try:
                    h = engine.submit(
                        prompt, max_new_tokens=cfg.serve_max_new_tokens,
                        temperature=cfg.serve_temperature)
                except Backpressure:
                    # drain (or a genuinely full queue): the request is
                    # the client's to retry elsewhere
                    shed_by_drain += 1
                    continue
                handles.append(h)
                consumers.append(ex.submit(_consume, h))
            streamed_tokens = sum(c.result() for c in consumers)
        results = [h.result(timeout=600) for h in handles]
        wall = time.time() - t0
        engine.stop()  # drain=True: waits out queued + in-flight work
    finally:
        if old_handler is not None:
            signal.signal(signal.SIGTERM, old_handler)
        if metrics_server is not None:
            metrics_server.shutdown()
    # nothing here cancels a request, so a cancelled or empty result
    # means the engine thread died under it (engine._loop delivers
    # cancellations so clients do not hang) — that is a failed run, not
    # a run with zero throughput
    dead = [r.request_id for r in results if r.cancelled or not r.tokens]
    if dead or engine.error is not None:
        raise RuntimeError(
            f"serve: {len(dead)} of {len(results)} requests came back "
            f"cancelled or empty (ids {dead[:8]}) — engine thread died: "
            f"{engine.error!r}") from engine.error
    if drained["signaled"]:
        log.info("serve: drained after SIGTERM (%d in-flight finished, "
                 "%d shed) — exiting 0", len(handles), shed_by_drain)

    stats = collect_stats(engine.completed, engine.shed_count,
                          wall_time_s=wall)
    if cfg.benchmark_log_dir:
        from dtf_tpu.utils.benchmark_logger import BenchmarkFileLogger
        blog = BenchmarkFileLogger(cfg.benchmark_log_dir)
        blog.log_run_info(cfg.model, cfg.dataset, cfg.to_dict(),
                          test_id=cfg.benchmark_test_id)
        blog.log_serving_stats(stats)
        # live engine registry (queue depth, sheds, slot occupancy,
        # latency histogram) in the same metric.log format
        blog.log_registry(engine.metrics)
    out = {
        "requests": stats.num_requests,
        "shed": stats.num_shed,
        "tokens_per_second": stats.tokens_per_s,
        "latency_p50_s": stats.latency_p50_s,
        "latency_p99_s": stats.latency_p99_s,
        "ttft_p50_s": stats.ttft_p50_s,
        "streamed_tokens": streamed_tokens,
        "tp": cfg.serve_tp,
    }
    log.info("Serve stats: %s", out)
    # what was generated, in submission order — for callers that check
    # the answers (chip_smoke.py replays them on the reference path)
    out["completions"] = [list(r.tokens) for r in results]
    return out


def main(argv=None) -> dict:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    argv = list(argv if argv is not None else sys.argv[1:])
    # one serving-only switch, kept out of Config: random-init serving
    # is a smoke-test posture, not a run configuration
    random_init = "--serve_random_init" in argv
    if random_init:
        argv.remove("--serve_random_init")
    cfg = parse_flags(argv, defaults=SERVE_DEFAULTS)
    from dtf_tpu.runtime import compile_cache
    compile_cache.configure()
    # --trace_dir: serve batch-form/decode spans + shed anomalies
    from dtf_tpu.obs import trace
    trace.maybe_configure(cfg)
    return serve(cfg, random_init=random_init)


if __name__ == "__main__":
    main()
