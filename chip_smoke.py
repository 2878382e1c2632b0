#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, at
the full width of the models the repo supports, on whatever TPU devices
this ONE process can see (it holds the chips; it starts no children):

  1. trainer, LM       dtf_tpu.cli.lm_main        transformer_tpu, bf16,
                                                  sequence 2048
  2. trainer, ResNet   dtf_tpu.cli.imagenet_main  ResNet-50 v1.5, bf16
  3. server            dtf_tpu.cli.serve_main     transformer_tpu, paged KV
                                                  cache, chunked prefill

Weights are random (from a seed) and the input is synthetic.  Each phase
is checked by the repo's own means: the loss trajectory and the compiled
step's ledger entry from the trace stream; every request's full token
budget through its stream; and the served tokens replayed through the
reference attention (gather + blockwise, plain XLA) against the Pallas
kernels — logits within bf16 tolerance, greedy choices the same.  On the
TPU the compiled bodies must contain the Pallas kernels; the reference
formulation there is a failure, not a fallback.

Exit code 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
only when every phase passed on a TPU.  No TPU (or no repo around this
file) is a non-zero exit with no result line.  Run it as
`python chip_smoke.py` from the repo root.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")

PLATFORM = "tpu"
LM_BATCH_PER_CHIP = 8
RESNET_BATCH_PER_CHIP = 256
TRAIN_ARGS = ["--use_synthetic_data", "--dtype", "bf16",
              "--distribution_strategy", "tpu", "--skip_checkpoint",
              "--skip_eval", "--log_steps", "2", "--seed", "7"]
LM_ARGS = TRAIN_ARGS + ["--model", "transformer_tpu", "--seq_len", "2048",
                        "--train_steps", "10"]
RESNET_ARGS = TRAIN_ARGS + ["--train_steps", "8"]
# seed 7 draws prompts of 189, 90, 149, 63, 133 and 77 tokens: one fits a
# single prefill chunk (64), the rest continue over 2-3 chunks with tails
# of 16, 32 and 64 — flash attention on every first chunk, the paged
# kernel at S = chunk and, in decode, at S = 1
SERVE_ARGS = ["--serve_random_init", "--model", "transformer_tpu",
              "--dtype", "bf16", "--serve_requests", "6",
              "--serve_prompt_len", "200", "--serve_max_new_tokens", "24",
              "--seed", "7"]
# the Pallas kernels each compiled body must contain on the TPU
TRAIN_KERNELS = {"train_step": ("flash_fwd", "flash_bwd_fused")}
SERVE_KERNELS = {"serve_decode_step": ("paged_flash_decode",),
                 "serve_prefill_chunk_c64": ("flash_fwd",
                                             "paged_flash_decode"),
                 "serve_prefill_chunk_c32": ("paged_flash_decode",),
                 "serve_prefill_chunk_c16": ("paged_flash_decode",)}
# kernel vs reference logits: 8 units of bf16 roundoff (2^-8) on the
# logit scale.  A wrong page, mask or index moves logits by their whole
# spread, two orders of magnitude more.
LOGIT_RTOL = 2.0 ** -5


class SmokeFailure(Exception):
    pass


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def read_trace(trace_dir: str) -> list:
    from dtf_tpu.obs import trace
    trace.disable()     # close + flush the phase's tracer
    return trace.read_records(os.path.join(trace_dir, "trace_rank0.jsonl"))


def check_kernels(records: list, required: dict) -> dict:
    """Every named executable registered with the ledger, holding the
    named Pallas kernels in its compiled HLO."""
    from dtf_tpu.cli.trace_main import ledger_rows
    found = {r["exec"]: r.get("kernels") or {} for r in ledger_rows(records)}
    for name, kernels in required.items():
        require(name in found, f"no ledger entry for {name} (AOT compile "
                               f"or cost_analysis failed — see warnings)")
        missing = [k for k in kernels if k not in found[name]]
        require(not missing,
                f"{name} compiled without {missing} — it runs the "
                f"reference formulation (kernels found: {found[name]})")
    return found


def trainer_phase(name: str, main, args: list, batch: int,
                  kernels: dict) -> None:
    import math

    import jax
    trace_dir = os.path.join(OUT, f"trace_{name}")
    argv = args + ["--batch_size", str(batch), "--model_dir",
                   os.path.join(OUT, name), "--trace_dir", trace_dir]
    print(f"[{name}] python -m {main.__module__} {' '.join(argv)}",
          flush=True)
    main(argv)
    records = read_trace(trace_dir)
    losses = [r["loss"] for r in records if r.get("name") == "train_loss"]
    require(len(losses) >= 3, f"{name}: {len(losses)} logged losses")
    require(all(math.isfinite(x) for x in losses),
            f"{name}: non-finite loss in {losses}")
    require(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    found = check_kernels(records, kernels)
    # every device took part: this counter tracks what the allocator
    # holds across steps — the replicated train state — so a device the
    # mesh left out would sit far below the others
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    require(min(peaks) > 0.5 * max(peaks),
            f"{name}: device memory peaks {peaks} — a device holds no "
            f"replica of the state")
    result = {"global_batch": batch, "logged_losses": losses,
              "kernels": found.get("train_step", {}),
              "peak_bytes_per_device": peaks}
    print(f"[{name}] ok: {json.dumps(result)}", flush=True)


def serve_phase() -> list:
    from dtf_tpu.cli import serve_main
    trace_dir = os.path.join(OUT, "trace_serve")
    argv = SERVE_ARGS + ["--trace_dir", trace_dir]
    print(f"[serve] python -m dtf_tpu.cli.serve_main {' '.join(argv)}",
          flush=True)
    out = serve_main.main(argv)
    budget = int(argv[argv.index("--serve_max_new_tokens") + 1])
    n = int(argv[argv.index("--serve_requests") + 1])
    require(out["requests"] == n and out["shed"] == 0,
            f"serve: {out['requests']} of {n} requests completed, "
            f"{out['shed']} shed")
    require(out["streamed_tokens"] == n * budget
            and all(len(c) == budget for c in out["completions"]),
            f"serve: streamed {out['streamed_tokens']} tokens, wanted "
            f"{n} x {budget}")
    found = check_kernels(read_trace(trace_dir), SERVE_KERNELS)
    result = {"requests": n, "tokens_returned": out["streamed_tokens"],
              "kernels": {k: found[k] for k in SERVE_KERNELS}}
    print(f"[serve] ok: {json.dumps(result)}", flush=True)
    return out["completions"]


def replay_logits(decoder, prompts, served):
    """Teacher-force ``served`` (the tokens the server returned) through
    a Decoder: prefill each prompt in the engine's chunk plan, then
    decode in lockstep feeding the served tokens.  Returns the logits
    that chose every served token, [requests, budget, vocab]."""
    import numpy as np

    from dtf_tpu.serve.engine import DEFAULT_PREFILL_PAGES, chunk_plan
    page, m = decoder.page_size, decoder.pages_per_slot
    n, budget = len(prompts), len(served[0])
    tables = np.zeros((decoder.num_slots, m), np.int32)
    for r in range(n):
        tables[r] = 1 + r * m + np.arange(m)   # page 0 is the scratch page
    cache = decoder.fresh_cache()
    logits = np.zeros((n, budget, decoder.model.vocab_size), np.float32)
    for r, prompt in enumerate(prompts):
        for start, clen in chunk_plan(len(prompt),
                                      DEFAULT_PREFILL_PAGES * page, page):
            real = prompt[start:start + clen]
            tokens = np.zeros((clen,), np.int32)
            tokens[:len(real)] = real
            _, cache, last = decoder.prefill_chunk(
                cache, tokens, tables[r], start, len(real) - 1, 0.0, seed=0)
        logits[r, 0] = np.asarray(last)
    index = np.zeros((decoder.num_slots,), np.int32)
    index[:n] = [len(p) for p in prompts]
    temps = np.zeros((decoder.num_slots,), np.float32)
    seeds = np.zeros((decoder.num_slots,), np.uint32)
    for t in range(1, budget):
        tokens = np.zeros((decoder.num_slots,), np.int32)
        tokens[:n] = [s[t - 1] for s in served]
        _, cache, step = decoder.decode_step(
            cache, tokens, index, temps, seeds=seeds, block_tables=tables)
        logits[:, t] = np.asarray(step)[:n]
        index[:n] += 1
    return logits


def agreement_phase(served: list) -> dict:
    """The server's answers against the reference attention.  Outside
    any timing: both formulations replay the served tokens on the same
    weights, block tables and positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtf_tpu.cli.serve_main import SERVE_DEFAULTS, synthetic_prompts
    from dtf_tpu.config import parse_flags
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.decode import Decoder

    argv = [a for a in SERVE_ARGS if a != "--serve_random_init"]
    cfg = parse_flags(argv, defaults=SERVE_DEFAULTS)
    model, _ = build_model(cfg.model, num_classes=cfg.num_classes,
                           dtype=cfg.compute_dtype)
    # the weights serve_main --serve_random_init made from this seed
    params = model.init(jax.random.key(cfg.seed), jnp.zeros(
        (1, model.max_seq_len), jnp.int32))["params"]
    prompts = synthetic_prompts(cfg, model.vocab_size)
    logits = {}
    for name, m in (("kernel", model),
                    ("reference", model.clone(use_pallas=False))):
        decoder = Decoder(m, params, num_slots=cfg.serve_max_batch,
                          max_seq_len=model.max_seq_len,
                          kv_page_size=cfg.kv_page_size)
        logits[name] = replay_logits(decoder, prompts, served)
    kern, ref = logits["kernel"], logits["reference"]
    require(np.isfinite(kern).all() and np.isfinite(ref).all(),
            "agreement: non-finite logits")
    served = np.asarray(served)
    require((kern.argmax(-1) == served).all(),
            "agreement: replaying the served tokens on the kernel path "
            "does not reproduce them")
    err = float(np.abs(kern - ref).max())
    scale = float(np.abs(ref).max())
    require(err <= LOGIT_RTOL * scale,
            f"agreement: kernel vs reference logits differ by {err:.4g} "
            f"(logit scale {scale:.4g}, allowed {LOGIT_RTOL * scale:.4g})")
    # where the reference would have chosen another token, the two must
    # be tied within the numerical error just measured
    chosen = np.take_along_axis(ref, served[..., None], -1)[..., 0]
    gap = ref.max(-1) - chosen
    differ = ref.argmax(-1) != served
    require((gap[differ] <= 2 * err).all(),
            f"agreement: {int(differ.sum())} greedy tokens differ from the "
            f"reference by more than a tie (gaps {gap[differ]}, "
            f"error {err:.4g})")
    result = {"tokens_compared": int(served.size),
              "greedy_identical": int((~differ).sum()),
              "max_abs_logit_diff": err, "logit_scale": scale}
    print(f"[agreement] ok: {json.dumps(result)}", flush=True)
    return result


def main() -> int:
    t0 = time.time()
    import jax

    from dtf_tpu.cli import imagenet_main, lm_main
    from dtf_tpu.runtime import compile_cache

    cache_dir = compile_cache.configure()
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        print(f"chip_smoke: no TPU — JAX found platform "
              f"{devices[0].platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); this check only "
              f"passes on the chip", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    import jaxlib
    print(f"chip_smoke: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu_version}, device {json.dumps(device)}, compile "
          f"cache {cache_dir}", flush=True)

    cache_events = {"requests": 0, "hits": 0, "written": 0}
    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "written"}

    def on_event(event, **_):
        if event in names:
            cache_events[names[event]] += 1
    jax.monitoring.register_event_listener(on_event)

    shutil.rmtree(OUT, ignore_errors=True)
    n = len(devices)
    failed = []

    def attempt(name, phase, *args):
        t = time.time()
        try:
            out = phase(*args)
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.time() - t:.0f}s",
                  flush=True)
            return None
        print(f"[{name}] wall {time.time() - t:.0f}s (compilation "
              f"included)", flush=True)
        return out

    attempt("lm", trainer_phase, "lm", lm_main.main, LM_ARGS,
            LM_BATCH_PER_CHIP * n, TRAIN_KERNELS)
    attempt("resnet50", trainer_phase, "resnet50", imagenet_main.main,
            RESNET_ARGS, RESNET_BATCH_PER_CHIP * n, {"train_step": ()})
    completions = attempt("serve", serve_phase)
    if completions is not None:
        attempt("agreement", agreement_phase, completions)
    print(f"chip_smoke: compile cache {json.dumps(cache_events)}; total "
          f"wall {time.time() - t0:.0f}s", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
