"""Benchmark: ResNet-50 ImageNet-shape training throughput per chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}
plus roofline context fields:
  - step_ms: mean wall time of one optimizer step
  - mfu: model FLOP utilization — XLA's own flop count for the compiled
    train step (fwd+bwd+update, 2·MAC convention) divided by step time
    and the chip's peak bf16 FLOP/s.  Peak is looked up from the device
    kind; unknown kinds report mfu=null rather than a made-up number.

Baseline: the reference's best steady-state per-GPU rate — 168.6
images/s on a Tesla P40 under the 16-process ParameterServer run
(BASELINE.md, ps_server/log1.log BenchmarkMetric lines).  This bench
runs the same workload shape (ResNet-50 v1.5, 224×224, synthetic data,
full train step incl. gradient all-reduce) on however many chips are
attached and reports images/sec/chip.

Roofline notes (v5 lite): r1's 1,937 img/s was lifted to ~2,430-2,520
in r2 by (a) bf16 BatchNorm I/O — r1 ran BN in fp32, doubling the HBM
traffic of every conv→BN→relu link (+20%), and (b) the space-to-depth
stem (exact 7×7/2/3ch → 4×4/1/12ch reformulation, models/resnet.py
Conv1SpaceToDepth, +4%).  The r3 profile (bench_profile.py) replaced
the r2 "conv-compute-bound" guess with a measurement; with r4's
sync-cancelled timing the step is 98.6 ms moving ~79 GB at 97.5% of
the chip's HBM bandwidth — ~31% MFU IS the v5e bandwidth roofline for
this program (the FLOP floor is only 31 ms), and the optimized HLO
shows BN/relu already fused into conv operand reads, so the lever is
byte-count reduction, not kernels or scheduling (docs/DESIGN.md has
the full table).
"""

import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_IMG_PER_SEC_PER_DEVICE = 168.6

# Peak dense bf16 TFLOP/s by TPU generation (public spec sheets).
# Keys are matched case-insensitively against jax device_kind.
PEAK_BF16_TFLOPS = {
    "v6e": 918.0, "v6": 918.0,
    "v5p": 459.0,
    "v5 lite": 197.0, "v5e": 197.0, "v5litepod": 197.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 45.0,
}


def peak_tflops(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_BF16_TFLOPS.items():
        if key in kind:
            return val
    return None


def is_oom(e: Exception) -> bool:
    """Only retry smaller batches on resource exhaustion — any other
    failure must surface (the r1 bench swallowed real regressions)."""
    msg = f"{type(e).__name__}: {e}"
    return bool(re.search(r"RESOURCE_EXHAUSTED|out of memory|OOM|"
                          r"Resource exhausted|memory space hbm", msg,
                          re.IGNORECASE))


def windowed_step_seconds(run_iters, sync, windows: int = 3,
                          short: int = 4, long: int = 24):
    """True per-step seconds, free of the host-sync overhead.

    Each window times a short and a long run of steps, each ended by
    one host sync; (t_long - t_short)/(long - short) cancels the
    constant sync/dispatch cost the way a single timed window cannot
    (a window of N steps carries sync/N per step).
    Returns (median, min, max) across windows of the per-step seconds.
    """
    per_step = []
    for _ in range(windows):
        t0 = time.perf_counter()
        run_iters(short)
        sync()
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_iters(long)
        sync()
        t_long = time.perf_counter() - t0
        d = (t_long - t_short) / (long - short)
        if d <= 0:  # pathological jitter: fall back to the long window
            d = t_long / long
        per_step.append(d)
    return (float(np.median(per_step)), float(np.min(per_step)),
            float(np.max(per_step)))


def timed_train_steps(step_fn, state, batch, windows: int = 3,
                      short: int = 4, long: int = 24):
    """Times a donated-state train step with the sync-cancelling
    protocol: threads the state through, syncs on the loss metric,
    asserts it finite.  THE shared wrapper for every bench that times
    a Trainer step (bench.py, bench_lm, bench_profile*).  Returns
    (median_s, min_s, max_s, iters_per_window, final_state)."""
    mbox = {}

    def run_iters(n):
        nonlocal state
        for _ in range(n):
            state, mbox["m"] = step_fn(state, *batch)

    def sync():
        loss = float(jax.device_get(mbox["m"]["loss"]))
        assert np.isfinite(loss), f"non-finite loss {loss}"

    med, lo, hi = windowed_step_seconds(run_iters, sync, windows=windows,
                                        short=short, long=long)
    return med, lo, hi, short + long, state


def run_bench(per_chip_batch: int, warmup: int = 5, windows: int = 3):
    from dtf_tpu.config import Config
    from dtf_tpu.data.base import IMAGENET
    from dtf_tpu.models import build_model
    from dtf_tpu.runtime import initialize
    from dtf_tpu.train import Trainer

    n_chips = len(jax.devices())
    global_batch = per_chip_batch * n_chips
    cfg = Config(model="resnet50", dataset="imagenet", dtype="bf16",
                 batch_size=global_batch, distribution_strategy="tpu",
                 skip_eval=True, train_steps=1)
    rt = initialize(cfg)
    model, l2 = build_model("resnet50", dtype=jnp.bfloat16)
    trainer = Trainer(cfg, rt, model, l2, IMAGENET)

    rng = np.random.default_rng(0)
    images = rng.normal(127, 60, (global_batch, 224, 224, 3)).astype(np.float32)
    labels = rng.integers(0, 1000, (global_batch,), dtype=np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    batch = rt.shard_batch((images, labels))

    # XLA's flop count for exactly this compiled step.  NB: for an
    # SPMD-partitioned executable cost_analysis reports the PER-DEVICE
    # module's flops, so it pairs with one chip's peak below (no
    # n_chips factor on either side).
    step_flops = None
    try:
        ca = trainer.train_step.lower(state, *batch).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        step_flops = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass

    # NB: sync via device_get of a non-donated output. On some remote
    # platforms block_until_ready returns before the computation
    # finishes; a host copy of the result cannot be faked.
    for _ in range(warmup):
        state, metrics = trainer.train_step(state, *batch)
    float(jax.device_get(metrics["loss"]))

    # Repeatability protocol (VERDICT r3 #5): N sync-cancelling timing
    # windows (windowed_step_seconds); the headline is the MEDIAN and
    # min/max expose the spread a single window silently bakes into
    # the tracked number.
    step_med, step_min, step_max, ipw, state = timed_train_steps(
        trainer.train_step, state, batch, windows=windows)
    mfu = None
    peak = peak_tflops(jax.devices()[0])
    if step_flops and peak:
        mfu = (step_flops / step_med) / (peak * 1e12)
    rate = lambda s: global_batch / s / n_chips
    return dict(per_chip=rate(step_med), per_chip_min=rate(step_max),
                per_chip_max=rate(step_min), windows=windows,
                iters_per_window=ipw, n_chips=n_chips,
                step_ms=step_med * 1e3, mfu=mfu)


def input_bench():
    """The input-pipeline measurement, run BEFORE any chip session in
    this process (VERDICT r3 weak #1: the r3 artifact measured it after
    the chip benches on this 1-core host and recorded 125.5 img/s where
    an idle-host run gives ~285-296 — contention garbage 2.4x off).
    bench_input.measure() itself takes best-of-N windows and reports
    the spread.

    r5 (VERDICT r4 #5): both configurations measured every round —
    fast_dct (JDCT_IFAST) as the nominal headline with the exact
    default alongside (`default`, `tuned_over_default`).  The r5 A/B
    RETIRED the r3 "+39%/core" fast_dct figure: against the r4
    fused-batch-op + uint8-wire pipeline it re-measures at +1-2%
    (window noise; README carries the retraction), so expect
    tuned_over_default ≈ 1.0.  scaled_decode stays off — it only
    engages on crops ≥2× target, rare on ImageNet-scale sources."""
    try:
        import bench_input
        tuned = bench_input.measure(fast_dct=True)
        default = bench_input.measure()
        tuned["default"] = default
        tuned["tuned_over_default"] = (
            round(tuned["value"] / default["value"], 3)
            if default.get("value") else None)
        return tuned
    except Exception as e:
        return {"error": str(e)[:200]}


def lm_bench():
    try:
        import bench_lm
        r = bench_lm.train_bench(remat=False)
        return {
            "metric": "lm_tokens_per_sec_per_chip",
            "value": round(r["per_chip_tps"], 0),
            "tps_min": round(r["per_chip_tps_min"], 0),
            "tps_max": round(r["per_chip_tps_max"], 0),
            "unit": "tokens/sec/chip",
            "step_ms": round(r["step_ms"], 2),
            "acc_metrics": False,
            "mfu": round(r["mfu"], 4) if r["mfu"] is not None else None,
            # true model flops incl. the Pallas attention kernels XLA's
            # count can't see (bench_lm docstring)
            "mfu_model": (round(r["mfu_model"], 4)
                          if r.get("mfu_model") is not None else None),
            "seq_len": bench_lm.SEQ,
        }
    except Exception as e:
        return {"error": str(e)[:200]}


def main():
    extras = {}
    if "--no-extras" not in sys.argv:
        # input pipeline first: it must see an idle host, not one
        # sharing its single core with chip-bench dispatch
        extras["input_pipeline"] = input_bench()
    # 256 measured fastest per-chip on v5 lite (2,432 img/s vs 2,431
    # @384, 2,306 @512, 2,386 @128); fall back on OOM
    err = None
    for batch in (256, 384, 128, 64):
        try:
            r = run_bench(batch)
            break
        except Exception as e:
            if not is_oom(e):
                raise
            err = e
            continue
    else:
        print(json.dumps({"metric": "resnet50_images_per_sec_per_chip",
                          "value": 0.0, "unit": "images/sec/chip",
                          "vs_baseline": 0.0, "error": str(err)[:200]}))
        sys.exit(1)
    out = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(r["per_chip"], 1),
        "value_min": round(r["per_chip_min"], 1),
        "value_max": round(r["per_chip_max"], 1),
        "windows": r["windows"],
        "iters_per_window": r["iters_per_window"],
        "unit": "images/sec/chip",
        "vs_baseline": round(r["per_chip"]
                             / BASELINE_IMG_PER_SEC_PER_DEVICE, 2),
        "step_ms": round(r["step_ms"], 2),
        "mfu": round(r["mfu"], 4) if r["mfu"] is not None else None,
        "per_chip_batch": batch,
        "n_chips": r["n_chips"],
        "device_kind": jax.devices()[0].device_kind,
    }
    out.update(extras)
    if "--no-extras" not in sys.argv:
        out["lm"] = lm_bench()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
