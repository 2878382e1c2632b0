"""Recorded end-to-end run: real data → production pipelines → real TPU.

Produces RUN_r03-style evidence (the reference's equivalent is its
captured cluster logs, /root/reference/README.md:255-291 and
ps_server/log1.log): a full training run where the PRODUCTION input
path feeds the ATTACHED chip, with a checkpoint-resume in the middle,
a full-coverage padded eval at the end, and an input-bound ImageNet
run recording the chip-fed JPEG-decode rate.

Two phases, one JSON report:

1. CIFAR: ResNet-56 on CIFAR-10-binary-format data through
   `cli.cifar_main`'s `run()` (binary record parse → pad-crop-flip →
   per-image standardization → SPMD train step → orbax checkpoint →
   resume → padded sharded eval).  This environment has no network
   egress, so the genuine CIFAR-10 tarball cannot be fetched; the
   records are a *learnable* 10-class dataset written in the exact
   CIFAR wire format at the real cardinalities (50k train / 10k eval,
   cifar_preprocessing.py:30-41) — same evidence class as
   tests/test_convergence.py, at full scale on the real chip.
   Milestone: final eval top-1 >= 0.60 (vs 0.10 chance), with the
   resume continuing (not restarting) the step counter.

2. ImageNet: `--use_trivial_model` over synthetic JPEG TFRecord shards
   — the step is input-bound, so the steady-state examples/sec IS the
   end-to-end rate of the C++ fused decode path feeding the chip.

3. ImageNet × the REAL ResNet-50 (VERDICT r4 Missing #1): the flagship
   model training against the production JPEG path on the chip,
   input-bound, with the input/compute overlap fraction derived from
   three measured rates — trivial-model-on-JPEG (pure input),
   resnet50-on-synthetic (pure compute), resnet50-on-JPEG (the
   composition).  Perfect prefetcher overlap ⇒ the composed step time
   ≈ max(input, compute); zero overlap ⇒ their sum.

Usage: python run_record.py [--out RUN_r05.json] [--quick]
(--quick shrinks cardinalities for a smoke pass; the committed
artifact must come from a full run.)
"""

import io
import json
import os
import sys
import tempfile
import time

import numpy as np

CIFAR_TRAIN = 50_000
CIFAR_EVAL = 10_000
IMAGENET_IMAGES = 2_000
MILESTONE_TOP1 = 0.60


def write_cifar_binaries(root: str, num_train: int, num_eval: int):
    """Learnable 10-class data in the exact CIFAR binary wire format:
    1 label byte + 3072 CHW bytes per record (cifar_preprocessing.py
    :30-33).  Class structure: smooth per-class pattern fields plus
    heavy pixel noise — separable by a convnet, not trivially by pixel
    lookup."""
    from dtf_tpu.data import cifar as cifar_mod
    d = os.path.join(root, "cifar-10-batches-bin")
    os.makedirs(d, exist_ok=True)
    # smooth class patterns: random low-frequency fields.  Amplitude vs
    # noise picked so eval is comfortably learnable (the first recorded
    # run used 35-60 amplitude vs sigma-40 noise: the model hit 100%
    # train top-1 but the eval Bayes ceiling sat near 50%)
    prng = np.random.default_rng(7)
    yy, xx = np.mgrid[0:32, 0:32].astype(np.float32)
    patterns = np.zeros((10, 32, 32, 3), np.float32)
    for c in range(10):
        for ch in range(3):
            fy, fx = prng.uniform(0.05, 0.35, 2)
            py, px = prng.uniform(0, 2 * np.pi, 2)
            amp = prng.uniform(70, 100)
            patterns[c, :, :, ch] = (128 + amp * np.sin(fy * yy + py)
                                     * np.cos(fx * xx + px))

    def write(name, n, rng):
        labels = rng.integers(0, 10, n)
        imgs = patterns[labels] + rng.normal(0, 30, (n, 32, 32, 3))
        imgs = np.clip(imgs, 0, 255).astype(np.uint8)
        cifar_mod.write_binary_file(os.path.join(d, name), imgs, labels)

    rng = np.random.default_rng(42)
    per_file = num_train // 5
    for i in range(1, 6):
        write(f"data_batch_{i}.bin", per_file, rng)
    write("test_batch.bin", num_eval, rng)


def write_imagenet_shards(root: str, num_images: int, num_shards: int = 8):
    """Synthetic JPEG TFRecord shards in the production layout — the
    same recipe bench_input measures (shared generator)."""
    from bench_input import make_shards
    make_shards(root, num_shards=num_shards,
                images_per_shard=num_images // num_shards)


def steady_rate(stats: dict, batch_size: int):
    """images/sec over the steady-state tail of the per-step timestamp
    log (drops the first logged window, which carries compile time)."""
    log = stats.get("step_timestamp_log") or []
    if len(log) < 3:
        return None
    # BatchTimestamp entries logged every log_steps
    steps = [e.batch_index for e in log]
    times = [e.timestamp for e in log]
    dsteps = steps[-1] - steps[1]
    dt = times[-1] - times[1]
    if dt <= 0 or dsteps <= 0:
        return None
    return batch_size * dsteps / dt


def run_cifar(quick: bool):
    import dataclasses

    import dtf_tpu.data.base as data_base
    from dtf_tpu.cli import run
    from dtf_tpu.config import Config

    num_train = 2_560 if quick else CIFAR_TRAIN
    num_eval = 640 if quick else CIFAR_EVAL
    if quick:
        data_base._SPECS["cifar10"] = dataclasses.replace(
            data_base.CIFAR10, num_train=num_train, num_eval=num_eval)

    tmp = tempfile.mkdtemp(prefix="run_record_cifar_")
    write_cifar_binaries(tmp, num_train, num_eval)
    model_dir = os.path.join(tmp, "model")
    batch = 128
    common = dict(model="resnet56", dataset="cifar10", data_dir=tmp,
                  batch_size=batch, model_dir=model_dir, log_steps=20,
                  epochs_between_evals=100)  # eval only at the end

    # Epoch budget: PAST the first LR decay (epoch 91, schedules.py /
    # resnet_cifar_main.py parity).  Evaluating mid-schedule at lr 0.1
    # is meaningless with BN decay 0.997: the weights drift faster than
    # the running averages converge, so eval logits are garbage even at
    # train top-1 = 1.0 (measured: batch-stats eval 1.00, running-stats
    # eval 0.43 at epoch 6).  The reference recipe has the same
    # property — its eval numbers come after the decay, and so do ours.
    t0 = time.time()
    epochs1 = 1 if quick else 30
    stats1 = run(Config(**common, train_epochs=epochs1, skip_eval=True))
    phase1_s = time.time() - t0

    # phase 2: resume mid-run, train through the decay, full eval
    t0 = time.time()
    epochs2 = 2 if quick else 95
    stats2 = run(Config(**common, train_epochs=epochs2, resume=True))
    phase2_s = time.time() - t0

    steps_per_epoch = num_train // batch
    return {
        "model": "resnet56",
        "dataset": "cifar10-binary-format (synthetic learnable, "
                   "real cardinalities)",
        "num_train": num_train, "num_eval": num_eval,
        "batch_size": batch,
        "phase1_epochs": epochs1, "phase1_loss": stats1["loss"],
        "phase1_wall_s": round(phase1_s, 1),
        "resumed": True,
        "phase2_epochs_total": epochs2,
        "final_loss": stats2["loss"],
        "final_train_top1": stats2.get("training_accuracy_top_1"),
        "final_eval_top1": stats2.get("accuracy_top_1"),
        "eval_loss": stats2.get("eval_loss"),
        "milestone_top1": MILESTONE_TOP1,
        "milestone_met": (stats2.get("accuracy_top_1") or 0.0)
        >= MILESTONE_TOP1,
        "steady_images_per_sec": steady_rate(stats2, batch),
        "steps_per_epoch": steps_per_epoch,
        "phase2_wall_s": round(phase2_s, 1),
        # r4: the uint8 wire (Config.input_wire default) ships raw
        # pixels — 4x fewer host->device bytes than the f32 wire
        "input_wire": "uint8",
        "batch_transfer_mb": round(batch * 32 * 32 * 3 * 1 / 2**20, 2),
        "note": "host->device batches are uint8 (standardization runs "
                "on-chip); the f32 wire moves 4x these bytes",
    }


def run_imagenet(quick: bool):
    import dataclasses

    import dtf_tpu.data.base as data_base
    from dtf_tpu.cli import run
    from dtf_tpu.config import Config

    n_images = 400 if quick else IMAGENET_IMAGES
    tmp = tempfile.mkdtemp(prefix="run_record_imagenet_")
    write_imagenet_shards(tmp, n_images)
    batch = 64
    steps = 10 if quick else 60
    t0 = time.time()
    # clip_grad_norm: the trivial (linear) model on 1001-way labels
    # diverges under the warmup schedule otherwise — the measurement
    # here is the input rate, but the evidence should train sanely too
    stats = run(Config(model="resnet50", dataset="imagenet", data_dir=tmp,
                       use_trivial_model=True, batch_size=batch,
                       train_steps=steps, log_steps=10, skip_eval=True,
                       skip_checkpoint=True, model_dir="",
                       clip_grad_norm=1.0))
    wall = time.time() - t0
    # uint8 wire (r4 default): 9.2 MB per 64-batch vs the 36.8 MB f32
    # batches RUN_r03 measured as the bottleneck
    batch_mb = batch * 224 * 224 * 3 * 1 / 2**20
    rate = steady_rate(stats, batch)
    return {
        "model": "trivial (input-bound)",
        "dataset": "imagenet TFRecord+JPEG (synthetic shards)",
        "num_images": n_images, "batch_size": batch,
        "train_steps": steps,
        "loss_finite": bool(np.isfinite(stats["loss"])),
        "chip_fed_images_per_sec": rate,
        "avg_images_per_sec_incl_compile": stats.get("avg_exp_per_second"),
        "input_wire": "uint8",
        "batch_transfer_mb": round(batch_mb, 1),
        "implied_host_to_device_mb_per_sec": (
            round(rate / batch * batch_mb, 1) if rate else None),
        "note": "uint8 [B,224,224,3] batches are ~9.2 MB (the f32 "
                "wire moves 36.8 MB); the recorded rate exercises the "
                "uint8 wire end-to-end over the host's PCIe/DMA "
                "(bench_input.py measures the host-side decode rate)",
        "wall_s": round(wall, 1),
    }, tmp, rate


def _pure_compute_rate(batch: int) -> float:
    """On-device ResNet-50 step rate at this batch: bench.run_bench's
    device-resident sync-cancelled harness (the one copy of that
    protocol).  A synthetic-data `run()` does not measure this:
    synthetic ImageNet ships f32 [B,224,224,3] batches (36.8 MB) from
    the host every step, so it includes the host→device wire."""
    from bench import run_bench
    return run_bench(batch, warmup=3, windows=2)["per_chip"]


def run_imagenet_resnet50(quick: bool, shards_dir: str,
                          input_only_rate):
    """The flagship workload shape (VERDICT r4 Missing #1): ResNet-50
    itself training on the production JPEG path on the chip, alongside
    the decomposition that explains its rate:
      t_in   — the trivial-model-on-JPEG step time (host decode + uint8
               wire + dispatch; everything but real compute),
      t_c    — the pure on-device compute step time (device-resident
               inputs, sync-cancelled windows),
      t_real — the composed step time.
    compute_hidden_fraction = (t_in + t_c - t_real) / t_c when t_real
    <= t_in + t_c (1 = compute fully hidden behind input); any excess
    t_real - (t_in + t_c) > 0 is reported as serial_overhead_ms — the
    per-step cost the composition adds beyond its parts (the large
    program's per-step dispatch/sync)."""
    from dtf_tpu.cli import run
    from dtf_tpu.config import Config

    batch = 64
    steps = 10 if quick else 60
    compute_rate = _pure_compute_rate(batch)
    common = dict(model="resnet50", dataset="imagenet", batch_size=batch,
                  train_steps=steps, log_steps=10, skip_eval=True,
                  skip_checkpoint=True, model_dir="", dtype="bf16")
    # the composition: the real model against the JPEG path
    t0 = time.time()
    stats = run(Config(**common, data_dir=shards_dir))
    wall = time.time() - t0
    rate = steady_rate(stats, batch)
    hidden = overhead_ms = None
    if rate and compute_rate and input_only_rate:
        t_in = 1.0 / input_only_rate
        t_c = 1.0 / compute_rate
        t_real = 1.0 / rate
        if t_real <= t_in + t_c:
            # clamp: t_in (trivial-model run) slightly overestimates
            # pure input time, so noise can push the ratio past 1
            hidden = min((t_in + t_c - t_real) / t_c, 1.0)
            overhead_ms = 0.0
        else:
            hidden = 0.0
            # t_* are per-image seconds; report the per-STEP excess
            overhead_ms = (t_real - (t_in + t_c)) * batch * 1e3
    batch_mb = batch * 224 * 224 * 3 * 1 / 2**20
    return {
        "model": "resnet50 (the real flagship model)",
        "dataset": "imagenet TFRecord+JPEG (same shards as the "
                   "input-bound arm)",
        "batch_size": batch, "train_steps": steps,
        "loss_finite": bool(np.isfinite(stats["loss"])),
        "chip_fed_images_per_sec": rate,
        "compute_only_images_per_sec": round(compute_rate, 1),
        "input_only_images_per_sec": input_only_rate,
        "compute_hidden_fraction": (round(hidden, 3)
                                    if hidden is not None else None),
        "serial_overhead_ms_per_step": (round(overhead_ms, 1)
                                        if overhead_ms is not None
                                        else None),
        "input_wire": "uint8",
        "batch_transfer_mb": round(batch_mb, 1),
        "wire_mb_per_sec": (round(rate / batch * batch_mb, 1)
                            if rate else None),
        "note": "the full composition — TFRecord parse + C++ fused "
                "JPEG decode + uint8 wire + DevicePrefetcher feeding "
                "the REAL model's train step on the chip; where it is "
                "input-bound the binding constraint is host decode "
                "cores vs the chip's compute rate (bench_input "
                "cores_needed_per_chip)",
        "wall_s": round(wall, 1),
    }


def main():
    import jax
    quick = "--quick" in sys.argv
    out = "RUN_r05.json"
    if "--out" in sys.argv:
        i = sys.argv.index("--out")
        if i + 1 >= len(sys.argv):
            sys.exit("usage: run_record.py [--quick] [--out FILE]")
        out = sys.argv[i + 1]

    device = jax.devices()[0]
    # --imagenet_only: redo just the ImageNet arms and merge into an
    # existing report (keeps a completed multi-minute CIFAR phase).
    # The quick-vs-full merge refusal runs BEFORE any chip work.
    imagenet_only = "--imagenet_only" in sys.argv
    existing = None
    if imagenet_only and os.path.exists(out):
        with open(out) as f:
            existing = json.load(f)
        if bool(quick) != bool(existing.get("quick")):
            sys.exit(f"refusing to merge "
                     f"{'--quick' if quick else 'full-run'} ImageNet "
                     f"arms into the "
                     f"{'quick' if existing.get('quick') else 'full-run'} "
                     f"report {out!r} — the mixed artifact would "
                     f"misrepresent how its arms were measured; use a "
                     f"different --out")
    imagenet_report, shards_dir, input_rate = run_imagenet(quick)
    report = existing if existing is not None else {
        "what": "recorded end-to-end runs: production input pipelines "
                "feeding the attached chip, with mid-run checkpoint "
                "resume and full-coverage eval",
        "device_kind": device.device_kind,
        "platform": device.platform,
        "quick": quick,
    }
    if existing is None and not imagenet_only:
        report["cifar"] = run_cifar(quick)
    report["imagenet_input_bound"] = imagenet_report
    report["imagenet_resnet50"] = run_imagenet_resnet50(
        quick, shards_dir, input_rate)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    if "cifar" in report:
        ok = report["cifar"]["milestone_met"]
        print(f"\nmilestone eval top-1 >= {MILESTONE_TOP1}: "
              f"{'MET' if ok else 'NOT MET'}")
    else:
        # imagenet_only against a fresh out-file: no CIFAR phase ran,
        # so there is no milestone to claim either way
        ok = True
        print("\ncifar milestone: not evaluated (--imagenet_only, "
              "no prior report)")
    # --quick is a plumbing smoke pass (a 3-epoch budget cannot reach
    # the milestone); only full runs gate their exit code on it
    sys.exit(0 if (ok or quick) else 1)


if __name__ == "__main__":
    main()
