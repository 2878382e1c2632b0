#!/usr/bin/env python
"""ZeRO-2/3 contract smoke — the ci_check stage-13 gate.

Four arms, every bar enforced by nonzero exit:

  1. EQUIVALENCE — transformer_small trained on 4 virtual devices at
     zero stages 0, 2 and 3: the PER-STEP loss trajectories (trace
     ``train_loss`` events) agree within the documented tolerance
     (LOSS_RTOL — the only difference is float reassociation of the
     reduce-scatter vs the all-reduce).  The stage-3 arm also runs
     ``--zero_probe`` with sharded grad accumulation, feeding arm 4.
  2. DOES-NOT-FIT-REPLICATED — a workload/mesh point where the planner
     marks zero ∈ {0, 1} memory-INFEASIBLE at any accumulation depth
     (transformer_small, batch 16, on a simulated
     hosts=1,devices=8,hbm=280m mesh) and zero=3 with a sharded grad
     accumulator (microbatch 2) feasible; the same model+global batch
     then TRAINS under ZeRO-3 (grad_accum 2) on 8 virtual devices, and
     its per-step losses match a smaller-mesh (dp=1) replicated oracle
     within the tolerance — the ROADMAP headline: ZeRO-3 unlocks a
     model replicated DP must refuse.
  3. OVERLAP — the stage-3 probe's measured gauges: exposed comm
     (step wall minus the comm-stubbed twin's wall) must be STRICTLY
     below the serialized collective wall (standalone reduce-scatter +
     all-gather probes), i.e. train_exposed_comm_frac < 1.0 — the
     overlap win is a measured number, not a cost-model assumption.
  4. CALIBRATION (skipped under --fast) — ``plan_main --calibrate``
     on 2 virtual devices with --zero_stage 2 and 3: predicted vs
     measured step time inside the 2x contract for both stages.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import argparse      # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import tempfile      # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# documented equivalence tolerance: reduce-scatter reassociation vs the
# all-reduce — float-ulp territory, orders below any training signal
LOSS_RTOL = 1e-4

# the does-not-fit-replicated point (arm 2): transformer_small × batch
# 16 on this simulated mesh — zero ∈ {0,1} over budget, zero=3 fits
INFEASIBLE_MESH = "hosts=1,devices=8,hbm=280m,flops=100t"


def _losses(trace_dir: str) -> list:
    path = os.path.join(trace_dir, "trace_rank0.jsonl")
    out = []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "event" and \
                    rec.get("name") == "train_loss":
                out.append((rec["step"], rec["loss"]))
    return out


def _train(tmp: str, tag: str, **overrides) -> list:
    """One in-process training run; returns the per-step loss
    trajectory from its trace."""
    from dtf_tpu.cli import run
    from dtf_tpu.config import Config
    trace_dir = os.path.join(tmp, f"trace_{tag}")
    kw = dict(model="transformer_small", dataset="lm", batch_size=8,
              seq_len=64, train_steps=4, use_synthetic_data=True,
              skip_eval=True, skip_checkpoint=True, log_steps=1,
              model_dir="", optimizer="adamw", trace_dir=trace_dir)
    kw.update(overrides)
    run(Config(**kw))
    losses = _losses(trace_dir)
    assert losses, f"{tag}: trace carried no train_loss events"
    return losses


def _match(tag: str, got: list, ref: list) -> float:
    assert [s for s, _ in got] == [s for s, _ in ref], \
        f"{tag}: step sets differ"
    worst = 0.0
    for (s, a), (_, b) in zip(got, ref):
        dev = abs(a - b) / max(1.0, abs(b))
        worst = max(worst, dev)
        if dev > LOSS_RTOL:
            raise SystemExit(
                f"zero_smoke FAIL [{tag}]: step {s} loss {a!r} vs "
                f"replicated {b!r} (rel dev {dev:.2e} > {LOSS_RTOL})")
    print(f"  {tag}: per-step losses match (worst rel dev {worst:.2e})")
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python tools/zero_smoke.py")
    ap.add_argument("--fast", action="store_true",
                    help="skip the calibrate arms (the slow-test "
                         "wrapper's mode; CI runs the full contract)")
    args = ap.parse_args(argv)

    from dtf_tpu.obs.registry import default_registry
    from dtf_tpu.plan.cost_model import Plan, predict
    from dtf_tpu.plan.mesh_spec import mesh_spec
    from dtf_tpu.plan.model_stats import characterize
    from dtf_tpu.plan.search import search

    bars_failed = []

    with tempfile.TemporaryDirectory(prefix="zero_smoke_") as tmp:
        # ---- arm 1: ZeRO-2/3 ≡ replicated, per step ------------------
        print("zero_smoke [1/4]: ZeRO-2/3 ≡ replicated per-step loss "
              "(transformer_small, 4 virtual devices)")
        ref = _train(tmp, "z0", num_devices=4)
        z2 = _train(tmp, "z2", num_devices=4, zero_stage=2,
                    grad_accum_steps=2)
        _match("zero2(accum=2) vs replicated", z2, ref)
        z3 = _train(tmp, "z3", num_devices=4, zero_stage=3,
                    grad_accum_steps=2, zero_probe=True)
        _match("zero3(accum=2,probe) vs replicated", z3, ref)

        # ---- arm 3 (gauges from the arm-1 probe run) -----------------
        print("zero_smoke [3/4]: measured overlap — exposed comm below "
              "the serialized collective wall")
        reg = default_registry()
        needed = ("train_zero_scatter_wall_s", "train_zero_gather_wall_s",
                  "train_zero_comm_serialized_s", "train_exposed_comm_s",
                  "train_exposed_comm_frac")
        vals = {}
        for name in needed:
            g = reg.get(name)
            if g is None:
                raise SystemExit(f"zero_smoke FAIL: --zero_probe did "
                                 f"not record {name}")
            vals[name] = float(g.value)
        frac = vals["train_exposed_comm_frac"]
        print(f"  scatter {vals['train_zero_scatter_wall_s']*1e3:.2f} ms"
              f", gather {vals['train_zero_gather_wall_s']*1e3:.2f} ms, "
              f"serialized {vals['train_zero_comm_serialized_s']*1e3:.2f}"
              f" ms, exposed {vals['train_exposed_comm_s']*1e3:.2f} ms "
              f"(frac {frac:.2f})")
        if not 0.0 <= frac < 1.0:
            bars_failed.append(
                f"exposed_comm_frac {frac:.3f} not strictly below the "
                f"serialized collective wall — no measured overlap")

        # ---- arm 2: the does-not-fit-replicated headline -------------
        print("zero_smoke [2/4]: replicated-infeasible config trains "
              "under ZeRO-3 (mesh " + INFEASIBLE_MESH + ")")
        stats = characterize("transformer_small", seq_len=64)
        mesh = mesh_spec(INFEASIBLE_MESH)
        for m in (1, 2):
            for z in (0, 1):
                c = predict(Plan(data=8, zero=z, microbatch=m), stats,
                            mesh, 16, optimizer="adamw")
                if c.feasible:
                    raise SystemExit(
                        f"zero_smoke FAIL: feasibility window broke — "
                        f"zero={z} micro={m} fits at "
                        f"{c.peak_bytes >> 20} MiB (budget "
                        f"{c.hbm_budget_bytes >> 20} MiB)")
        c0 = predict(Plan(data=8), stats, mesh, 16, optimizer="adamw")
        c3 = predict(Plan(data=8, zero=3, microbatch=2), stats, mesh,
                     16, optimizer="adamw")
        if not c3.feasible:
            raise SystemExit(
                f"zero_smoke FAIL: zero3,micro=2 no longer fits — peak "
                f"{c3.peak_bytes >> 20} MiB vs budget "
                f"{c3.hbm_budget_bytes >> 20} MiB")
        best = next(r for r in search(stats, mesh, 16,
                                      optimizer="adamw") if r.feasible)
        assert best.plan.zero >= 2, best.plan.describe()
        print(f"  planner: zero 0/1 over the "
              f"{c0.hbm_budget_bytes >> 20} MiB budget at micro 1 and "
              f"2 (zero0 peak {c0.peak_bytes >> 20} MiB); zero3,micro=2"
              f" fits at {c3.peak_bytes >> 20} MiB; auto pick "
              f"{best.plan.describe()}")
        oracle = _train(tmp, "oracle", batch_size=16,
                        distribution_strategy="off")
        z3big = _train(tmp, "z3big", batch_size=16, num_devices=8,
                       zero_stage=3, grad_accum_steps=2)
        _match("zero3(dp=8) vs dp=1 oracle", z3big, oracle)

        # ---- arm 4: calibrate contract for zero ∈ {2,3} --------------
        if args.fast:
            print("zero_smoke [4/4]: SKIPPED (--fast)")
        else:
            print("zero_smoke [4/4]: plan_main --calibrate within 2x "
                  "for zero_stage 2 and 3")
            for stage in (2, 3):
                bench_dir = os.path.join(tmp, f"cal{stage}")
                cmd = [sys.executable, "-m", "dtf_tpu.cli.plan_main",
                       "--devices", "2", "--model", "transformer_small",
                       "--dataset", "lm", "--use_synthetic_data",
                       "--seq_len", "128", "--batch_size", "16",
                       "--optimizer", "adamw", "--zero_stage",
                       str(stage), "--calibrate", "--calibrate_steps",
                       "4", "--calibrate_tolerance", "2.0", "--top",
                       "0", "--benchmark_log_dir", bench_dir]
                env = dict(os.environ)
                env.pop("XLA_FLAGS", None)   # plan_main sets its own
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   cwd=REPO, env=env, timeout=900)
                tail = "\n".join(r.stdout.splitlines()[-4:])
                print("  " + tail.replace("\n", "\n  "))
                if r.returncode != 0:
                    raise SystemExit(
                        f"zero_smoke FAIL: calibrate zero_stage={stage} "
                        f"exited {r.returncode}\n{r.stdout}\n{r.stderr}")
                ratio = None
                for line in r.stdout.splitlines():
                    if "ratio" in line and "step time" in line:
                        ratio = float(line.rsplit("ratio", 1)[1]
                                      .strip(" ()"))
                assert ratio is not None, r.stdout

        if bars_failed:
            for b in bars_failed:
                print(f"zero_smoke FAIL — {b}", file=sys.stderr)
            return 1
    print("zero_smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
