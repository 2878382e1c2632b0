#!/usr/bin/env python
"""Elastic-training smoke — the ci_check stage-14 gate.

The headline contract, every bar enforced by nonzero exit: losing
capacity turns preemption into a THROUGHPUT DIP, not an outage.

  1. HOST LOSS → SHRINK: transformer_small under ZeRO-3 on 4 virtual
     devices, ``host_loss@step:4`` injected (self-SIGKILL — the
     unprompted-SIGKILL rank-exit pattern) under the ``cli/launch.py``
     supervisor with ``--elastic``: the supervisor classifies the loss
     apart from a crash and resumes on 2 devices at the sealed step-4
     checkpoint instead of crash-looping — the canonical (stage-0)
     ZeRO checkpoint re-slices onto the surviving mesh through the
     train/zero.py layout contract.
  2. TRAJECTORY-EXACT vs ORACLE: the per-step losses of the shrunken
     window are BIT-IDENTICAL to an oracle run launched FRESH on 2
     devices from the same checkpoint (both compute on the same
     topology, so even float reassociation agrees).  The 4-device
     prefix is additionally pinned against a 4-device prep run.
  3. GROW-BACK: when capacity re-announces (elastic_rejoin.json,
     written here once the shrunken run has sealed step-6), the
     supervisor drains the job at a checkpoint boundary (SIGTERM ⇒
     emergency sealed checkpoint ⇒ exit 75) and relaunches on 4
     devices; the run completes all steps, exit 0.
  4. ``trace_main --check --allow injected_fault --allow host_loss``
     (``device_loss`` for arm 5) is clean — the injected fault fired
     and NOTHING ELSE went anomalous — and the ``elastic_resume``
     trace events pin which steps ran on which topology.
  5. DEVICE LOSS arm: ``device_loss@step:2`` (EXIT_DEVICE_LOST, 76)
     classifies as device_loss and resumes on half the devices to
     completion.

Usage: python tools/elastic_smoke.py [--steps 20] [--keep DIR]
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the smoke's own process only supervises + reads traces; subprocess
# device counts come from launch_local's devices_per_process (an
# inherited XLA_FLAGS would fight it)
os.environ.pop("XLA_FLAGS", None)

import argparse      # noqa: E402
import glob          # noqa: E402
import json          # noqa: E402
import shutil        # noqa: E402
import subprocess    # noqa: E402
import tempfile      # noqa: E402
import threading     # noqa: E402
import time          # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FULL = 4          # full topology (virtual devices)
KILL = 4          # host-loss step; must be a multiple of the
                  # checkpoint interval (2) or the fault re-fires on
                  # every resume (exact-match chaos semantics)
GROW_AFTER = 6    # re-announce capacity once this step's checkpoint
                  # manifest is sealed (guarantees a 2-device window)


def _train_cmd(model_dir: str, trace_dir: str, steps: int, extra=()):
    return [sys.executable, "-m", "dtf_tpu.cli.lm_main",
            "--use_synthetic_data", "--model", "transformer_small",
            "--seq_len", "64", "--batch_size", "8",
            "--train_steps", str(steps), "--log_steps", "1",
            "--skip_eval", "--verbose", "0",
            "--step_time_guard_factor", "0",
            "--zero_stage", "3",
            "--resume", "--checkpoint_steps", "2",
            "--model_dir", model_dir, "--trace_dir", trace_dir, *extra]


def _loss_by_step(trace_dir: str) -> dict:
    out: dict = {}
    for path in glob.glob(os.path.join(trace_dir, "trace_rank*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("kind") == "event" and \
                        rec.get("name") == "train_loss":
                    out.setdefault(int(rec["step"]), set()).add(rec["loss"])
    return out


def _elastic_resumes(trace_dir: str) -> list:
    """[(step, devices)] from the elastic_resume trace events."""
    out = []
    for path in glob.glob(os.path.join(trace_dir, "trace_rank*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("kind") == "event" and \
                        rec.get("name") == "elastic_resume":
                    out.append((int(rec["step"]), int(rec["devices"])))
    return sorted(out)


def _subprocess_train(model_dir, trace_dir, steps, devices,
                      extra=()) -> int:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices}")
    return subprocess.run(_train_cmd(model_dir, trace_dir, steps,
                                     extra=extra),
                          env=env, cwd=REPO).returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--keep", default="",
                    help="keep artifacts under this dir (default: "
                         "temp, removed)")
    args = ap.parse_args(argv)

    from dtf_tpu.cli.launch import launch_local
    from dtf_tpu.cli.trace_main import main as trace_main
    from dtf_tpu.train import elastic

    base = args.keep or tempfile.mkdtemp(prefix="elastic_smoke_")
    os.makedirs(base, exist_ok=True)
    try:
        # ---- arm 1+3: host loss under the elastic supervisor --------
        print(f"== elastic_smoke [1/5]: host_loss@step:{KILL} on "
              f"{FULL} devices under --elastic — shrink to "
              f"{FULL // 2}, then grow back ==")
        m1 = os.path.join(base, "m1")
        t1 = os.path.join(base, "t1")
        logs = os.path.join(base, "logs")
        os.makedirs(logs, exist_ok=True)
        meta = os.path.join(m1, "checkpoints.meta",
                            f"manifest_{GROW_AFTER}.json")

        def announcer():
            # the healed host's agent, emulated: once the SHRUNKEN run
            # has sealed the step-6 checkpoint (so a 2-device window
            # provably exists), re-announce full capacity
            while not os.path.exists(meta):
                time.sleep(0.1)
            elastic.announce_rejoin(logs, FULL)

        th = threading.Thread(target=announcer, daemon=True)
        th.start()
        rc = launch_local(
            _train_cmd(m1, t1, args.steps,
                       extra=("--fault", f"host_loss@step:{KILL}")),
            num_processes=1, coordinator="localhost:0", log_dir=logs,
            devices_per_process=FULL, max_restarts=2,
            restart_backoff_s=0.1, elastic=True, min_devices=2)
        if rc != 0:
            print(f"elastic_smoke: supervised run exited {rc}",
                  file=sys.stderr)
            return 1
        ev_path = os.path.join(logs, "supervisor_events.jsonl")
        with open(ev_path) as f:
            ev = [json.loads(line) for line in f if line.strip()]
        shrinks = [e for e in ev if e["event"] == "elastic_shrink"]
        if not (shrinks and shrinks[0]["classification"] == "host_loss"
                and shrinks[0]["total_devices"] == FULL // 2):
            print(f"elastic_smoke: expected a host_loss shrink to "
                  f"{FULL // 2} devices; events: {shrinks}",
                  file=sys.stderr)
            return 1
        if not any(e["event"] == "elastic_grow" for e in ev):
            print("elastic_smoke: the run never grew back "
                  "(capacity re-announce not consumed?)",
                  file=sys.stderr)
            return 1
        resumes = _elastic_resumes(t1)
        if (len(resumes) != 2 or resumes[0] != (KILL, FULL // 2)
                or resumes[1][1] != FULL
                or resumes[1][0] < GROW_AFTER):
            print(f"elastic_smoke: elastic_resume events "
                  f"{resumes} do not match (shrink at {KILL} to "
                  f"{FULL // 2}, grow at >= {GROW_AFTER} to {FULL})",
                  file=sys.stderr)
            return 1
        grow_step = resumes[1][0]
        got = _loss_by_step(t1)
        want_steps = set(range(1, args.steps + 1))
        if set(got) != want_steps or any(len(v) != 1
                                         for v in got.values()):
            print(f"elastic_smoke: trajectory incomplete or "
                  f"double-trained: {sorted(got)}", file=sys.stderr)
            return 1
        print(f"  shrink at step {KILL} -> {FULL // 2} devices, grow "
              f"at step {grow_step} -> {FULL}; all {args.steps} steps "
              f"trained exactly once")

        # ---- arm 2: the shrunken window vs a fresh N/2 oracle --------
        print(f"== elastic_smoke [2/5]: steps {KILL + 1}..{grow_step} "
              f"bit-identical to a fresh {FULL // 2}-device oracle "
              f"from the same checkpoint ==")
        prep_m = os.path.join(base, "prep_m")
        prep_t = os.path.join(base, "prep_t")
        # the prep run must be CONFIG-IDENTICAL to the elastic run's
        # first phase (train_steps feeds the LR schedule), so it runs
        # the same 20-step command and stops at step KILL via an
        # injected crash AFTER the sealed checkpoint — its model_dir
        # is then byte-for-byte the checkpoint the elastic run (and
        # the oracle) resumed from
        rc_prep = _subprocess_train(prep_m, prep_t, args.steps, FULL,
                                    extra=("--fault",
                                           f"crash@step:{KILL}"))
        from dtf_tpu.chaos import EXIT_INJECTED_CRASH
        if rc_prep != EXIT_INJECTED_CRASH:
            print(f"elastic_smoke: prep run exited {rc_prep} (expected "
                  f"the injected crash, {EXIT_INJECTED_CRASH})",
                  file=sys.stderr)
            return 1
        prep = _loss_by_step(prep_t)
        for step in range(1, KILL + 1):
            if got[step] != prep[step]:
                print(f"elastic_smoke: 4-device prefix diverged at "
                      f"step {step}: {sorted(got[step])} != "
                      f"{sorted(prep[step])}", file=sys.stderr)
                return 1
        oracle_m = os.path.join(base, "oracle_m")
        oracle_t = os.path.join(base, "oracle_t")
        # the oracle resumes from a COPY of the prep checkpoint — the
        # same bytes the elastic run resumed from (deterministic
        # training makes the two step-K checkpoints identical; the
        # prefix check above is the witness)
        shutil.copytree(prep_m, oracle_m)
        if _subprocess_train(oracle_m, oracle_t, args.steps,
                             FULL // 2) != 0:
            print("elastic_smoke: oracle run failed", file=sys.stderr)
            return 1
        oracle = _loss_by_step(oracle_t)
        for step in range(KILL + 1, grow_step + 1):
            if got[step] != oracle[step]:
                print(f"elastic_smoke: step {step} loss diverged from "
                      f"the fresh N/2 oracle: {sorted(got[step])} != "
                      f"{sorted(oracle[step])}", file=sys.stderr)
                return 1
        print(f"  steps {KILL + 1}..{grow_step} bit-identical to the "
              f"oracle (and the {FULL}-device prefix to the prep run)")

        # ---- arm 4: anomaly cleanliness ------------------------------
        print("== elastic_smoke [3/5]: trace_main --check --allow "
              "injected_fault --allow host_loss ==")
        if trace_main([t1, "--check", "--allow", "injected_fault",
                       "--allow", "host_loss"]) != 0:
            print("elastic_smoke: elastic trace contains unexpected "
                  "anomalies", file=sys.stderr)
            return 1
        if trace_main([t1, "--check"]) == 0:
            print("elastic_smoke: injected fault never fired",
                  file=sys.stderr)
            return 1

        # ---- arm 5: device loss (exit 76) ----------------------------
        print("== elastic_smoke [4/5]: device_loss@step:2 (exit 76) "
              "classifies + resumes on half the devices ==")
        m2 = os.path.join(base, "m2")
        t2 = os.path.join(base, "t2")
        logs2 = os.path.join(base, "logs2")
        rc = launch_local(
            _train_cmd(m2, t2, 6,
                       extra=("--fault", "device_loss@step:2")),
            num_processes=1, coordinator="localhost:0", log_dir=logs2,
            devices_per_process=FULL, max_restarts=1,
            restart_backoff_s=0.1, elastic=True, min_devices=2)
        if rc != 0:
            print(f"elastic_smoke: device-loss arm exited {rc}",
                  file=sys.stderr)
            return 1
        with open(os.path.join(logs2, "supervisor_events.jsonl")) as f:
            ev2 = [json.loads(line) for line in f if line.strip()]
        if not any(e["event"] == "elastic_shrink"
                   and e["classification"] == "device_loss"
                   for e in ev2):
            print("elastic_smoke: device loss not classified/shrunk",
                  file=sys.stderr)
            return 1
        got2 = _loss_by_step(t2)
        if set(got2) != set(range(1, 7)) or any(len(v) != 1
                                                for v in got2.values()):
            print(f"elastic_smoke: device-loss arm trajectory "
                  f"incomplete: {sorted(got2)}", file=sys.stderr)
            return 1

        print("== elastic_smoke [5/5]: device-loss trace cleanliness ==")
        if trace_main([t2, "--check", "--allow", "injected_fault",
                       "--allow", "device_loss"]) != 0:
            print("elastic_smoke: device-loss trace contains "
                  "unexpected anomalies", file=sys.stderr)
            return 1

        print(f"elastic_smoke: OK — host loss at step {KILL} on {FULL} "
              f"devices resumed on {FULL // 2} (trajectory "
              f"bit-identical to the fresh oracle), grew back at step "
              f"{grow_step}; device loss resharded too")
        return 0
    finally:
        if not args.keep:
            shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
