#!/usr/bin/env python
"""CI data-service smoke: sharded determinism + bit-exact imagenet resume.

One command, four assertions (the executable form of the data-service
contract — tools/ci_check.sh runs it as its data-service stage):

  1. the 2-worker sharded merged stream is BIT-IDENTICAL to the inline
     single-process stream (worker count never changes the stream)
  2. a baseline imagenet run (synthetic JPEG shards, trivial model,
     service pipeline) completes and logs a per-step loss trajectory
  3. the same run killed at step K by an injected hard crash
     (``--fault crash@step:K``) under the cli/launch.py supervisor —
     resumed with a DIFFERENT worker count — exits 0 and
     ``trace_main --check --allow injected_fault`` is green
  4. the killed+resumed loss trajectory is BIT-IDENTICAL to the
     baseline at every step: the PR-4 crash-exact guarantee holds on
     the flagship workload (the old imagenet path re-keyed best-effort)

Usage: python tools/data_service_smoke.py [--steps 8] [--kill 4]
                                          [--keep DIR]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_SHARDS = 2
IMAGES_PER_SHARD = 48


def check_worker_invariance(data: str) -> None:
    import numpy as np
    from dtf_tpu.data.service import ServiceStream
    inline = ServiceStream(data, 4, seed=3, num_shards=NUM_SHARDS,
                           num_workers=0)
    want = [next(inline) for _ in range(8)]
    inline.close()
    pooled = ServiceStream(data, 4, seed=3, num_shards=NUM_SHARDS,
                           num_workers=2)
    try:
        for i in range(8):
            im, lb = next(pooled)
            if not (np.array_equal(im, want[i][0])
                    and np.array_equal(lb, want[i][1])):
                raise SystemExit(
                    f"data_service_smoke: merged batch {i} differs "
                    f"between 2-worker and inline streams")
    finally:
        pooled.close()


def _train_cmd(data: str, model_dir: str, trace_dir: str, steps: int,
               extra=()):
    return [sys.executable, "-m", "dtf_tpu.cli.imagenet_main",
            "--use_trivial_model", "--data_dir", data,
            "--batch_size", "4", "--train_steps", str(steps),
            "--log_steps", "1", "--skip_eval", "--verbose", "0",
            "--distribution_strategy", "off",
            "--step_time_guard_factor", "0",
            "--input_num_shards", str(NUM_SHARDS),
            # baseline runs inline; the chaos run overrides with 2
            # workers, so the trajectory comparison ALSO pins worker-
            # count invariance across a kill + resume
            "--input_workers", "0",
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--kill", type=int, default=4,
                    help="crash step; must be a multiple of the "
                         "checkpoint interval (2) or the crash re-fires "
                         "on every resume")
    ap.add_argument("--keep", default="",
                    help="keep artifacts under this dir (default: temp, "
                         "removed)")
    args = ap.parse_args(argv)
    if args.kill % 2 or args.kill >= args.steps:
        print("data_service_smoke: --kill must be an even step below "
              "--steps", file=sys.stderr)
        return 2

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    base = args.keep or tempfile.mkdtemp(prefix="data_service_smoke_")
    os.makedirs(base, exist_ok=True)
    try:
        from dtf_tpu.testing.shards import make_shards
        # 48x64 sources keep decode cheap; the determinism contract does
        # not care about pixels
        data = make_shards(os.path.join(base, "shards"), NUM_SHARDS,
                           IMAGES_PER_SHARD, height=(48, 49),
                           width=(64, 65))

        print("== data_service_smoke [1/4]: 2-worker merged stream == "
              "inline stream ==")
        check_worker_invariance(data)

        print(f"== data_service_smoke [2/4]: baseline {args.steps}-step "
              f"imagenet run (service pipeline) ==")
        t0 = os.path.join(base, "t0")
        r = subprocess.run(
            _train_cmd(data, os.path.join(base, "m0"), t0, args.steps),
            capture_output=True)
        if r.returncode != 0:
            sys.stderr.write(r.stdout.decode()[-2000:])
            sys.stderr.write(r.stderr.decode()[-2000:])
            print("data_service_smoke: baseline run failed",
                  file=sys.stderr)
            return 1
        baseline = _loss_by_step(t0)
        if len(baseline) < args.steps:
            print(f"data_service_smoke: baseline logged "
                  f"{len(baseline)}/{args.steps} steps", file=sys.stderr)
            return 1

        print(f"== data_service_smoke [3/4]: crash@step:{args.kill} -> "
              f"supervised resume (2 workers) -> trace check ==")
        from dtf_tpu.cli import launch
        t1 = os.path.join(base, "t1")
        logs = os.path.join(base, "logs")
        rc = launch.launch_local(
            _train_cmd(data, os.path.join(base, "m1"), t1, args.steps,
                       extra=("--resume", "--checkpoint_steps", "2",
                              "--input_workers", "2",
                              "--fault", f"crash@step:{args.kill}")),
            num_processes=1, coordinator="localhost:0", log_dir=logs,
            devices_per_process=None, max_restarts=2,
            restart_backoff_s=0.05)
        if rc != 0:
            print(f"data_service_smoke: supervised chaos run exited "
                  f"{rc}", file=sys.stderr)
            return 1
        r = subprocess.run(
            [sys.executable, "-m", "dtf_tpu.cli.trace_main", t1,
             "--check", "--allow", "injected_fault"],
            capture_output=True)
        if r.returncode != 0:
            sys.stderr.write(r.stdout.decode()[-2000:])
            print("data_service_smoke: trace check failed",
                  file=sys.stderr)
            return 1

        print("== data_service_smoke [4/4]: loss trajectory "
              "bit-identical ==")
        resumed = _loss_by_step(t1)
        for step in sorted(baseline):
            if baseline[step] != resumed.get(step):
                print(f"data_service_smoke: step {step} diverged: "
                      f"baseline {sorted(baseline[step])} vs resumed "
                      f"{sorted(resumed.get(step, set()))}",
                      file=sys.stderr)
                return 1
        print(f"data_service_smoke: OK — {len(baseline)} steps "
              f"bit-identical across kill@{args.kill} + resume with a "
              f"different worker count")
        return 0
    finally:
        if not args.keep:
            shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
