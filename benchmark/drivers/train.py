"""A training cell: the program's own path (``build_model`` -> ``Trainer``
on a ``MeshRuntime`` -> ``Trainer.fit`` over the synthetic input iterator),
with the benchmark's clock in a callback.

``fit`` syncs with the device where it reads the loss, every
``log_steps`` steps; the callback stamps those boundaries.  The first
``warmup_windows`` boundaries are set-up (the first holds the compile or
the cache load).  The window opens at the last of them and closes at the
first boundary ``--seconds`` later, where the callback ends ``fit`` by an
exception: every step and every second in between counts.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import os
import time

from benchmark.lib import peaks
from benchmark.lib.runtime import RunContext, TracedWindow, memory_peak_bytes


class _WindowClosed(Exception):
    pass


class _Clock:
    """on_batch_end runs after fit's own sync at a log boundary."""

    def __init__(self, log_steps: int, warmup_windows: int, seconds: float,
                 traced: TracedWindow = None):
        self.log_steps, self.warmup_left = log_steps, warmup_windows
        self.seconds, self.traced = seconds, traced
        self.boundaries = []        # (monotonic time, step), window only
        self.t_open = self.step_open = self.wall_open = None
        self.t_close = self.step_close = self.wall_close = None

    def on_batch_end(self, batch_idx, logs):
        step = logs["step"]
        if step % self.log_steps:
            return
        if self.t_open is None:
            self.warmup_left -= 1
            if self.warmup_left:
                return
            if self.traced is not None:
                self.traced.start()
            self.t_open, self.step_open = time.monotonic(), step
            self.wall_open = time.time()
            self.boundaries.append((self.t_open, step))
            return
        now = time.monotonic()
        self.boundaries.append((now, step))
        if now - self.t_open >= self.seconds:
            self.t_close, self.step_close = now, step
            self.wall_close = time.time()
            if self.traced is not None:
                self.traced.stop()
            raise _WindowClosed()


def run(ctx: RunContext) -> dict:
    import jax

    from dtf_tpu.cli.runner import make_input_fns
    from dtf_tpu.config import parse_flags
    from dtf_tpu.data import get_dataset_spec
    from dtf_tpu.data.normalize import for_config
    from dtf_tpu.data.pipeline import DevicePrefetcher
    from dtf_tpu.models import build_model
    from dtf_tpu.obs import trace
    from dtf_tpu.runtime import initialize
    from dtf_tpu.runtime.mesh import DATA_AXIS
    from dtf_tpu.train import Trainer

    cell, wl, traffic = ctx.cell, ctx.cell.workload, ctx.cell.traffic
    toy = ctx.toy or {}
    model_kw = dict(cell.config["build_model"]["kwargs"])
    model_kw.update(toy.get("model_kwargs", {}))
    batch = toy.get("batch_size", traffic["batch_size"])
    seq_len = toy.get("seq_len", traffic.get("seq_len"))
    log_steps = toy.get("log_steps", wl["log_steps"])
    span_dir = os.path.join(ctx.out_dir, "spans")
    argv = ["--use_synthetic_data", "--skip_eval", "--skip_checkpoint",
            "--dtype", cell.config["dtype"], "--dataset", traffic["dataset"],
            "--distribution_strategy",
            toy.get("distribution_strategy", "tpu"),
            "--num_devices", str(cell.chips),
            "--batch_size", str(batch), "--log_steps", str(log_steps),
            "--train_steps", str(wl["max_steps"]),
            "--seed", str(ctx.key_seed), "--trace_dir", span_dir,
            "--verbose", "0"] + list(wl.get("flags", []))
    if seq_len:
        argv += ["--seq_len", str(seq_len)]
    cfg = parse_flags(argv, defaults=wl.get("defaults", {}))
    # the program's span stream carries the logged losses (`correct`
    # reads them) and the log_window spans; a handful of records a second
    trace.configure(span_dir)
    rt = initialize(cfg)
    spec = get_dataset_spec(cfg.dataset)
    spec = dataclasses.replace(
        spec, num_classes=cell.config["num_classes"],
        **({"seq_len": seq_len} if spec.is_sequence else {}))
    rt.shard_seq = spec.is_sequence
    model, l2 = build_model(
        cell.config["build_model"]["name"], num_classes=spec.num_classes,
        dtype=cfg.compute_dtype, bn_axis=DATA_AXIS if cfg.sync_bn else None,
        **model_kw)
    trainer = Trainer(cfg, rt, model, l2, spec,
                      normalize_fn=for_config(cfg, spec))
    train_fn, _ = make_input_fns(cfg, spec, batch)
    train_iter = train_fn()
    first = next(train_iter)
    state = trainer.init_state(jax.random.key(cfg.seed), first)
    prefetched = DevicePrefetcher(itertools.chain([first], train_iter), rt,
                                  buffer_size=2)

    traced = None
    seconds = ctx.seconds
    if ctx.traced:
        traced = TracedWindow(os.path.join(ctx.out_dir, "profile"))
        seconds = min(seconds, float(wl["trace_seconds"]))
    clock = _Clock(log_steps, wl["warmup_windows"], seconds, traced)
    try:
        trainer.fit(state, prefetched, callbacks=[clock])
    except _WindowClosed:
        pass
    else:
        raise RuntimeError(
            f"fit ended after {wl['max_steps']} steps before the window "
            f"closed; raise max_steps in the workload file")
    trace.disable()             # flush and close the span stream

    steps = clock.step_close - clock.step_open
    window_s = clock.t_close - clock.t_open
    samples_per_s = steps * batch / window_s
    records = trace.read_records(
        os.path.join(span_dir, "trace_rank0.jsonl"))
    losses = [(r["step"], r["loss"]) for r in records
              if r.get("name") == "train_loss"]
    in_window = [x for s, x in losses
                 if clock.step_open < s <= clock.step_close]
    bad_steps = sum(not math.isfinite(x) for _, x in losses)
    compiles_in_window = ctx.compiles.between(clock.t_open, clock.t_close)
    reasons = []
    if bad_steps:
        reasons.append(f"{bad_steps} logged losses are not finite")
    if not in_window or not in_window[-1] < losses[0][1]:
        reasons.append(f"the loss did not fall below its first value: "
                       f"first {losses[:1]}, window {in_window[-3:]}")
    if compiles_in_window:
        reasons.append(
            f"{compiles_in_window} compilations inside the window: "
            f"{ctx.compiles.names_between(clock.t_open, clock.t_close)}")
    device_kind = jax.devices()[0].device_kind
    flops = cell.family.train_flops_per_sample(cell.config, traffic)
    step_walls = [(t1 - t0) / (s1 - s0) for (t0, s0), (t1, s1)
                  in zip(clock.boundaries, clock.boundaries[1:])]
    ctx.note(phase="train_window", steps=steps, window_s=window_s,
             samples_per_s=samples_per_s,
             tokens_per_s=samples_per_s * (seq_len or 0),
             setup_s=clock.t_open - ctx.t_process, losses=losses[:2] + losses[-2:],
             compiles_total=ctx.compiles.total,
             compile_cache_hits=ctx.compiles.hits,
             compiles_in_window=compiles_in_window,
             flops_per_sample=flops, global_batch=batch)
    result = {
        "correct": not reasons, "reasons": reasons,
        "attempted": steps, "failed": bad_steps,
        # the one number compared: the window's last loss under the first
        "compared": {"loss_last": [in_window[-1] if in_window else None,
                                   losses[0][1] if losses else None]},
        "setup_s": clock.t_open - ctx.t_process,
        "memory_peak_bytes": memory_peak_bytes(),
        "readers": {"steps": steps, "window_s": window_s,
                    "step_walls_s": step_walls, "records": records,
                    "window_wall": (clock.wall_open, clock.wall_close),
                    "profile_dir": traced.trace_dir if traced else None},
    }
    if toy:
        result["end_to_end"] = {"samples_per_s": samples_per_s}
        return result
    peak = peaks.peaks_for(device_kind)["bf16_flops_per_s"]
    result["end_to_end"] = {
        "train_mfu": 100.0 * samples_per_s * flops / (cell.chips * peak)}
    return result
