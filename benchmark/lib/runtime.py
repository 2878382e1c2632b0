"""What both drivers need of the process they run in: the cell's files,
the device check, the count of compilations, the traced window and the
memory peak."""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
from typing import Optional

from benchmark import families

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
# the largest whole number jax.random.key takes without 64-bit mode
SEED_MODULUS = 2_147_483_629


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files its names point to."""
    name: str
    chips: int
    config_name: str
    config: dict        # benchmark/configs/<config>.json (via BENCHMARK's file)
    traffic_name: str
    traffic: dict       # benchmark/traffic/<traffic>.json
    workload: dict      # benchmark/workloads/<cell>.json
    per_layer: list     # names of the per-layer metrics the cell reports
    family: object      # benchmark/families/<config's family>.py
    root: str           # the checkout the files came from


def load_cell(benchmark: dict, name: str, root: str = ROOT) -> Cell:
    """Finds every file of the cell by the names in BENCHMARK.json, under
    ``root`` (a checkout; the tests load the README's examples from a
    copy)."""
    bench_dir = os.path.join(root, "benchmark")
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r} (has "
                       f"{[w['name'] for w in benchmark['workloads']]})")
    cfg = next(c for c in benchmark["configs"]
               if c["name"] == entry["config"])
    per_layer = [m["name"] for m in benchmark["per_layer"]
                 if "workloads" not in m or name in m["workloads"]]
    config = load_json(os.path.join(root, cfg["file"]))
    workload = load_json(os.path.join(bench_dir, "workloads",
                                      name + ".json"))
    family = families.load(config["family"], root)
    if config.get("reference") is None and workload["driver"] == "serve":
        raise ValueError(
            f"cell {name!r} serves {cfg['file']}, which names no plain "
            f"reference to hold the served tokens to")
    return Cell(
        name=name, chips=entry["chips"], config_name=cfg["name"],
        config=config, traffic_name=entry["traffic"],
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       entry["traffic"] + ".json")),
        workload=workload, per_layer=per_layer, family=family, root=root)


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    out_dir: str            # benchmark/out/<cell>/, for what is not a result
    t_process: float        # time.monotonic() at process start
    compiles: "CompileWatch"
    toy: Optional[dict] = None   # rehearse.py's overrides; None on the chip

    @property
    def key_seed(self) -> int:
        return self.seed % SEED_MODULUS

    def note(self, **fields) -> None:
        """A line that is not the result: printed early, kept in out/."""
        line = json.dumps({"note": self.cell.name, **fields})
        print(line, flush=True)
        with open(os.path.join(self.out_dir, "notes.jsonl"), "a") as f:
            f.write(line + "\n")


class CompileWatch:
    """Stamps every request to compile a program (a hit of the persistent
    cache included: a hit still means a shape that was not warmed up), so
    that a driver can count those that fell inside its window, and keeps
    each program's name from JAX's own "Compiling <name> ..." log line,
    so that a run that is not ``correct`` for it says which."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"
    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._stamps = []
        self._named = []        # (monotonic time, program name)
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        # JAX logs the line at DEBUG unless jax_log_compiles is on; this
        # filter lets that one line through to nobody but this object
        logger = logging.getLogger(self.LOGGER)
        logger.setLevel(logging.DEBUG)
        logger.addFilter(self._on_log)

    def _on_event(self, event, **_):
        if event == self.EVENT:
            with self._lock:
                self._stamps.append(time.monotonic())
        elif event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def _on_log(self, record) -> bool:
        if str(record.msg).startswith("Compiling %s"):
            with self._lock:
                self._named.append((time.monotonic(), str(record.args[0])))
        return record.levelno > logging.DEBUG

    @property
    def total(self) -> int:
        with self._lock:
            return len(self._stamps)

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t <= t1 for t in self._stamps)

    def names_between(self, t0: float, t1: float) -> list:
        with self._lock:
            return [n for t, n in self._named if t0 <= t <= t1]


class Ticker(threading.Thread):
    """A thread that asks to sleep ``every_s`` again and again and keeps
    the longest it overslept.  A window whose engine stood still says here
    whether the whole process did (the host took its cores: this thread was
    late too) or only the engine's thread (a call into the runtime or the
    device: this thread was on time).  ``/proc``'s schedstat and steal time
    read 0 on the chip's machine (my chip run, PR 26), so they cannot."""

    def __init__(self, every_s: float = 0.05):
        super().__init__(daemon=True, name="bench-ticker")
        self.every_s = every_s
        self.late_max_s = 0.0
        self._halt = threading.Event()
        self.start()

    def run(self):
        while not self._halt.is_set():
            t = time.monotonic()
            self._halt.wait(self.every_s)
            self.late_max_s = max(self.late_max_s,
                                  time.monotonic() - t - self.every_s)

    def close(self) -> float:
        self._halt.set()
        self.join(timeout=5)
        return self.late_max_s


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits the run where it is not the
    TPU the cell asks for.  No fallback: a CPU number under a device
    metric's name is worse than none."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found platform {devices[0].platform!r}, not a "
            f"TPU (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            f"no result")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chips, JAX found "
            f"{len(devices)}; no result")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip: what the allocator had in use at
    its peak (arrays: weights, state, cache, batches) plus the most it had
    reserved for programs' temporaries.  On the v5e the first excludes the
    second: ResNet-50 at batch 256 reads 2.29e9 in use and 9.10e9 reserved,
    and the step compiled for the v5e needs 9.14e9 of temporaries (my chip
    run and compiled program, PR 23).  The two peaks need not coincide, so
    this is an upper reading, held to the device's limit."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        total = (int(stats.get("peak_bytes_in_use", 0))
                 + int(stats.get("peak_bytes_reserved", 0)))
        peaks.append(min(total, int(stats.get("bytes_limit", total))))
    return max(peaks)


def memory_stats_of_device0() -> dict:
    import jax
    return dict(jax.devices()[0].memory_stats() or {})


class TracedWindow:
    """The profiler round a window.  ``start`` returns once the profiler
    runs and a marker program has run on every device; ``stop`` runs the
    marker again and ends the profile.  The markers pin the window's two
    edges on the device's own clock (lib/xplane.py)."""

    def __init__(self, trace_dir: str):
        import jax
        import jax.numpy as jnp
        self.trace_dir = trace_dir
        self._x = [jax.device_put(jnp.zeros((8, 128), jnp.float32), d)
                   for d in jax.devices()]
        self._mark = jax.jit(lambda x: x + 1.0)
        self._run_marks()           # compiled in set-up, not in the window

    def _run_marks(self):
        for x in self._x:
            self._mark(x).block_until_ready()

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the device timeline is what
        # is read.  Host tracing at level 1 slowed fit's host loop 4.6x
        # (ResNet-50 step 460 ms traced against 99.5 ms; at level 0 the
        # traced step is 99.5 ms too — my chip runs, PR 23)
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._run_marks()

    def stop(self):
        import jax
        self._run_marks()
        jax.profiler.stop_trace()
