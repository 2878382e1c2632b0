"""The engine thread's laps laid on device 0's timeline: which lap of the
program's ``serve_iteration`` spans each idle stretch of the chip fell
under, and what a prefill chunk takes on the device.

The program stamps a ``serve_iteration`` span a turn of its engine thread
with the host's clock (``ts``, and laps as offsets from it); the profile
stamps device 0's ops and programs (the ``XLA Modules`` line: one event a
program run) with its own.  They are joined after the run, by causality:

* *Pair.*  The n-th run of the decode body on the module line is the
  launch of the ``serve_iteration`` whose ``step`` ordinal is k + n.
* *Bound.*  With device = host + delta, a body ends no later than its
  tokens reached the host (the end of the turn's ``ready`` lap); it
  starts no sooner than its call (the end of the ``launch_args`` lap);
  and the first program of any name to run after the body before it
  starts no sooner than the first lap that launches one (``chunk_host``,
  ``launch_args``) to begin after that body's ``ready`` lap, in whichever
  turn.  So delta lies in
  [max_n(end_n - ready_n), min_n(start_n - launch_n)].  A wrong k
  empties the interval: k is the one that does not (the nearest to the
  driver's wall stamp of the window's start, should two hold).  delta is
  the interval's middle; its width — one dispatch and one read-back, the
  round trip no causality can split — is the error of every attribution
  below, and the JSON gives the idle seconds by lap at both its ends too.
* *Attribute.*  Every idle interval of the op line inside the traced
  window (the union rule of ``lib/xplane.py``) is cut by the laps under
  it; what lies under no lap — between two turns, where the tracer
  writes its record — is ``unattributed``.
* *Chunks.*  The n-th run of a chunk body with the turn whose ``chunk``
  ordinal is kc + n, under the same two bounds and delta's interval: a
  chunk starts no sooner than its turn's first ``chunk_host`` lap and
  ends no later than the next lap that waited for the device
  (``chunk_sync``, ``ready``).  Each run then has its
  ``serve_prefill_chunk`` span (the turn's child): the JSON sets the
  program's time on the device beside the span's, by the chunk's tokens.

The turn's counts (rows by phase, admissions, retirements, queue depth,
pages in use) are summed over the window into the JSON, and each of the
ten longest gaps carries those of the turn under its middle.

``analyse`` writes ``benchmark/out/<cell>/idle_by_lap.json`` and is made
once a run.  ``read`` returns None where the run traced nothing (no span
record and no profile: the harness's probe), and for what is read off the
device where the profile holds no device.  A run that has span records
but no ``serve_iteration`` among them, or whose pairing fails, reads 0
attributed (``run.py`` prints no line at all for a traced run that lacks
a declared metric): the JSON says why under ``why`` / ``chunk_why``, and
so does a line on the run's stderr.  A 0 in ``idle_host_pct``,
``idle_wait_pct`` or ``prefill_chunk_device_ms`` is read against those.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys

import numpy as np

from benchmark.lib import xplane

MODULE_LINE = "XLA Modules"
DECODE_BODY = "_decode_paged_impl"      # serve/decode.py keeps these names
CHUNK_BODY = "_chunk_impl"
# blocked on the device or on the queue: not the host's own work
NOT_HOST = ("wait", "ready", "chunk_sync")
ORDINALS = ("step", "chunk")
SEARCH = 3          # steps either side of the wall stamp's guess for k
EDGE = 2            # decode runs at each edge of the profile left unpaired


class Unpaired(Exception):
    """The two clocks could not be joined; the message says why."""


def turns_of(records) -> list:
    """The ``serve_iteration`` records, oldest first."""
    return sorted((r for r in records if r.get("kind") == "span"
                   and r.get("name") == "serve_iteration" and "laps" in r),
                  key=lambda r: r["ts"])


def lap_table(turns):
    """(starts, ends, names, steps) of every lap of every turn on the
    host's clock, in time order: a lap starts where the one before it
    ended; ``steps`` holds its turn's ``step`` ordinal, or None."""
    starts, ends, names, steps = [], [], [], []
    for r in turns:
        t = r["ts"]
        for name, seconds in r["laps"]:
            starts.append(t)
            t += seconds
            ends.append(t)
            names.append(name)
            steps.append(r.get("step"))
    return np.asarray(starts), np.asarray(ends), names, steps


def turn_counts(turn) -> dict:
    """The whole-number attributes of a turn's record: its counts and the
    ordinals of its launches."""
    return {k: v for k, v in turn.items()
            if type(v) is int and k != "rank"}


def lap_seconds(turns, window) -> tuple:
    """({lap: seconds}, decode launches, {count: {total, mean, max}}) over
    the turns that started inside ``window`` (host clock)."""
    total, launches, counts = {}, 0, {}
    for r in turns:
        if window[0] <= r["ts"] <= window[1]:
            launches += "step" in r
            for name, seconds in r["laps"]:
                total[name] = total.get(name, 0.0) + seconds
            for key, v in turn_counts(r).items():
                if key not in ORDINALS:
                    counts.setdefault(key, []).append(v)
    return total, launches, {
        key: {"total": sum(v), "mean": sum(v) / len(v), "max": max(v)}
        for key, v in counts.items()}


def _device_lines(trace: dict):
    first = xplane.first_device([p["name"] for p in trace["planes"]])
    plane = next(p for p in trace["planes"] if p["name"] == first)
    return {ln["name"]: ln["events"] for ln in plane["lines"]}


def program_runs(trace: dict, body: str = "") -> list:
    """[start_s, end_s] of every run on device 0 of the programs whose
    name holds ``body``, in time order."""
    events = _device_lines(trace).get(MODULE_LINE)
    if not events:
        raise Unpaired(f"device 0's plane has no line {MODULE_LINE!r} with "
                       f"events")
    return sorted([s / 1e9, (s + d) / 1e9] for n, s, d in events
                  if body in n)


def first_after_each(runs, programs) -> list:
    """For each run of ``runs`` but the first, the start of the first
    program of ``programs`` (every run of the module line, sorted) to
    start after the run before it ended: the run's own, where nothing
    came between."""
    starts = [s for s, _ in programs]
    return [starts[bisect.bisect_left(starts, before[1])]
            for before in runs[:-1]]


def busy_intervals(events):
    """(starts, ends) of the union of the op line's events (sorted by
    start), in seconds: the window runs from starts[0] to ends[-1]."""
    if not events:
        raise Unpaired("the op line of device 0 holds no event")
    s = np.asarray([e[1] for e in events]) / 1e9
    e = np.maximum.accumulate(s + np.asarray([e[2] for e in events]) / 1e9)
    new = np.concatenate(([True], s[1:] > e[:-1]))
    last = np.concatenate((new[1:], [True]))
    return s[new], e[last]


def _busy_before(t, starts, ends):
    """Busy seconds of the union before each time of ``t``."""
    cum = np.concatenate(([0.0], np.cumsum(ends - starts)[:-1]))
    i = np.maximum(np.searchsorted(starts, t, side="right") - 1, 0)
    return cum[i] + np.clip(t - starts[i], 0.0, (ends - starts)[i])


def pair(laps, runs, programs, guess_delta):
    """(k, lo, hi, pairs): the offset of step ordinals and delta's
    interval (module docstring).  ``laps``: the lap table; ``runs``: the
    decode body's, of which ``programs`` holds every program's."""
    starts, ends, names, steps = laps
    launches = [t for t, n in zip(starts, names)
                if n in ("chunk_host", "launch_args")]
    # (a turn without a step closes launch_args too where it ran a job
    # that called the decoder itself: the benchmark's logit replay)
    call = {s: t for t, n, s in zip(ends, names, steps)
            if n == "launch_args" and s is not None}
    ready = {s: t for t, n, s in zip(ends, names, steps) if n == "ready"}
    if not call or len(runs) < 2 * EDGE + 2:
        raise Unpaired(f"{len(call)} turns launched a step and the "
                       f"module line holds {len(runs)} runs of the decode "
                       f"body")

    def launch(step):       # the first launching lap after the step before
        at = bisect.bisect_left(launches, ready.get(step - 1, call[step]))
        return launches[at] if at < len(launches) else call[step]
    # the runs at the profile's edges may be cut by them, and the
    # driver's marker programs run beside them
    inner = runs[EDGE:-EDGE]
    first_program = first_after_each(runs, programs)[EDGE - 1:-EDGE]
    # the step whose call came last before the first paired run began
    order = sorted(call)
    at = bisect.bisect_right([call[s] for s in order],
                             inner[0][0] - guess_delta) - 1
    guess = order[max(at, 0)] - EDGE
    found = []
    for k in range(guess - SEARCH, guess + SEARCH + 1):
        steps = [k + EDGE + n for n in range(len(inner))]
        if any(s not in call or s not in ready for s in steps):
            continue
        lo = max(run[1] - ready[s] for run, s in zip(inner, steps))
        hi = min(min(run[0] - call[s], p - launch(s))
                 for run, p, s in zip(inner, first_program, steps))
        if lo <= hi:
            found.append((abs((lo + hi) / 2 - guess_delta), k, lo, hi))
    if not found:
        raise Unpaired(
            f"no offset of step ordinals within {SEARCH} of {guess} leaves "
            f"delta an interval over {len(inner)} runs of the decode body")
    _, k, lo, hi = min(found)
    return k, lo, hi, len(inner)


def idle_under(busy, laps, delta):
    """({lap: idle seconds}, {lap: busy seconds}) of device 0 under each
    lap name, the laps moved onto the device's clock by ``delta`` and cut
    to the window."""
    b_start, b_end = busy
    lo = np.clip(laps[0] + delta, b_start[0], b_end[-1])
    hi = np.clip(laps[1] + delta, b_start[0], b_end[-1])
    under = _busy_before(hi, b_start, b_end) - _busy_before(lo, b_start,
                                                            b_end)
    idle_by, busy_by = {}, {}
    for name, i, b in zip(laps[2], (hi - lo) - under, under):
        idle_by[name] = idle_by.get(name, 0.0) + float(i)
        busy_by[name] = busy_by.get(name, 0.0) + float(b)
    return idle_by, busy_by


def longest_gaps(busy, laps, turns, delta, keep=10):
    """The ``keep`` longest idle intervals of the window, each with its
    place in it, the seconds of every lap that fills it, and the counts
    of the turn under its middle."""
    b_start, b_end = busy
    l_start, l_end, names = laps[0] + delta, laps[1] + delta, laps[2]
    t_start = [r["ts"] + delta for r in turns]
    out = []
    for length, a, b in sorted(zip(b_start[1:] - b_end[:-1], b_end[:-1],
                                   b_start[1:]), reverse=True)[:keep]:
        inside = {}
        for i in range(int(np.searchsorted(l_end, a, side="right")),
                       int(np.searchsorted(l_start, b, side="left"))):
            part = min(b, l_end[i]) - max(a, l_start[i])
            inside[names[i]] = inside.get(names[i], 0.0) + float(part)
        under = turns[max(bisect.bisect_right(t_start, (a + b) / 2) - 1, 0)]
        out.append({"seconds": float(length),
                    "at_s": float(a - b_start[0]), "laps": inside,
                    "turn": turn_counts(under)})
    return out


def pair_chunks(turns, laps, runs, lo, hi):
    """(kc, [(turn, run)]): each run of a chunk body with the turn that
    launched it (module docstring), delta known to lie in [lo, hi]."""
    syncs = [t for t, n in zip(laps[1], laps[2])
             if n in ("chunk_sync", "ready")]
    by = {}             # ordinal → (launch began, waited for by, turn)
    for r in turns:
        if "chunk" in r:
            began = r["ts"]
            for name, seconds in r["laps"]:
                if name == "chunk_host":
                    break
                began += seconds
            at = bisect.bisect_right(syncs, began)
            by[r["chunk"]] = (began,
                              syncs[at] if at < len(syncs) else np.inf, r)
    if not by or not runs:
        raise Unpaired(f"{len(by)} turns launched a chunk and the module "
                       f"line holds {len(runs)} runs of a chunk body")
    # the chunk launched last before the first run began
    order = sorted(by)
    at = bisect.bisect_right([by[c][0] for c in order],
                             runs[0][0] - (lo + hi) / 2) - 1
    guess = order[max(at, 0)]
    for kc in sorted(range(guess - SEARCH, guess + SEARCH + 1),
                     key=lambda kc: abs(kc - guess)):
        mine = [by.get(kc + n) for n in range(len(runs))]
        if all(m is not None and m[0] + lo <= run[0] and run[1] <= m[1] + hi
               for m, run in zip(mine, runs)):
            return kc, [(m[2], run) for m, run in zip(mine, runs)]
    raise Unpaired(
        f"no offset of chunk ordinals within {SEARCH} of {guess} holds "
        f"every one of {len(runs)} runs of a chunk body between its "
        f"turn's launch and the wait after it")


def chunk_device(pairs, records) -> dict:
    """The chunk programs' time on the device: the median over the paired
    runs, and by the chunk's tokens beside the ``serve_prefill_chunk``
    span that launched it (which ends where the call returns)."""
    spans = {r.get("parent_span"): r for r in records
             if r.get("name") == "serve_prefill_chunk"}
    by = {}
    for turn, (start, end) in pairs:
        span = spans.get(turn.get("span_id"), {})
        row = by.setdefault(span.get("tokens"), ([], []))
        row[0].append(1e3 * (end - start))
        row[1].append(1e3 * span.get("dur_s", 0.0))
    return {
        "prefill_chunk_device_ms": statistics.median(
            1e3 * (end - start) for _, (start, end) in pairs),
        "chunk_runs": len(pairs),
        "chunk_device_ms_by_tokens": {
            str(tokens): {"runs": len(dev),
                          "device_ms": statistics.median(dev),
                          "span_ms": statistics.median(span)}
            for tokens, (dev, span) in sorted(by.items(), key=str)}}


def analysis(trace, records, window) -> dict:
    """Everything the metrics read, from the loaded profile (None where
    there is none), the program's span records and the window's two wall
    stamps; ``why`` / ``chunk_why`` say what could not be read."""
    turns = turns_of(records)
    out = {"why": None, "chunk_why": None, "device": trace is not None}
    out["lap_s"], out["decode_launches"], out["counts"] = lap_seconds(
        turns, window)
    if trace is None:
        out["why"] = out["chunk_why"] = "no profile of a device was found"
        return out
    try:
        if not turns:
            raise Unpaired("the program wrote no serve_iteration record")
        ops = xplane.op_events(trace)
        busy, laps = busy_intervals(ops), lap_table(turns)
        # the driver stamps the window's start right after the first
        # marker program has run: a first guess of delta, to the millisecond
        k, lo, hi, pairs = pair(
            laps, program_runs(trace, DECODE_BODY), program_runs(trace),
            (ops[0][1] + ops[0][2]) / 1e9 - window[0])
        idle_by, busy_by = idle_under(busy, laps, (lo + hi) / 2)
        window_s = float(busy[1][-1] - busy[0][0])
        idle_s = window_s - float((busy[1] - busy[0]).sum())
        out.update(
            k=k, delta_s=[lo, hi], delta_width_s=hi - lo, pairs=pairs,
            window_s=window_s, idle_s=idle_s, idle_by_lap_s=idle_by,
            unattributed_s=idle_s - sum(idle_by.values()),
            busy_by_lap_s=busy_by,
            longest_gaps=longest_gaps(busy, laps, turns, (lo + hi) / 2),
            idle_by_lap_s_at_delta={
                end: idle_under(busy, laps, d)[0]
                for end, d in (("lo", lo), ("hi", hi))})
    except (Unpaired, LookupError) as e:
        out["why"] = out["chunk_why"] = str(e)
        return out
    try:
        kc, pairs = pair_chunks(turns, laps,
                                program_runs(trace, CHUNK_BODY), lo, hi)
        out.update(chunk_device(pairs, records), chunk_k=kc)
    except Unpaired as e:
        out["chunk_why"] = str(e)
    return out


def analyse(run) -> dict:
    """``analysis`` of a run, made once and kept on it; written to
    ``idle_by_lap.json`` beside the run's profile."""
    kept = run.driver.get("host_laps")
    if kept is not None:
        return kept
    profile_dir = run.driver["profile_dir"]
    try:
        trace = xplane.load(xplane.find_xplane(profile_dir))
        xplane.first_device([p["name"] for p in trace["planes"]])
    except (OSError, LookupError):      # a rehearsal on the CPU has none
        trace = None
    out = run.driver["host_laps"] = analysis(
        trace, run.driver["records"], run.driver["window_wall"])
    path = os.path.join(os.path.dirname(profile_dir), "idle_by_lap.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    for key, reads in (("why", "idle_host_pct and idle_wait_pct"),
                       ("chunk_why", "prefill_chunk_device_ms")):
        if out.get(key) and trace is not None:
            print(f"host_laps: {reads} read 0, not measured: {out[key]} "
                  f"({path})", file=sys.stderr)
    return out


def read(args, run):
    if not run.driver.get("records") or not run.driver.get("profile_dir"):
        return None                     # nothing was traced
    a = analyse(run)
    if args["stat"] == "lap_ms":
        seconds = sum(s for name, s in a["lap_s"].items()
                      if name in args["laps"])
        return 1e3 * seconds / max(a["decode_launches"], 1)
    if not a["device"]:
        return None                     # no device: no device's number
    if args["stat"] == "chunk_device_ms":
        return a.get("prefill_chunk_device_ms", 0.0)
    idle = a.get("idle_by_lap_s")
    if not idle:
        return 0.0                      # a["why"] says why
    seconds = sum(s for name, s in idle.items()
                  if (name in args["laps"] if "laps" in args
                      else name not in NOT_HOST))
    return seconds / a["window_s"]
