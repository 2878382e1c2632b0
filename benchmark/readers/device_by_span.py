"""Device 0's timeline laid under the program's spans, on ONE clock: which
span launched each program run of the traced window, which run each op of
the op line ran in, and what lies between the host's call and the body's
first op (``dispatch``) and between its last op and the engine thread
awake with the tokens (``readback``).

* *Clock.*  delta = device − host.  The program stamps both clocks at
  once with ``clock_anchor`` spans: a tiny program launched AND waited for
  between two readings of the host's clock (``ts``, ``ts + dur_s``), whose
  n-th run on the module line (``XLA Modules``) is anchor k + n.  Each
  bounds delta by [end_n − (ts_n + dur_n), start_n − ts_n]; the window's
  anchors intersect (``clock: "anchors"``).  The drift is the slope from
  the first tight anchor's middle to the last's (a tight anchor: one no
  wider than twice the narrowest; one that waited behind a chunk is wide
  and bounds nothing); where the window's drift exceeds the interval's
  width, or the intersection is empty, delta is taken between neighbouring
  anchors (``delta: "between anchors"``) and not once a window.  Records
  that hold no anchor — the parent's program — leave the causal interval
  of ``host_laps.pair`` (``clock: "causal"``).  Both are written, and
  whether the anchors' lies inside the causal one (``inside_causal``).
* *Runs to spans.*  A span of a launch names its ``program`` and carries
  an ordinal (``serve_decode``: ``step``; ``serve_prefill_chunk``:
  ``chunk``; ``serve_close_window``: ``close``; ``clock_anchor``: ``n``);
  the n-th run of that program is the span with ordinal k_program + n,
  held to the clock: it starts no sooner than its span did and ends no
  later than the next lap that waited for the device.  Where the spans
  carry neither (the parent's), the turn's ``step`` / ``chunk`` and
  ``host_laps``' two body names stand in.  An operands program
  (``OPERANDS``) lies under the span of the body it feeds; a jitted
  lambda's run that no span launched is the driver's marker, its own.
* *Ops to runs.*  Each event of the op line goes to the run that holds
  its start; ``lib/xplane.reduce_trace`` reduces each span name's ops on
  their own, so self time (nested ops taken off) adds up over the span
  names to the window's.  A span name's programs' time on the device, its
  ops' busy time, the idle INSIDE its runs, and the window's idle BETWEEN
  runs follow.
* *The boundary.*  A step's ``dispatch`` = its body's start − (the end of
  its turn's ``launch_args`` lap + delta): the body's call, on the host,
  begins there.  Its ``readback`` = (the end of the turn's ``ready`` lap
  + delta) − the body's end.  For a prompt's last chunk: from the span's
  start to the body's, and from the body's end to the end of the
  ``chunk_sync`` lap that read its token.
* *Idle by lap* through ``host_laps.idle_under`` on this clock, beside
  ``idle_by_lap.json``'s on the causal one.

``analyse`` writes ``benchmark/out/<cell>/device_by_span.json``, once a
run; ``python3 -m benchmark.readers.device_by_span benchmark/out/<cell>``
reads a kept run by hand (no wall stamp of the window there: the first
guess of delta is then the one under which the gaps between the decode
body's runs are the gaps between the turns' calls).  ``read`` returns None
where nothing was traced or the profile holds no device, and 0 — ``why``
in the JSON and on stderr — where the records hold nothing to pair.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys

import numpy as np

from benchmark.lib import stats, xplane
from benchmark.readers import host_laps
from benchmark.readers.host_laps import (Unpaired, busy_intervals,
                                         idle_under, lap_table, pair,
                                         program_runs, turns_of)

# span name → (ordinal's key, the body's name where the span names no
# program: the parent's)
LAUNCHES = {"serve_decode": ("step", host_laps.DECODE_BODY),
            "serve_prefill_chunk": ("chunk", host_laps.CHUNK_BODY),
            "serve_close_window": ("close", None),
            "clock_anchor": ("n", None)}
# serve/decode.py keeps these names: the one small program a launch runs
# before its body (a close's: the cast of its window's ordinal), and the
# span it lies under
OPERANDS = {"jit__step_operands": "serve_decode",
            "jit__chunk_operands": "serve_prefill_chunk",
            "jit_convert_element_type": "serve_close_window"}
WAITED = ("ready", "chunk_sync")        # laps that end with the device read
SEARCH = 3          # ordinals either side of the clock's guess for k
SLACK = 5e-5        # seconds two readings of one instant may differ by
# lib/runtime.py TracedWindow pins the window's edges with a jitted lambda
MARKER, MARKER_PROGRAM = "driver_marker", "jit__lambda"


def base(name: str) -> str:
    """``jit__chunk_impl(4162985419019916287)`` → ``jit__chunk_impl``."""
    return name.split("(", 1)[0]


def module_runs(trace) -> list:
    """[name, start_s, end_s] of every run on device 0's module line."""
    events = host_laps._device_lines(trace).get(host_laps.MODULE_LINE)
    if not events:
        raise Unpaired(f"device 0's plane has no line "
                       f"{host_laps.MODULE_LINE!r} with events")
    return sorted(([base(n), s / 1e9, (s + d) / 1e9] for n, s, d in events),
                  key=lambda r: r[1])


def launch_spans(records) -> dict:
    """{span name: {ordinal: record}} of the spans that launched a
    program, each with ``program`` (its own, or the body's name) and
    ``last`` as the record has them; the parent's decode and chunk spans
    take their ordinal from the turn they are children of."""
    turns = {r["span_id"]: r for r in records
             if r.get("name") == "serve_iteration" and "span_id" in r}
    out = {name: {} for name in LAUNCHES}
    for r in records:
        if r.get("kind") != "span" or r.get("name") not in LAUNCHES:
            continue
        key, body = LAUNCHES[r["name"]]
        ordinal = r.get(key, turns.get(r.get("parent_span"), {}).get(key))
        if ordinal is not None:
            out[r["name"]][ordinal] = dict(r, program=r.get("program", body))
    return out


def guess_from_gaps(laps, runs) -> float:
    """A first guess of delta without the driver's wall stamp: the offset
    k under which the gaps between the decode body's runs are the gaps
    between the calls of steps k, k + 1, …; then the median of start −
    call."""
    call = sorted(t for t, n, s in zip(laps[1], laps[2], laps[3])
                  if n == "launch_args" and s is not None)
    starts = np.asarray([r[0] for r in runs])
    n = len(starts)
    if n < 8 or len(call) < n:
        raise Unpaired(f"{n} runs of the decode body and {len(call)} calls: "
                       f"too few to align by their gaps")
    call = np.asarray(call)
    host, dev = np.diff(call), np.diff(starts)
    k = min(range(len(call) - n + 1),
            key=lambda k: float(np.median(np.abs(dev - host[k:k + n - 1]))))
    return float(np.median(starts - call[k:k + n]))


class Clock:
    """delta as a function of the host's time: one number, or a line
    through neighbouring anchors' middles."""

    def __init__(self, lo, hi, kind, at=None, mids=None):
        self.lo, self.hi, self.kind = lo, hi, kind
        self.at, self.mids = at, mids       # piecewise: host times, deltas

    @property
    def width(self):
        return self.hi - self.lo

    def delta(self, t):
        if self.at is None:
            return (self.lo + self.hi) / 2
        return np.interp(t, self.at, self.mids)


def anchor_clock(anchors, runs, guess):
    """(Clock, facts) from the ``clock_anchor`` spans ({n: record}) and
    the anchor program's runs; (None, facts) where they do not pair."""
    facts = {"anchors": len(anchors), "anchor_runs": len(runs)}
    if not anchors or not runs:
        return None, facts
    order = sorted(anchors)
    # the anchor whose span holds the first run, by the guess (anchors
    # lie a third of a second apart: a guess good to 0.1 s chooses)
    first = min(order, key=lambda n: abs(anchors[n]["ts"] + guess
                                         - runs[0][0]))
    mine = [(anchors.get(first + i), run) for i, run in enumerate(runs)]
    mine = [(a, run) for a, run in mine if a is not None]
    lo = np.asarray([run[1] - (a["ts"] + a["dur_s"]) for a, run in mine])
    hi = np.asarray([run[0] - a["ts"] for a, run in mine])
    if np.any(lo > hi + SLACK) or abs((lo[0] + hi[0]) / 2 - guess) > 0.5:
        facts["anchor_why"] = (
            f"anchor {first} + n does not hold the n-th run between its "
            f"span's two edges")
        return None, facts
    width = hi - lo
    tight = width <= 2 * width.min()
    at = np.asarray([a["ts"] for a, _ in mine])[tight]
    mids = ((lo + hi) / 2)[tight]
    span_s = float(at[-1] - at[0])
    drift = float(mids[-1] - mids[0])
    facts.update(
        anchor_k=first, anchors_paired=len(mine), anchors_tight=int(
            tight.sum()),
        anchor_dur_s_median=statistics.median(a["dur_s"] for a, _ in mine),
        anchor_run_s_median=statistics.median(r[1] - r[0] for _, r in mine),
        anchor_width_s=[float(w) for w in width],
        anchor_delta_s=[[float(a), float(b)] for a, b in zip(lo, hi)],
        drift_s=drift, drift_over_s=span_s,
        drift_ppm=1e6 * drift / span_s if span_s > 0 else 0.0)
    lo_all, hi_all = float(lo.max()), float(hi.min())
    if lo_all <= hi_all and abs(drift) <= hi_all - lo_all:
        return Clock(lo_all, hi_all, "once a window"), facts
    # it drifts: between neighbouring anchors, each to its own width
    worst = float(width[tight].max())
    mid = float(np.median(mids))
    return Clock(mid - worst / 2, mid + worst / 2, "between anchors",
                 at=at, mids=mids), facts


def pair_runs(spans, runs, clock, ends):
    """{run index: ordinal} for one program: the n-th run with the span of
    ordinal k + n, k the offset near the clock's guess that holds the most
    runs between their span's start and ``ends`` (the sorted host times a
    wait for the device ended) after it."""
    if not spans or not runs:
        return {}
    order = sorted(spans)
    began = [spans[o]["ts"] for o in order]
    d = float(clock.delta(began[0]))
    at = bisect.bisect_right(began, runs[0][1] - d + clock.width / 2
                             + SLACK) - 1
    guess = order[max(at, 0)]

    def held(k):
        ok = {}
        for i, (_, start, end) in enumerate(runs):
            span = spans.get(k + i)
            if span is None:
                continue
            d = float(clock.delta(span["ts"]))
            j = bisect.bisect_left(ends, span["ts"])
            until = ends[j] if j < len(ends) else np.inf
            if (span["ts"] + d - clock.width / 2 - SLACK <= start
                    and end <= until + d + clock.width / 2 + SLACK):
                ok[i] = k + i
        return ok
    return max((held(k) for k in sorted(
        range(guess - SEARCH, guess + SEARCH + 1),
        key=lambda k: abs(k - guess))), key=len)


def ops_by_group(trace, runs, groups):
    """{group: Reduction of the ops whose start lies in a run of that
    group} (``lib/xplane.reduce_trace``'s rules, group by group) and the
    busy seconds of the ops inside any run."""
    ops = xplane.op_events(trace)
    starts = np.asarray([r[1] for r in runs]) * 1e9
    ends = np.asarray([r[2] for r in runs]) * 1e9
    op_start = np.asarray([e[1] for e in ops])
    at = np.searchsorted(starts, op_start, side="right") - 1
    inside = (at >= 0) & (op_start < ends[np.maximum(at, 0)])
    by = {}
    for e, i, ok in zip(ops, at, inside):
        by.setdefault(groups[i] if ok else "between runs", []).append(e)
    device = xplane.first_device([p["name"] for p in trace["planes"]])
    return {g: xplane.reduce_trace({"planes": [{"name": device, "lines": [
        {"name": xplane.OP_LINE, "events": events}]}]})
        for g, events in by.items()}


def _stat(values):
    values = [1e3 * v for v in values]
    return ({"median": statistics.median(values),
             "p95": stats.percentile(values, 95), "count": len(values)}
            if values else None)


def analysis(trace, records, window=None) -> dict:
    """Everything the metrics read (module docstring), from the loaded
    profile (None where there is none), the program's records and the
    window's two wall stamps (None: by hand); ``why`` says what could not
    be read.  ``reductions`` (the ops by span name) is not JSON."""
    turns = turns_of(records)
    out = {"why": None, "device": trace is not None, "clock": None}
    if trace is None:
        out["why"] = "no profile of a device was found"
        return out
    try:
        if not turns:
            raise Unpaired("the program wrote no serve_iteration record")
        ops = xplane.op_events(trace)
        busy, laps = busy_intervals(ops), lap_table(turns)
        runs = module_runs(trace)
        spans = launch_spans(records)
        decode_runs = program_runs(trace, host_laps.DECODE_BODY)
        guess = ((ops[0][1] + ops[0][2]) / 1e9 - window[0] if window
                 else guess_from_gaps(laps, decode_runs))
        try:
            k, lo, hi, _ = pair(laps, decode_runs, program_runs(trace),
                                guess)
            out["causal"] = {"k": k, "delta_s": [lo, hi],
                             "delta_width_s": hi - lo}
            causal = Clock(lo, hi, "once a window")
        except Unpaired as e:
            out["causal"], causal = {"why": str(e)}, None
        anchors = spans["clock_anchor"]
        names = {a["program"] for a in anchors.values()}
        clock, facts = anchor_clock(
            anchors, [r[1:] for r in runs if r[0] in names],
            (causal.lo + causal.hi) / 2 if causal else guess)
        out.update(facts)
        out["clock"] = "anchors" if clock else "causal"
        clock = clock or causal
        if clock is None:
            raise Unpaired(f"no anchor pairs and the causal join fails: "
                           f"{out['causal']['why']}")
        out.update(delta_s=[clock.lo, clock.hi], delta_width_s=clock.width,
                   delta=clock.kind)
        if causal and out["clock"] == "anchors":
            out["inside_causal"] = bool(causal.lo - SLACK <= clock.lo
                                        and clock.hi <= causal.hi + SLACK)
            out["overlaps_causal"] = bool(clock.lo <= causal.hi
                                          and causal.lo <= clock.hi)
    except (Unpaired, LookupError) as e:
        out["why"] = str(e)
        return out

    # -- runs to spans --------------------------------------------------
    ends = sorted([t for t, n in zip(laps[1], laps[2]) if n in WAITED]
                  + [a["ts"] + a["dur_s"] for a in anchors.values()])
    groups = [None] * len(runs)         # the span name each run lies under
    ordinal = [None] * len(runs)
    by_program = {}
    for i, (name, _, _) in enumerate(runs):
        by_program.setdefault(name, []).append(i)
    for span_name, by_ordinal in spans.items():
        programs = {s["program"] for s in by_ordinal.values()}
        for program in programs:
            # the parent's spans name the body, not the program
            mine = [i for name, at in by_program.items()
                    if name == program or (program in name
                                           and name not in OPERANDS)
                    for i in at]
            mine.sort()
            held = pair_runs(
                {o: s for o, s in by_ordinal.items()
                 if s["program"] == program},
                [runs[i] for i in mine], clock, ends)
            for n, o in held.items():
                groups[mine[n]], ordinal[mine[n]] = span_name, o
    for i, (name, start, _) in enumerate(runs):
        under = OPERANDS.get(name)
        if groups[i] is None and under is not None:
            # the next body of that span name, and its span begun by now
            nxt = next((j for j in range(i + 1, min(i + 8, len(runs)))
                        if groups[j] == under
                        and runs[j][0] not in OPERANDS), None)
            if nxt is not None:
                span = spans[under][ordinal[nxt]]
                d = float(clock.delta(span["ts"]))
                if span["ts"] + d - clock.width / 2 - SLACK <= start:
                    groups[i], ordinal[i] = under, ordinal[nxt]
    unpaired = {}
    for i, (name, _, _) in enumerate(runs):
        if groups[i] is None and name.startswith(MARKER_PROGRAM):
            groups[i] = MARKER          # no span's: the driver's own
        elif groups[i] is None:
            unpaired[name] = unpaired.get(name, 0) + 1
    out.update(runs=len(runs), paired=sum(g is not None for g in groups),
               unpaired=unpaired)

    # -- ops to runs ----------------------------------------------------
    named = [g or "no span" for g in groups]
    reductions = ops_by_group(trace, runs, named)
    window_s = float(busy[1][-1] - busy[0][0])
    idle_s = window_s - float((busy[1] - busy[0]).sum())
    by_span, inside_idle = {}, 0.0
    for g in sorted(set(named)):
        mine = [r for r, name in zip(runs, named) if name == g]
        device_s = sum(r[2] - r[1] for r in mine)
        red = reductions.get(g)
        busy_s = red.busy_s if red else 0.0
        inside_idle += device_s - busy_s
        by_span[g] = {
            "runs": len(mine),
            "bodies": sum(r[0] not in OPERANDS for r in mine),
            "device_s": device_s, "busy_s": busy_s,
            "idle_inside_s": device_s - busy_s,
            "ops": red.top_ops(8) if red else []}
    out.update(window_s=window_s, idle_s=idle_s, by_span=by_span,
               idle_inside_runs_s=inside_idle,
               idle_between_runs_s=idle_s - inside_idle,
               reductions=reductions)

    # -- the boundary ---------------------------------------------------
    call = {s: t for t, n, s in zip(laps[1], laps[2], laps[3])
            if n == "launch_args" and s is not None}
    ready = {s: t for t, n, s in zip(laps[1], laps[2], laps[3])
             if n == "ready" and s is not None}
    syncs = [t for t, n in zip(laps[1], laps[2]) if n == "chunk_sync"]
    step_s = {}                         # step → its programs' device time
    dispatch, readback, chunk_host, chunk_sync = [], [], [], []
    for (name, start, end), g, o in zip(runs, groups, ordinal):
        if g == "serve_decode":
            step_s[o] = step_s.get(o, 0.0) + end - start
            if name not in OPERANDS and o in call and o in ready:
                d = float(clock.delta(call[o]))
                dispatch.append(start - (call[o] + d))
                readback.append(ready[o] + d - end)
        elif (g == "serve_prefill_chunk" and name not in OPERANDS
              and spans[g][o].get("last")):
            span = spans[g][o]
            d = float(clock.delta(span["ts"]))
            j = bisect.bisect_left(syncs, span["ts"])
            chunk_host.append(start - (span["ts"] + d))
            if j < len(syncs):
                chunk_sync.append(syncs[j] + d - end)
    out["boundary"] = {
        "decode_dispatch_ms": _stat(dispatch),
        "decode_readback_ms": _stat(readback),
        "decode_body_device_ms": _stat(step_s.values()),
        "last_chunk_host_ms": _stat(chunk_host),
        "last_chunk_sync_ms": _stat(chunk_sync)}

    # -- idle by lap on this clock ---------------------------------------
    idle_by, busy_by = idle_under(busy, laps, clock.delta(laps[0]))
    out.update(idle_by_lap_s=idle_by, busy_by_lap_s=busy_by,
               unattributed_s=idle_s - sum(idle_by.values()))
    if causal:
        out["idle_by_lap_s_causal"] = idle_under(
            busy, laps, (causal.lo + causal.hi) / 2)[0]
    return out


def load_trace(profile_dir):
    """The loaded profile, or None where it holds no device (a rehearsal
    on the CPU) or is not there."""
    try:
        trace = xplane.load(xplane.find_xplane(profile_dir))
        xplane.first_device([p["name"] for p in trace["planes"]])
        return trace
    except (OSError, LookupError):
        return None


def write(out: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump({k: v for k, v in out.items() if k != "reductions"}, f,
                  indent=1)
    if out.get("why") and out["device"]:
        print(f"device_by_span: every metric of this reader reads 0, not "
              f"measured: {out['why']} ({path})", file=sys.stderr)


def analyse(run) -> dict:
    """``analysis`` of a run, made once and kept on it; written to
    ``device_by_span.json`` beside the run's profile."""
    kept = run.driver.get("device_by_span")
    if kept is None:
        profile_dir = run.driver["profile_dir"]
        kept = run.driver["device_by_span"] = analysis(
            load_trace(profile_dir), run.driver["records"],
            run.driver["window_wall"])
        write(kept, os.path.join(os.path.dirname(profile_dir),
                                 "device_by_span.json"))
    return kept


def read(args, run):
    if not run.driver.get("records") or not run.driver.get("profile_dir"):
        return None                     # nothing was traced
    a = analyse(run)
    if not a["device"]:
        return None                     # no device: no device's number
    if a["why"]:
        return 0.0                      # a["why"] says why
    stat = args["stat"]
    if stat == "paired_share":
        return a["paired"] / a["runs"]
    if stat == "idle_between_runs_share":
        return a["idle_between_runs_s"] / a["window_s"]
    if stat == "boundary_ms":
        found = a["boundary"][args["of"]]
        return found["median"] if found else 0.0
    if stat == "kernel_ms_per_run":
        red = a["reductions"].get(args["span"])
        bodies = a["by_span"].get(args["span"], {}).get("bodies")
        seconds = red.kernel_s(args["regex"]) if red else None
        return 1e3 * seconds / bodies if seconds and bodies else 0.0
    raise ValueError(f"device_by_span: unknown stat {stat!r}")


def main(argv=None) -> int:
    """``python3 -m benchmark.readers.device_by_span benchmark/out/<cell>
    [regex ...]``: a kept traced run read by hand; each regex's self time
    by span name is printed beside the JSON's summary."""
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(main.__doc__, file=sys.stderr)
        return 2
    out_dir, patterns = argv[0], argv[1:]
    spans = os.path.join(out_dir, "spans", "trace_rank0.jsonl")
    with open(spans) as f:
        records = [json.loads(line) for line in f if line.strip()]
    a = analysis(load_trace(os.path.join(out_dir, "profile")), records)
    write(a, os.path.join(out_dir, "device_by_span.json"))
    if a["why"]:
        return 1
    print(json.dumps({k: a[k] for k in (
        "clock", "delta", "delta_s", "delta_width_s", "runs", "paired",
        "unpaired", "boundary", "idle_between_runs_s", "idle_inside_runs_s",
        "window_s") if k in a}, indent=1))
    for pattern in patterns:
        for g, red in sorted(a["reductions"].items()):
            seconds = red.kernel_s(pattern)
            if seconds:
                bodies = a["by_span"][g]["bodies"]
                print(f"{pattern}  {g}: {seconds:.6f} s in "
                      f"{red.kernel_calls(pattern)} calls, "
                      f"{1e3 * seconds / max(bodies, 1):.3f} ms a run of "
                      f"{bodies}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
