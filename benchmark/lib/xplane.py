"""From a profiler trace (``.xplane.pb``) to device busy time, kernel times
and the breakdown — the one reduction every PR's numbers go through.

Rules, each a fault PR 22's missing reduction could have made:

* ONE device: the plane ``/device:TPU:<n>`` with the lowest n.  Summing
  over four devices gives a busy time four windows long.
* ONE line of that plane: the one named ``XLA Ops``.  "XLA Modules" and
  "Steps" cover the same time again.
* busy_s is the length of the UNION of the op intervals (a ``while`` op
  holds its body's ops; a sum would count them twice), so busy ≤ window.
* window_s is taken on the same device clock: from the start of the
  first op of the line to the end of its last.  The drivers pin both
  edges with a tiny marker program right after the profiler starts and
  right before it stops, so an idle stretch at either edge is inside.
* per-name times are SELF times (an op's duration less the ops nested in
  it), so the names add up to busy_s.

The trace is first brought into plain lists (``load``), so the same code
reduces the cut-down JSON trace kept with the tests.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# the TPU's op line spells the ZeRO step's scatter ``reduce_scatter.N``
# and the others with hyphens (recorded on the v5e, PR 35): both spellings
COLLECTIVE_RE = re.compile(
    r"^(all[-_]gather|all[-_]reduce|reduce[-_]scatter|all[-_]to[-_]all|"
    r"collective[-_]permute|collective[-_]broadcast|"
    r"ragged[-_]all[-_]to[-_]all)")


def first_device(plane_names) -> str:
    """The name of the device plane with the lowest ordinal."""
    ordinals = [int(m.group(1)) for m in map(DEVICE_PLANE_RE.match,
                                             plane_names) if m]
    if not ordinals:
        raise LookupError(f"no plane named /device:TPU:<n> (planes: "
                          f"{list(plane_names)})")
    return f"/device:TPU:{min(ordinals)}"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """The TPU's op line names an event by its whole HLO instruction
    (``%paged_flash_decode.39 = bf16[48,16,1,128]{...} custom-call(...)``,
    as recorded on the v5e in PR 23); the instruction's own name, without
    the ``%``, is what metrics and the breakdown use."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]} — the first device's plane in full,
    of every other plane only the names of its lines (host planes are
    large and the reduction does not read them)."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    planes = []
    all_planes = list(ProfileData.from_file(path).planes)
    try:
        first = first_device([p.name for p in all_planes])
    except LookupError:
        first = None                    # reduce_trace will say so
    for plane in all_planes:
        device = plane.name == first    # the other chips' events stay unread
        lines = []
        for line in plane.lines:
            events = ([[short_name(e.name), float(e.start_ns),
                        float(e.duration_ns)]
                       for e in line.events] if device else [])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict, longest: int = 20) -> str:
    """Planes, lines and event counts, and the longest event names of the
    op line — what to look at by hand before trusting the reduction."""
    out = []
    for plane in trace["planes"]:
        out.append(f"plane {plane['name']!r}")
        for line in plane["lines"]:
            out.append(f"  line {line['name']!r}: "
                       f"{len(line['events'])} events")
    try:
        events = op_events(trace)
    except LookupError as e:
        out.append(f"no op line: {e}")
        return "\n".join(out)
    top = sorted(events, key=lambda e: -e[2])[:longest]
    out.append(f"longest {len(top)} events of the op line:")
    out += [f"  {d / 1e6:10.3f} ms  {n}" for n, _, d in top]
    return "\n".join(out)


def op_events(trace: dict) -> List[list]:
    """The events of device 0's op line, sorted by start."""
    first = first_device([p["name"] for p in trace["planes"]])
    plane = next(p for p in trace["planes"] if p["name"] == first)
    lines = [ln for ln in plane["lines"] if ln["name"] == OP_LINE]
    if len(lines) != 1:
        raise LookupError(
            f"plane {plane['name']} has {len(lines)} lines named "
            f"{OP_LINE!r} (lines: {[ln['name'] for ln in plane['lines']]})")
    return sorted(lines[0]["events"], key=lambda e: (e[1], -e[2]))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    self_s: Dict[str, float]            # per op name, adds up to busy_s
    calls: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]  # longest first

    def kernel_s(self, pattern: str) -> Optional[float]:
        """Self time of every op whose name matches; None where no op
        does (the metric then cannot be read in this cell)."""
        rx = re.compile(pattern)
        hit = [s for n, s in self.self_s.items() if rx.search(n)]
        return sum(hit) if hit else None

    def kernel_calls(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(c for n, c in self.calls.items() if rx.search(n))

    def top_ops(self, k: int = 10) -> List[list]:
        """The k op families (an instruction's name less its ``.<n>``:
        ``fusion.12`` and ``fusion.7`` are both ``fusion``) with the most
        self time, as [family, seconds]."""
        families: Dict[str, float] = {}
        for n, s in self.self_s.items():
            fam = re.sub(r"\.\d+$", "", n)
            families[fam] = families.get(fam, 0.0) + s
        rows = sorted(families.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s] for n, s in rows]

    def top_gaps(self, k: int = 10) -> List[list]:
        return [[n, s] for n, s in self.idle_gaps[:k]]


def reduce_trace(trace: dict) -> Reduction:
    events = op_events(trace)
    if not events:
        raise LookupError("the op line of device 0 holds no event")
    t0 = events[0][1]
    t1 = max(s + d for _, s, d in events)
    self_ns: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    gaps: List[Tuple[str, float]] = []
    busy_ns = 0.0
    stack: List[list] = []      # open events: [name, end, self]
    cover_end = t0              # end of the union so far
    last_top = "window start"

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, self_t = stack.pop()
            self_ns[name] = self_ns.get(name, 0.0) + max(self_t, 0.0)

    for name, start, dur in events:
        end = start + dur
        close(start)
        if stack:
            # nested: the parent's self time loses the child's span
            # (clipped to the parent, should clocks disagree by a tick)
            stack[-1][2] -= min(end, stack[-1][1]) - start
        if start > cover_end:
            gaps.append((f"after {last_top} before {name}",
                         (start - cover_end) / 1e9))
        if end > cover_end:
            busy_ns += end - max(start, cover_end)
            cover_end = end
        if not stack:
            last_top = name
        calls[name] = calls.get(name, 0) + 1
        stack.append([name, min(end, stack[-1][1]) if stack else end, dur])
    close(float("inf"))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=(t1 - t0) / 1e9, busy_s=busy_ns / 1e9,
                     self_s={n: v / 1e9 for n, v in self_ns.items()},
                     calls=calls, idle_gaps=gaps)


def cut_down(trace: dict, keep_events: int) -> dict:
    """The first ``keep_events`` events of device 0's op line and nothing
    else, for a trace small enough to keep with the tests."""
    plane = first_device([p["name"] for p in trace["planes"]])
    return {"planes": [{"name": plane, "lines": [
        {"name": OP_LINE, "events": op_events(trace)[:keep_events]}]}]}
