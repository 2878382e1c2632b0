"""The check of the last line.  ``run.py`` holds what it is about to print
against the contract for the cell and mode, and prints nothing that this
refuses."""

from __future__ import annotations

import math
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def declared_metrics(benchmark: dict, cell: str, traced: bool) -> dict:
    """name -> unit of every metric BENCHMARK.json requires of ``cell`` in
    this mode: its end-to-end metrics untraced, its per-layer metrics
    traced.  A metric without a ``workloads`` key belongs to every cell."""
    group = benchmark["per_layer" if traced else "end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if "workloads" not in m or cell in m["workloads"]}


def _is_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def check_line(line, benchmark: dict, cell: str, traced: bool) -> list:
    """Every fault of ``line`` (the parsed last line) as a string; an
    empty list means the contract takes it."""
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    faults = [f"key {k!r} is missing" for k in TOP_KEYS if k not in line]
    if faults:
        return faults
    chips = next(w["chips"] for w in benchmark["workloads"]
                 if w["name"] == cell)
    if not isinstance(line["correct"], bool):
        faults.append("'correct' is not true or false")
    for key, least in (("attempted", 1), ("failed", 0)):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < least:
            # a window in which nothing fell due measured nothing: the
            # driver's check refuses attempted 0 too
            faults.append(f"{key!r} is not a count of at least {least}: "
                          f"{v!r}")
    want = declared_metrics(benchmark, cell, traced)
    got = line["metrics"]
    if not isinstance(got, dict):
        return faults + ["'metrics' is not an object"]
    for name in want:
        if name not in got:
            faults.append(f"metric {name!r} is declared for {cell} in this "
                          f"mode and is missing")
    for name, entry in got.items():
        if name not in want:
            faults.append(f"metric {name!r} is not declared for {cell} in "
                          f"this mode")
            continue
        if not NAME_RE.match(name):
            faults.append(f"metric name {name!r} has characters outside "
                          f"the contract")
        if (not isinstance(entry, dict)
                or set(entry) != {"value", "unit"}):
            faults.append(f"metric {name!r} is not {{value, unit}}: "
                          f"{entry!r}")
            continue
        if not _is_number(entry["value"]):
            faults.append(f"metric {name!r} is not a finite number: "
                          f"{entry['value']!r}")
        unit = entry["unit"]
        if not isinstance(unit, str) or not UNIT_RE.match(unit):
            faults.append(f"unit {unit!r} of {name!r} is outside the "
                          f"contract (1-16 of letters digits _ / % . -)")
        elif unit != want[name]:
            faults.append(f"metric {name!r} has unit {unit!r}, declared "
                          f"{want[name]!r}")
    dev = line["device"]
    if not isinstance(dev, dict):
        return faults + ["'device' is not an object"]
    if dev.get("platform") != "tpu":
        faults.append(f"device.platform is {dev.get('platform')!r}, not "
                      f"'tpu'")
    if not isinstance(dev.get("kind"), str) or not dev.get("kind"):
        faults.append("device.kind is missing")
    if dev.get("count") != chips:
        faults.append(f"device.count is {dev.get('count')!r}, the cell "
                      f"asks for {chips}")
    mem = dev.get("memory_peak_bytes")
    if not _is_number(mem) or mem <= 0:
        faults.append(f"device.memory_peak_bytes is {mem!r}, not above 0")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _is_number(busy) or not _is_number(window):
            faults.append(f"traced run without busy_s/window_s numbers: "
                          f"{busy!r}, {window!r}")
        elif not 0 < busy <= window:
            faults.append(f"busy_s {busy!r} is not above 0 and at most "
                          f"window_s {window!r}")
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                if (not isinstance(rows, list) or len(rows) > 10
                        or not all(isinstance(r, list) and len(r) == 2
                                   and isinstance(r[0], str)
                                   and _is_number(r[1]) for r in rows)):
                    faults.append(f"breakdown.{key} is not a list of at "
                                  f"most 10 [name, seconds] pairs")
    return faults
