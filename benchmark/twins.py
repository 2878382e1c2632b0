"""The builder's tool: per-layer entries that are ONE measurement written
twice.

    python3 -m benchmark.twins [--against <checkout of an older tree>]

Two entries of ``BENCHMARK.json`` ``per_layer`` are twins where their files
under ``layer_metrics/`` are equal but for ``name`` and ``note``: the same
reader, ``args``, scale, ``unit``, ``layer`` and ``moves``.  Twins are one
entry with both cells under ``workloads``, unless one file's ``note`` names
the other entry and so says what differs for their cells.  It prints every
group of twins that no note sets apart and exits 1 if there is one; entries
without a file, files without an entry and entries without a ``workloads``
list are faults too.

With ``--against`` it also holds every (metric, cell) pair the older tree
declared against this one: the pair is declared here by an entry whose file
is equal but for ``name`` and ``note`` and whose ``unit``, ``better``,
``source``, ``layer`` and ``moves`` in ``BENCHMARK.json`` are the same, and
prints the map old name -> new name and the pairs only this tree has.  It
never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY_KEYS = ("unit", "better", "source", "layer", "moves")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def declared(root: str):
    """({name: entry}, {name: file}, faults) of a checkout."""
    entries = {m["name"]: m for m in
               _load(os.path.join(root, "BENCHMARK.json"))["per_layer"]}
    metric_dir = os.path.join(root, "benchmark", "layer_metrics")
    files = {fn[:-len(".json")]: _load(os.path.join(metric_dir, fn))
             for fn in sorted(os.listdir(metric_dir))
             if fn.endswith(".json")}
    faults = [f"{n}: an entry with no file" for n in entries
              if n not in files]
    faults += [f"{n}: a file with no entry" for n in files
               if n not in entries]
    faults += [f"{n}: the file's name is {f.get('name')!r}"
               for n, f in files.items() if f.get("name") != n]
    faults += [f"{n}: no workloads list" for n, e in entries.items()
               if not e.get("workloads")]
    return entries, files, faults


def measurement(entry: dict, spec: dict) -> str:
    """What an entry measures, without what it is called."""
    body = {k: v for k, v in spec.items() if k not in ("name", "note")}
    return json.dumps([body, {k: entry[k] for k in ENTRY_KEYS}],
                      sort_keys=True)


def _names_in_note(spec: dict) -> set:
    return {w.rstrip(".") for w in
            re.findall(r"[\w.\-]+", spec.get("note", ""))}


def twins(entries: dict, files: dict) -> list:
    """Groups of entries of one measurement in which some pair is not set
    apart (a note sets a pair apart where it names the other entry)."""
    by_measurement = {}
    for name in entries:
        if name in files:
            by_measurement.setdefault(
                measurement(entries[name], files[name]), []).append(name)
    return [names for names in by_measurement.values() if any(
        b not in _names_in_note(files[a]) and a not in _names_in_note(files[b])
        for i, a in enumerate(names) for b in names[i + 1:])]


def pairs_against(old_root: str, root: str = ROOT):
    """(old -> new names, pairs the old tree declared that this one does
    not, pairs only this tree declares)."""
    old_entries, old_files, _ = declared(old_root)
    entries, files, _ = declared(root)
    here = {}
    for name, entry in entries.items():
        for cell in entry["workloads"]:
            here[(measurement(entry, files[name]), cell)] = name
    renamed, dropped, kept = {}, [], set()
    for name, entry in old_entries.items():
        for cell in entry["workloads"]:
            key = (measurement(entry, old_files[name]), cell)
            if key not in here:
                dropped.append((name, cell))
                continue
            kept.add(key)
            renamed.setdefault(name, set()).add(here[key])
    added = sorted((name, key[1]) for key, name in here.items()
                   if key not in kept)
    return renamed, dropped, added


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--against", help="a checkout of the tree to compare")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    entries, files, faults = declared(ROOT)
    for group in twins(entries, files):
        faults.append("twins: " + " ".join(group))
    n_pairs = sum(len(e.get("workloads", ())) for e in entries.values())
    print(f"{len(entries)} entries, {n_pairs} (metric, cell) pairs")
    if args.against:
        renamed, dropped, added = pairs_against(args.against)
        for old, new in sorted(renamed.items()):
            if new != {old}:
                print(f"{old} -> {' '.join(sorted(new))}")
        faults += [f"dropped: {name} in {cell}" for name, cell in dropped]
        faults += [f"{old}: one entry became {sorted(new)}"
                   for old, new in renamed.items() if len(new) > 1]
        for name, cell in added:
            print(f"added: {name} in {cell}")
    for fault in faults:
        print(fault, file=sys.stderr)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
