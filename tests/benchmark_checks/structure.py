"""What a family's test may assert of ``BENCHMARK.json``: ITS OWN, and
nothing about other cells.

A family's test file names the per-layer metrics its cells need,

    NEEDS = {"<cell>": ["decode_step_ms", "moe_experts_roofline", ...]}

and holds them through ``check_cell``: the cell is in ``workloads``, the
entries that list it include those names, each moves a metric the cell
reports, each has its file, and each ``cost`` those files name is in the
cell's family file.  No place in a list, no length of a list and no
``== [CELL]``: a later cell appends its name to the entries it joins and
must break no test by arriving.  The caps on the lists are
``test_benchmark.py``'s, once.  ``test_benchmark.py`` also runs every
family's ``NEEDS`` against a copy that a made-up cell has joined
(``family_needs``).
"""

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for _path in (ROOT, HERE):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchmark.lib import contract  # noqa: E402
from benchmark.lib.runtime import load_cell, load_json  # noqa: E402

COST_TABLES = ("SPAN_COSTS", "COUNTED_COSTS", "STEP_COSTS")


def listing(bench: dict, cell_name: str) -> dict:
    """{name: entry} of the per-layer entries that list the cell."""
    return {m["name"]: m for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())}


def cost_is_the_familys(cell, spec: dict) -> bool:
    """A ``cost`` a metric's file names is a NAME looked up in the cell's
    own family file; a file that names none owes none."""
    cost = (spec.get("args") or {}).get("cost")
    return cost is None or any(cost in getattr(cell.family, table, {})
                               for table in COST_TABLES)


def check_cell(bench: dict, root: str, cell_name: str, needs,
               reports: str = "serve_tok_s", chips: int = 1,
               config: str = None, traffic: str = None):
    """Holds one cell's own claims on ``bench`` (the files under ``root``)
    and returns (the loaded cell, {name: entry} of what lists it)."""
    entry = next(w for w in bench["workloads"] if w["name"] == cell_name)
    assert entry["chips"] == chips and len(entry["why"]) <= 200
    if config is not None:
        assert (entry["config"], entry["traffic"]) == (config, traffic)
    judged = next(m for m in bench["end_to_end"] if m["name"] == reports)
    assert cell_name in judged["workloads"]
    cell = load_cell(bench, cell_name, root=root)
    mine = listing(bench, cell_name)
    assert sorted(mine) == sorted(cell.per_layer)
    missing = [n for n in needs if n not in mine]
    assert not missing, f"{cell_name} is not listed by {missing}"
    end_to_end = contract.declared_metrics(bench, cell_name, False)
    for name, m in mine.items():
        assert m["moves"] in end_to_end and m["moves"] != "setup_s", name
        spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                      name + ".json"))
        assert (spec["name"], spec["unit"], spec["layer"]) == (
            name, m["unit"], m["layer"]), name
        if name.split(".")[0].endswith("_roofline"):
            assert (m["unit"], m["better"]) == ("%", "higher"), name
        assert cost_is_the_familys(cell, spec), name
    if "serve_mfu" in mine:
        assert callable(cell.family.SPAN_COSTS["model_flops"])
    return cell, mine


def family_needs() -> dict:
    """{cell: names} of every ``NEEDS`` the test files beside this one
    declare (a later family's file is found by being there)."""
    found = {}
    for fn in sorted(os.listdir(HERE)):
        if fn.startswith("test_") and fn.endswith(".py"):
            module = importlib.import_module(fn[:-len(".py")])
            for cell, names in getattr(module, "NEEDS", {}).items():
                assert cell not in found, f"two files claim {cell}"
                found[cell] = list(names)
    return found
