"""What the harness knows of a model family, it finds here by the name in
a configuration's ``family``: the file ``benchmark/families/<family>.py``.
Nothing else in the benchmark names a family, so a new one is new files.

A family's file exports

``train_flops_per_sample(config, traffic)``
    the numerator of ``train_mfu``: forward + backward, no recomputation;
``TOY``
    driver -> the sizes ``rehearse.py`` runs the family at on the CPU;
``STEP_COSTS`` (where a roofline reader names one)
    name -> ``f(config, traffic, chips) -> (FLOPs, bytes)`` of one train
    step's calls of a kernel on one chip (``readers/trace_kernel.py``);
``COUNTED_COSTS`` (likewise)
    name -> ``f(config, count) -> (FLOPs, bytes)`` of a kernel's calls
    over ``count`` units the program counted, all layers together
    (``readers/trace_kernel_counted.py``);
``SPAN_COSTS`` (likewise)
    name -> ``f(config, span) -> (FLOPs, bytes)`` of a kernel's calls in
    the one compiled call a span of the program records, from the counts
    on the span (``readers/trace_kernel_spans.py``); and, in a family
    that is served, ``model_flops``: ``f(config, call) -> FLOPs`` the
    configuration's mathematics needs for that call, whatever the
    implementation does (``readers/span_mfu.py``, the metric
    ``serve_mfu``; a new serving cell adds its name to that metric's
    ``workloads``).

The family's plain reference is the file a configuration names under
``reference`` (a path from the repo's root): a module with
``forward(params, tokens)`` and ``served_tokens_agree(params, prompts,
served, rtol, program_logits, logit_rms_limit)``.  ``null`` there means
the family has none yet, and a configuration without one cannot be served:
a serving cell of it is an error when the cell is loaded, not a check that
is skipped.
"""

from __future__ import annotations

import importlib.util
import os

_LOADED = {}


def _module_at(path: str, name: str):
    path = os.path.abspath(path)
    if path not in _LOADED:
        if not os.path.exists(path):
            raise FileNotFoundError(f"{name}: no file {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def load(family: str, root: str):
    """The family's module, from the checkout at ``root``, held to the
    interface above."""
    module = _module_at(
        os.path.join(root, "benchmark", "families", family + ".py"),
        f"benchmark.families.{family}")
    faults = [] if hasattr(module, "TOY") else ["TOY"]
    if not callable(getattr(module, "train_flops_per_sample", None)):
        faults.append("train_flops_per_sample()")
    if faults:
        raise AttributeError(f"family {family!r} ({module.__file__}) lacks "
                             f"{faults}")
    for costs in ("STEP_COSTS", "COUNTED_COSTS"):
        if not hasattr(module, costs):
            setattr(module, costs, {})
    return module


def load_reference(config: dict, root: str):
    """The plain reference a configuration names.  Imported only by the
    driver that holds the served tokens to it: it imports jax."""
    path = config["reference"]
    module = _module_at(os.path.join(root, path),
                        "benchmark.families."
                        + os.path.splitext(os.path.basename(path))[0])
    for name in ("forward", "served_tokens_agree"):
        if not callable(getattr(module, name, None)):
            raise AttributeError(f"reference {path} lacks {name}()")
    return module
