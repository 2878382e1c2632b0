"""One small reader per kind of per-layer metric.  A metric's file under
``benchmark/layer_metrics/`` names its reader; the harness imports
``benchmark.readers.<reader>`` and calls ``read(args, run)``.  A reader
that finds nothing to read returns None."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional


@dataclasses.dataclass
class ReaderInput:
    cell: object                # lib.runtime.Cell
    device_kind: str
    reduction: Optional[object]  # lib.xplane.Reduction of the traced window
    driver: dict                # what the driver handed over ("readers")


def read_metric(spec: dict, run: ReaderInput) -> Optional[float]:
    module = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    value = module.read(spec.get("args", {}), run)
    return None if value is None else float(value) * spec.get("scale", 1.0)
