"""Metric readers, one file per metric, found by the metric's name.

`bench/metrics/<name>.py` defines `read(r: Readings)`, which returns the
metric's value or None where the run gave it nothing to read.  End-to-end
readers take the host clock (r.window, r.setup_s); per-layer readers take
the trace of a traced run (r.trace) and the work counts of bench/model.py.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Optional

METRIC_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Readings:
    cfg: dict                      # the configuration file
    mix: dict                      # the traffic mix file
    peak: dict                     # bench/peaks.py entry of this chip
    window: object                 # bench.traffic.Window
    setup_s: float
    trace: Optional[object] = None  # bench.trace.Trace of a traced run


def reader(name: str):
    path = METRIC_DIR / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
