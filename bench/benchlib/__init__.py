"""Shared pieces of the benchmark: cell lookup, trace reduction, peaks, FLOPs."""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a driver, metric or reference)."""
    key = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, os.path.join(BENCH, kind, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]
