"""The benchmark's files found by name: ``bench/<kind>/<name>.py``.

Drivers, topologies, traffic patterns and per-layer metric readers are
one file each, so a later change adds one by adding a file.  Each is
loaded once per process."""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
_loaded: dict = {}


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, name + ".py")
    if path not in _loaded:
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} file {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
