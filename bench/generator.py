"""The benchmark's one traffic generator: streams of events from a seed.

A traffic mix is a JSON file under ``bench/traffic/`` that holds only
parameters.  Its ``pattern`` names a module of its own,
``bench/traffic/<pattern>.py``, whose ``generate(rng, n_chips, mix)``
returns one event stream ``(src, t, dest)`` of int32 numpy arrays.  The
streams are drawn on the host with numpy, so the chip and the CPU see
the same events.  A new pattern is a new file; no file here changes.

Instance ``i`` of a run is drawn from ``(seed, i)`` alone, so every
instance differs, any seed up to and past 2**31 works, and the same seed
gives the same instances in the same order.
"""

from __future__ import annotations

import hashlib

import numpy as np

from bench import plugins

def instance(mix: dict, n_chips: int, seed: int, i: int):
    """Event stream ``i`` of the run seeded ``seed``."""
    rng = np.random.default_rng([int(seed), int(i)])
    gen = plugins.load("traffic", mix["pattern"]).generate
    src, t, dest = gen(rng, n_chips, mix)
    return (np.asarray(src, np.int32), np.asarray(t, np.int32),
            np.asarray(dest, np.int32))


def digest(streams) -> str:
    """sha256 over the streams' arrays, in order (first 16 hex digits)."""
    h = hashlib.sha256()
    for s in streams:
        for a in s:
            h.update(np.ascontiguousarray(a, np.int32).tobytes())
    return h.hexdigest()[:16]
