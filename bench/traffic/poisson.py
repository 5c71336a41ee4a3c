"""Poisson traffic, with the semantics of the program's Poisson generator.

Each chip offers ``events_per_chip`` events.  Their gaps are independent
exponentials of mean ``mean_gap_ns``, each truncated to whole ns before
the running sum, and every destination is uniform over the other chips.
The stream is chip-major, with times nondecreasing per chip.
"""

import numpy as np


def generate(rng: np.random.Generator, n_chips: int, mix: dict):
    epc = int(mix["events_per_chip"])
    gaps = rng.exponential(size=(n_chips, epc)) * float(mix["mean_gap_ns"])
    t = np.cumsum(gaps.astype(np.int32), axis=1, dtype=np.int32)
    d = rng.integers(0, n_chips - 1, size=(n_chips, epc), dtype=np.int32)
    d = d + (d >= np.arange(n_chips, dtype=np.int32)[:, None])
    src = np.repeat(np.arange(n_chips, dtype=np.int32), epc)
    return src, t.reshape(-1), d.reshape(-1)
