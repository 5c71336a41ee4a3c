"""A ring of ``chips`` chips: link i joins chip i (side 0) to chip
i + 1 mod n; two chips share one link."""

import numpy as np


def links(topo: dict):
    n = int(topo["chips"])
    pairs = [(i, (i + 1) % n) for i in range(n)] if n > 2 else [(0, 1)]
    return n, np.asarray(pairs, np.int32).reshape(-1, 2)
