"""A 2-D mesh of ``rows`` x ``cols`` chips: chip (r, c) is
``r * cols + c``; scanning chips in id order, the link to the right
neighbour comes before the link to the one below, the lower id on
side 0."""

import numpy as np


def links(topo: dict):
    rows, cols = int(topo["rows"]), int(topo["cols"])
    pairs = []
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            if c + 1 < cols:
                pairs.append((i, i + 1))
            if r + 1 < rows:
                pairs.append((i, i + cols))
    return rows * cols, np.asarray(pairs, np.int32).reshape(-1, 2)
