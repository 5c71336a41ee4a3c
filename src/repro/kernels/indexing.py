"""The gathers and scatters of one slot-engine step, in two exact forms.

``core.network._slot_step_body`` reads and writes its per-link and
per-queue vectors only through one of these objects, so one physics
implementation serves every slot engine:

* :data:`XLA_INDEX` is plain ``jnp`` indexing — the reference engine and
  the per-step ``pallas`` engine run it in XLA.
* :class:`OneHotIndex` expresses every gather and scatter as a compare
  against an ``iota`` followed by a masked reduction.  The TPU's Pallas
  compiler (Mosaic) lowers that, and has no gather, scatter, ``cumsum``
  or vector reshape; the multi-step kernel body runs this form.

Both give identical int32 results for in-range indices (a one-hot
reduction sums exactly one term); scatter indices outside ``[0, n)``
are dropped by both.  Vectors stay 1-D or (rows, 2): shapes Mosaic
lays out without a reshape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class _XlaIndex:
    """Plain ``jnp`` indexing (the reference form)."""

    @staticmethod
    def table(t):
        return t

    @staticmethod
    def take(x, idx):
        return x[idx]

    @staticmethod
    def take_nr(table, i, j, k=None):
        return table[i, j] if k is None else table[i, j, k]

    @staticmethod
    def pick(q, col):
        return jnp.take_along_axis(q, col[:, None], axis=1)[:, 0]

    @staticmethod
    def flat(x):
        return x.reshape(-1)

    @staticmethod
    def unflat(x, k: int):
        return x.reshape(-1, k)

    @staticmethod
    def repeat(x, k: int):
        return jnp.repeat(x, k)

    @staticmethod
    def cumsum(x):
        return jnp.cumsum(x)

    @staticmethod
    def set(x, idx, vals):
        return x.at[idx].set(vals, mode="drop")

    @staticmethod
    def add(x, idx, vals):
        return x.at[idx].add(vals, mode="drop")


XLA_INDEX = _XlaIndex()


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


class OneHotIndex:
    """Gather/scatter as one-hot masked reductions (Mosaic-safe).

    ``n_routes`` is the route axis R of the (N, R[, K]) replication
    tables, which :meth:`table` flattens to (N·R[, K]) outside the
    kernel so that :meth:`take_nr` is a 1-D gather.
    """

    def __init__(self, n_routes: int):
        self.n_routes = int(n_routes)

    def table(self, t):
        return t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:])

    def take(self, x, idx):
        """``x[idx]`` for 1-D ``x`` and 1-D or (m, c) ``idx``."""
        if idx.ndim == 2:
            return jnp.stack([self.take(x, idx[:, c])
                              for c in range(idx.shape[1])], axis=1)
        hit = idx[:, None] == _iota((idx.shape[0], x.shape[0]), 1)
        return jnp.sum(jnp.where(hit, x[None, :], 0), axis=1)

    def take_nr(self, table, i, j, k=None):
        """``table[i, j]`` (or ``table[i, j, k]``) on the flattened table
        (see :meth:`table`)."""
        return self.take(table if k is None else table[:, k],
                         i * self.n_routes + j)

    def pick(self, q, col):
        """``q[r, col[r]]`` for every row r."""
        hit = _iota(q.shape, 1) == col[:, None]
        return jnp.sum(jnp.where(hit, q, 0), axis=1)

    def flat(self, x):
        """(m, k) -> (m·k,), row-major."""
        m, k = x.shape
        j = _iota((m * k,), 0)
        out = jnp.zeros((m * k,), x.dtype)
        for c in range(k):
            out = jnp.where(j % k == c, self.take(x[:, c], j // k), out)
        return out

    def unflat(self, x, k: int):
        """(m·k,) -> (m, k), row-major."""
        base = _iota((x.shape[0] // k,), 0) * k
        return jnp.stack([self.take(x, base + c) for c in range(k)], axis=1)

    def repeat(self, x, k: int):
        return self.take(x, _iota((x.shape[0] * k,), 0) // k)

    def cumsum(self, x):
        n = x.shape[0]
        le = _iota((n, n), 1) <= _iota((n, n), 0)
        return jnp.sum(jnp.where(le, x[None, :], 0), axis=1)

    # the scatters put the target axis on lanes (m, n): a long target such
    # as the (E,) delivery log then pads m, not n, to the 8x128 tile

    def set(self, x, idx, vals):
        """``x.at[idx].set(vals, mode="drop")`` for unique ``idx``."""
        hit = idx[:, None] == _iota((idx.shape[0], x.shape[0]), 1)
        new = jnp.sum(jnp.where(hit, vals[:, None], 0), axis=0)
        return jnp.where(jnp.any(hit, axis=0), new, x)

    def add(self, x, idx, vals):
        """``x.at[idx].add(vals, mode="drop")``."""
        hit = idx[:, None] == _iota((idx.shape[0], x.shape[0]), 1)
        return x + jnp.sum(jnp.where(hit, vals[:, None], 0), axis=0)
