"""Half-duplex / bidirectional ring collectives (paper technique, layer 2).

The paper's transceiver shares ONE physical bus between two directions and
switches on demand; the measured lesson is that a reversal costs only
~4 ns against a 31 ns event cycle, so keeping a link busy in both
directions is nearly free.  On TPU the ICI links are physically
bidirectional, but a *unidirectional* ring schedule (the naive "two
parallel buses" design the paper argues against) drives each link in one
direction only and leaves half the aggregate wire bandwidth idle.

``bidirectional=True`` splits every payload in half and runs two
counter-rotating rings concurrently — both directions of every link carry
useful traffic, halving the wall-clock of the bandwidth term exactly like
the paper's shared bus halves the pin count.  These run inside
``shard_map`` over a DP axis via ``jax.lax.ppermute``.

All variants are numerically equivalent to ``jax.lax.psum`` (tested on 8
host devices) and are selectable as the gradient-reduction schedule in
``runtime/train_loop.py`` (``dp_reduce = ring | bidir_ring``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp


def _ring_perm(n, reverse=False):
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def _pad_to(x, mult):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % mult
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def ring_reduce_scatter(x, axis_name, *, reverse=False):
    """Unidirectional ring reduce-scatter over ``axis_name``.

    x: identical-shape local array per device. Returns this device's
    reduced chunk (flattened, 1/n of padded x).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    flat, _ = _pad_to(x, n)
    chunks = flat.reshape(n, -1)
    perm = _ring_perm(n, reverse)
    sign = -1 if reverse else 1

    # step s: device i adds its local copy of chunk (i - sign*(s+1)) to the
    # accumulating partial and passes it on; after n-1 steps device i holds
    # the full sum of chunk i... shifted by ring direction.
    def body(s, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        cid = (idx - sign * (s + 2)) % n
        return acc + chunks[cid]

    acc0 = chunks[(idx - sign) % n]
    acc = jax.lax.fori_loop(0, n - 1, body, acc0) if n > 1 else chunks[idx]
    return acc  # device i holds reduced chunk ((i - sign*(n)) % n == i)


def ring_all_gather(x, axis_name, *, reverse=False):
    """Unidirectional ring all-gather: local chunk -> (n * chunk) flat."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n, reverse)
    sign = -1 if reverse else 1
    out = jnp.zeros((n,) + x.shape, x.dtype).at[idx].set(x)

    def body(s, carry):
        out, buf = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        src = (idx - sign * (s + 1)) % n
        out = out.at[src].set(buf)
        return out, buf

    if n > 1:
        out, _ = jax.lax.fori_loop(0, n - 1, body, (out, x))
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_allreduce(x, axis_name, *, bidirectional=False):
    """Ring all-reduce == psum(x, axis_name), as RS + AG.

    bidirectional=True: payload split in half, two counter-rotating rings —
    both ICI link directions utilized (the paper-adapted schedule).
    """
    shape, dtype = x.shape, x.dtype
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if not bidirectional:
        flat, pad = _pad_to(x, n)
        red = ring_reduce_scatter(x, axis_name)
        full = ring_all_gather(red, axis_name)
        if pad:
            full = full[:flat.shape[0] - pad]
        return full[:x.size].reshape(shape).astype(dtype)

    flat, pad = _pad_to(x, 2 * n)
    half = flat.reshape(2, -1)
    fwd, bwd = half[0], half[1]
    red_f = ring_reduce_scatter(fwd, axis_name, reverse=False)
    red_b = ring_reduce_scatter(bwd, axis_name, reverse=True)
    full_f = ring_all_gather(red_f, axis_name, reverse=False)
    full_b = ring_all_gather(red_b, axis_name, reverse=True)
    out = jnp.concatenate([full_f, full_b])
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def wire_bytes_per_direction(n_bytes_payload: int, n_devices: int,
                             bidirectional: bool) -> float:
    """Ring all-reduce ships 2*(n-1)/n of the payload per device.  A
    unidirectional ring puts all of it on one link direction; the
    bidirectional schedule splits it across both — the per-direction (i.e.
    wall-clock-critical) traffic halves, the paper's pin-saving argument in
    byte units."""
    total = 2 * (n_devices - 1) / n_devices * n_bytes_payload
    return total / (2 if bidirectional else 1)
