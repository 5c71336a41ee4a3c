"""Per-chip peaks and the fabric roofline (:func:`fabric_roofline_cells`).

The slot engines are memory-bound — every micro-transaction moves the packed
carry (``network.slot_carry_bytes``: 3 (Q, C) slot planes + link/side
lanes + logs) through memory, so the events/s ceiling is

  bound_ev_s = HBM_bw / bytes_per_event
  bytes_per_event = 2 * carry_bytes * launches_per_step * max_steps
                    / delivered

with ``launches_per_step`` = 1 for the per-step kernel pair (one full
read+write round-trip per micro-transaction) and ``1 / chunk`` for the
fused multi-step kernel (carry resident across ``chunk`` steps).  The
mode times both kernels on the benchmark ring and emits per-backend
cells (measured MEv/s vs the bound) into ``BENCH_fabric.json``.
"""

from __future__ import annotations

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "link_bytes_s": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device that is not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# ---------------------------------------------------------------------------
# Fabric roofline: packed-carry traffic vs memory bandwidth, per kernel
# ---------------------------------------------------------------------------

FABRIC_ROOFLINE_CHUNK = 64


def fabric_roofline_cells() -> list:
    """Measured fabric throughput vs the memory-bandwidth roofline.

    Runs the ring-16 hot-spot workload through ``engine="pallas"`` with
    both kernel choices and derives, from the engine's OWN packed state
    shapes (no profiler):

    * ``carry_bytes``     — ``slot_carry_bytes(L, E, C)``, the int32
      words one micro-transaction round-trips;
    * ``bytes_per_event`` — carry read+write per launch group, times
      launch groups per run, over delivered events;
    * ``bound_ev_s``      — HBM bandwidth / ``bytes_per_event``, the
      roofline ceiling for this shape on the device the run is on
      (:data:`PEAKS`; an unknown device raises);
    * ``measured_ev_s``   — delivered events over wall-clock, and the
      fraction of the bound it reaches.

    Every cell carries ``backend`` + ``kernel`` fields; ``compare.py``
    only gates same-backend ratios.
    """
    import time

    import jax
    import numpy as np

    from benchmarks.fabric_sweep import _derived, _metrics, stamp_env, _cell
    from repro.core import traffic as tr
    from repro.core.fabric import EngineSpec, Fabric
    from repro.core.network import slot_carry_bytes
    from repro.core.router import ring_topology

    hbm_bytes_s = peaks(jax.devices()[0].device_kind)["hbm_bytes_s"]
    topo = ring_topology(16)
    spec = tr.hot_spot(jax.random.PRNGKey(5), 16, 3, mean_gap_ns=150.0,
                       hot_frac=0.75)
    cells = []
    for kern in ("step", "multistep"):
        fab = Fabric(topo, engine=EngineSpec(
            name="pallas", kernel=kern, chunk_size=FABRIC_ROOFLINE_CHUNK))
        cf = fab.compile(spec)          # warmed: timing excludes compile
        t0 = time.perf_counter()
        res = cf.run(spec)
        jax.block_until_ready(res.log_del)
        us = (time.perf_counter() - t0) * 1e6

        _eng, L, E, C, max_steps, _mb, _R, _K, _kern, chunk = cf.bucket
        carry_bytes = slot_carry_bytes(L, E, C)
        steps_per_launch = chunk if kern == "multistep" else 1
        bytes_per_step = 2.0 * carry_bytes / steps_per_launch
        delivered = max(int(res.delivered), 1)
        bytes_per_event = bytes_per_step * max_steps / delivered
        bound_ev_s = hbm_bytes_s / bytes_per_event
        measured_ev_s = delivered / (us * 1e-6)
        m = _metrics(res)
        m.update({"carry_bytes": carry_bytes,
                  "bytes_per_event": bytes_per_event,
                  "bound_mev_s": bound_ev_s / 1e6,
                  "measured_wallclock_mev_s": measured_ev_s / 1e6,
                  "roofline_fraction": measured_ev_s / bound_ev_s,
                  "max_steps": max_steps,
                  "chunk": steps_per_launch})
        cells.append(_cell(
            f"fabric_roofline_pallas_{kern}", us,
            f"{_derived(m)} carry={carry_bytes}B "
            f"bound={m['bound_mev_s']:.0f}MEv/s "
            f"wallclock={m['measured_wallclock_mev_s']:.3f}MEv/s "
            f"({m['roofline_fraction']:.1e} of bound)",
            "pallas", metrics=m, api="Fabric", kernel=kern))
    return stamp_env(cells)

