"""Roofline analysis over the dry-run artifacts (§Roofline of EXPERIMENTS).

Reads experiments/dryrun/<arch>--<shape>--<mesh>[--tag].json and derives,
per cell, on TPU v5e constants:

  compute term    = HLO_FLOPs(per-device) / peak_FLOP/s
  memory term     = HLO_bytes(per-device) / HBM_bw
  collective term = collective_bytes(per-device) / link_bw

(the dry-run JSON stores PER-DEVICE numbers: the HLO module is the
post-SPMD per-device program), plus MODEL_FLOPS = 6·N·D (dense) /
6·N_active·D (MoE) and the useful-compute ratio.

Plus a FABRIC roofline mode (:func:`fabric_roofline_cells`): the slot
engines are memory-bound — every micro-transaction moves the packed
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

import glob
import json
import os
import sys

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "link_bytes_s": 50e9},
}

#: The part the dry-run artifacts were compiled for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device that is not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")

# active params (N for MODEL_FLOPS): computed from configs
def _active_params(arch: str) -> float:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.configs.base import get_config
    from repro.models.layers import padded_vocab
    cfg = get_config(arch)
    D, L, V = cfg.d_model, cfg.n_layers, padded_vocab(cfg.vocab)
    H, Kv, dh, F = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff

    def attn_p():
        return D * H * dh + 2 * D * Kv * dh + H * dh * D

    def ffn_p(f=None):
        f = f or F
        gated = cfg.act in ("silu", "gelu") and cfg.family != "encoder"
        return (3 if gated else 2) * D * f

    def moe_active():
        m = cfg.moe
        return m.top_k * 3 * D * F + D * m.num_experts

    def mamba_p():
        m = cfg.mamba
        d_in = m.expand * D
        R = cfg.dt_rank
        return (D * 2 * d_in + m.d_conv * d_in + d_in * (R + 2 * m.d_state)
                + R * d_in + d_in * D)

    from repro.models.transformer import pattern_for
    pat = pattern_for(cfg)
    per_period = 0.0
    for kind in pat:
        if kind.startswith("attn") or kind.startswith("xattn"):
            per_period += attn_p()
        else:
            per_period += mamba_p()
        if kind.endswith("_ffn"):
            per_period += ffn_p()
        elif kind.endswith("_moe"):
            per_period += moe_active()
    n_periods = L // len(pat)
    body = per_period * n_periods
    embed = V * D + (0 if cfg.tie_embeddings else D * V)
    return body + embed


def _ssm_state_flops_per_token(arch: str) -> float:
    """Selective-scan state math NOT captured by 6·N·D: per mamba layer
    ~9 multiply-adds per (d_inner × d_state) element per token (discretize,
    recurrence, output contraction), ×3 for fwd+bwd+remat."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.configs.base import get_config
    from repro.models.transformer import pattern_for
    cfg = get_config(arch)
    if cfg.mamba is None:
        return 0.0
    pat = pattern_for(cfg)
    n_mamba = sum(1 for k in pat if k.startswith("mamba")) * (
        cfg.n_layers // len(pat))
    d_in = cfg.mamba.expand * cfg.d_model
    return 9.0 * 3.0 * n_mamba * d_in * cfg.mamba.d_state


def analyze_cell(rec: dict, device_kind: str = DRYRUN_DEVICE_KIND) -> dict:
    pk = peaks(device_kind)
    n_dev = rec["n_devices"]
    flops_dev = rec["flops"]
    bytes_dev = rec["bytes_accessed"]
    coll_dev = rec.get("collective_bytes_total", 0.0)

    t_compute = flops_dev / pk["flops"]
    t_memory = bytes_dev / pk["hbm_bytes_s"]
    t_coll = coll_dev / pk["link_bytes_s"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)

    out = dict(rec)
    out.update({
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "bound_time_s": max(terms.values()),
        "roofline_fraction": t_compute / max(terms.values())
        if max(terms.values()) > 0 else 0.0,
    })

    # useful-compute ratio for train cells
    if rec["kind"] == "train":
        try:
            n_active = _active_params(rec["arch"])
            # tokens per step (global)
            from repro.configs.base import ALL_SHAPES
            sh = ALL_SHAPES[rec["shape"]]
            model_flops_global = 6.0 * n_active * sh.global_batch * sh.seq_len
            hlo_flops_global = flops_dev * n_dev
            out["model_flops_global"] = model_flops_global
            out["useful_ratio"] = model_flops_global / max(
                hlo_flops_global, 1.0)
            ssm = _ssm_state_flops_per_token(rec["arch"])
            if ssm:
                adj = model_flops_global + ssm * sh.global_batch * sh.seq_len
                out["useful_ratio_ssm_adjusted"] = adj / max(
                    hlo_flops_global, 1.0)
        except Exception as e:          # pragma: no cover
            out["useful_ratio_error"] = repr(e)
    return out


def load_cells(mesh="pod", tag=None, dryrun_dir=DRYRUN_DIR):
    cells = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("mesh_kind") != mesh:
            continue
        base = os.path.basename(path)[:-5].split("--")
        cell_tag = base[3] if len(base) > 3 else ""
        if (tag or "") != cell_tag:
            continue
        cells.append(analyze_cell(rec))
    return cells


def table(cells, fmt="md"):
    hdr = ["arch", "shape", "dominant", "t_comp(ms)", "t_mem(ms)",
           "t_coll(ms)", "roofline", "useful"]
    lines = ["| " + " | ".join(hdr) + " |",
             "|" + "---|" * len(hdr)]
    for c in sorted(cells, key=lambda c: (c["arch"], c["shape"])):
        lines.append("| " + " | ".join([
            c["arch"], c["shape"], c["dominant"],
            f"{c['t_compute_s']*1e3:.2f}", f"{c['t_memory_s']*1e3:.2f}",
            f"{c['t_collective_s']*1e3:.2f}",
            f"{c['roofline_fraction']:.2f}",
            f"{c.get('useful_ratio', float('nan')):.2f}"
            if "useful_ratio" in c else "-",
        ]) + " |")
    return "\n".join(lines)


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


def run():
    """Benchmark-harness entry: summarize baseline cells."""
    cells = load_cells("pod")
    rows = []
    for c in cells:
        rows.append((f"roofline_{c['arch']}--{c['shape']}", 0.0,
                     f"dom={c['dominant']} frac={c['roofline_fraction']:.2f} "
                     f"comp={c['t_compute_s']*1e3:.1f}ms "
                     f"mem={c['t_memory_s']*1e3:.1f}ms "
                     f"coll={c['t_collective_s']*1e3:.1f}ms"))
    if not rows:
        rows.append(("roofline", 0.0, "no dryrun artifacts yet"))
    return rows


if __name__ == "__main__":
    cells = load_cells(sys.argv[1] if len(sys.argv) > 1 else "pod")
    print(table(cells))
