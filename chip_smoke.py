"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths, on four chips

One chip:
  * fabric  — the AER fabric as a user builds it: an 8x8 mesh (64 chips)
    under Poisson traffic, 4096 events per chip (262,144 in all), on the
    default engine, with conservation checked and the warm run timed;
  * anchor  — the paper's Fig. 8 ring-2 ping-pong rate, 28.6 MEv/s;
  * engines — the reference, ring and compiled multi-step Pallas engines
    bit-identical on a ring-16 fabric;
  * kernels — every Pallas kernel of the fabric and aer_topk paths,
    compiled, against its pure-jnp oracle;
  * serve   — granite-3-2b at its published widths (random weights from
    a seed) answering four requests through ``repro.launch.serve``.

Four chips (``--chips 4``), and nothing else:
  * batch   — ``Fabric.run_batch`` of 32 ring-16 instances sharded over
    the four chips, bit-exact with the same batch on one chip;
  * dp      — data-parallel training of granite-3-2b, depth cut to 4
    layers, with ``psum`` and with ``aer_topk`` gradient reduction on one
    fixed batch; aer_topk must make a set share of psum's loss drop.

Everything runs in this one process.  The script exits non-zero, and
prints no result line, when JAX finds no TPU, when a Pallas kernel would
run in interpret mode, or when any phase fails.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: 8x8 mesh, Poisson load: 4096 events per chip = 262,144 injected.
MESH_SIDE, MESH_EVENTS_PER_CHIP = 8, 4096
#: ring-16 under Poisson load for the engine comparisons.
RING, RING_EVENTS_PER_CHIP, MULTISTEP_CHUNK = 16, 64, 64
#: paper Fig. 8 worst-case bidirectional rate and the allowed error.
ANCHOR_MEV_S, ANCHOR_TOL = 28.6, 1e-3
#: the four-chip batch.
BATCH = 32
#: data-parallel training: granite-3-2b widths, depth cut so that the
#: replicated input and output state (params, AdamW moments, AER
#: residuals) fit 16 GB per chip: 15.0 GB at 4 layers, 19.0 at 6.
DP_LAYERS, DP_STEPS, DP_SEQ, DP_GLOBAL_BATCH = 4, 6, 128, 8
#: the aer_topk codec settings of tests/test_train_modes.py.
DP_AER_FRAC, DP_AER_BUDGET = 0.1, 256
#: psum must lower the loss by this much for the comparison to mean
#: anything, and aer_topk must make at least this share of psum's drop.
#: A step that applies no update makes none (the batch is fixed).
DP_MIN_DROP, DP_MIN_SHARE = 0.25, 1 / 3


def say(*a):
    print(*a, flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def phase_fabric():
    import jax

    from repro.core import network as net
    from repro.core import traffic as tr
    from repro.core.fabric import Fabric
    from repro.core.router import mesh2d_topology

    n = MESH_SIDE * MESH_SIDE
    fab = Fabric(mesh2d_topology(MESH_SIDE, MESH_SIDE))
    spec = tr.poisson(jax.random.PRNGKey(0), n, MESH_EVENTS_PER_CHIP)
    t0 = time.perf_counter()
    cf = fab.compile(spec)
    t_compile = time.perf_counter() - t0
    runs = []
    for _ in range(2):       # the first run, then a warm one
        t0 = time.perf_counter()
        res = cf.run(spec)
        jax.block_until_ready(res.log_del)
        runs.append(time.perf_counter() - t0)
    delivered, drops = int(res.delivered), int(res.drops)
    lat = net.latency_stats(res)
    say(f"fabric: mesh {MESH_SIDE}x{MESH_SIDE} ({n} chips), poisson "
        f"{MESH_EVENTS_PER_CHIP} events/chip, engine "
        f"{fab.engine.resolved}, bucket {cf.bucket}")
    say(f"fabric: injected={res.injected} delivered={delivered} "
        f"drops={drops}")
    say(f"fabric: simulated {float(net.fabric_throughput_mev_s(res))} "
        f"MEv/s, latency p50={lat['p50_ns']} ns p99={lat['p99_ns']} ns")
    say(f"fabric: wall compile {t_compile} s, first run {runs[0]} s, "
        f"warm run {runs[1]} s")
    check(res.injected == n * MESH_EVENTS_PER_CHIP, "all events injected")
    check(delivered + drops == res.injected, "delivered + drops == injected")


def phase_anchor():
    from repro.core import network as net
    from repro.core import traffic as tr
    from repro.core.fabric import Fabric, QueuePolicy
    from repro.core.router import ring_topology

    fab = Fabric(ring_topology(2), queues=QueuePolicy(max_burst=1))
    res = fab.run(tr.ping_pong(2, 1024))
    thr = float(net.fabric_throughput_mev_s(res))
    err = abs(thr - ANCHOR_MEV_S) / ANCHOR_MEV_S
    say(f"anchor: ring-2 ping-pong {thr} MEv/s vs paper {ANCHOR_MEV_S} "
        f"(error {err})")
    check(err <= ANCHOR_TOL, f"Fig. 8 anchor within {ANCHOR_TOL:.1%}")


def phase_engines():
    import jax

    from repro.core import network as net
    from repro.core import traffic as tr
    from repro.core.fabric import EngineSpec, Fabric
    from repro.core.router import ring_topology

    topo = ring_topology(RING)
    spec = tr.poisson(jax.random.PRNGKey(0), RING, RING_EVENTS_PER_CHIP)
    engines = {
        "ring": "ring",
        "reference": "reference",
        "pallas-multistep": EngineSpec("pallas", kernel="multistep",
                                       chunk_size=MULTISTEP_CHUNK),
    }
    out = {}
    for name, eng in engines.items():
        t0 = time.perf_counter()
        out[name] = Fabric(topo, engine=eng).run(spec)
        jax.block_until_ready(out[name].log_del)
        say(f"engines: ring-{RING} {name}: delivered="
            f"{int(out[name].delivered)} t_end={int(out[name].t_end)} ns "
            f"({time.perf_counter() - t0} s incl. compile)")
    net.assert_results_equal(out["ring"], out["reference"],
                             "ring vs reference")
    net.assert_results_equal(out["ring"], out["pallas-multistep"],
                             "ring vs pallas multistep")
    check(int(out["ring"].delivered) == out["ring"].injected,
          "ring-16 delivers every event")
    say("engines: reference, ring and pallas multistep bit-identical")


def _random_queues(rng, nq, ncols):
    import numpy as np

    from repro.core.protocol_sim import BIG_NS
    q_time = rng.integers(0, 1 << 20, (nq, ncols))
    q_time = np.where(rng.random((nq, ncols)) < 0.3, int(BIG_NS), q_time)
    q_dest = rng.integers(0, 1 << 30, (nq, ncols))
    t_q = rng.integers(0, 1 << 20, (nq,))
    return (q_time.astype(np.int32), q_dest.astype(np.int32),
            t_q.astype(np.int32))


def phase_kernels():
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    nq, ncols, lanes = 2 * RING, 1024, RING
    q_time, q_dest, t_q = (jnp.asarray(a)
                           for a in _random_queues(rng, nq, ncols))
    got = ops.fabric_queue_scan(q_time, q_dest, t_q)
    want = ref.fabric_queue_scan(q_time, q_dest, t_q)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    say(f"kernels: fabric_queue_scan ({nq}x{ncols}) bit-exact")

    q_inj = rng.integers(0, 1 << 30, (nq, ncols)).astype(np.int32)
    # one pop per queue at most and append slots past every pop slot: the
    # update's contract (the engine pops each link's own queue once)
    pop_q = np.where(rng.random(lanes) < 0.8,
                     rng.permutation(nq)[:lanes], nq).astype(np.int32)
    pop_slot = rng.integers(0, ncols // 2, lanes).astype(np.int32)
    app_q = np.where(rng.random(lanes) < 0.8,
                     rng.integers(0, nq, lanes), nq).astype(np.int32)
    app_slot = (ncols - 1 - np.arange(lanes)).astype(np.int32)
    vals = [rng.integers(0, 1 << 31, lanes, dtype=np.int64).astype(np.int32)
            for _ in range(3)]
    args = tuple(jnp.asarray(a) for a in (q_time, q_dest, q_inj, pop_q,
                                          pop_slot, app_q, app_slot, *vals))
    got = ops.fabric_queue_update(*args)
    want = ref.fabric_queue_update(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    say(f"kernels: fabric_queue_update ({nq}x{ncols}, {lanes} lanes) "
        f"bit-exact")

    x = jnp.asarray(rng.standard_normal((16, ops.DEFAULT_BLOCK)),
                    jnp.float32)
    tau = ops.tau_from_fraction(x, 0.05)
    ev = ops.aer_compress(x, tau)
    want = ref.aer_encode(x, tau, ops.DEFAULT_BUDGET)
    for g, w in zip(ev, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    dense = ops.aer_decompress(ev)
    np.testing.assert_array_equal(
        np.asarray(dense), np.asarray(ref.aer_decode(ev.idx, ev.val,
                                                     ops.DEFAULT_BLOCK)))
    say(f"kernels: aer_encode/aer_decode (16x{ops.DEFAULT_BLOCK}, budget "
        f"{ops.DEFAULT_BUDGET}) bit-exact, {int(ev.count.sum())} events")

    v = jnp.asarray(rng.standard_normal((1024, 128)), jnp.float32)
    i_syn = jnp.asarray(rng.standard_normal((1024, 128)), jnp.float32)
    got = ops.lif_step(v, i_syn)
    want = ref.lif_step(v, i_syn, 0.9, 1.0, 0.0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)
    say("kernels: lif_step (1024x128) matches")


def phase_serve():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch import serve

    cfg = get_config("granite_3_2b")
    say(f"serve: {cfg.name} at published widths: {cfg.n_layers} layers, "
        f"d_model {cfg.d_model}, vocab {cfg.vocab}")
    out = serve.main(["--arch", "granite_3_2b", "--batch", "4",
                      "--prompt-len", "32", "--gen", "16"])
    tokens = np.asarray(out.tokens)
    logits = np.asarray(out.logits.astype(jnp.float32))
    say(f"serve: {out.param_bytes} parameter bytes on the device, "
        f"{out.tok_per_s} tok/s decode, tokens {tokens.shape}")
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        say(f"serve: peak device memory {mem['peak_bytes_in_use']} bytes")
    check(np.issubdtype(tokens.dtype, np.integer), "tokens are integers")
    check(tokens.shape == (4, 16), "4 requests x 16 tokens")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab)).all()),
          "tokens in [0, vocab)")
    check(bool(np.isfinite(logits).all()), "logits finite")


def phase_batch():
    import jax

    from repro.core import network as net
    from repro.core import traffic as tr
    from repro.core.fabric import Fabric
    from repro.core.router import ring_topology

    fab = Fabric(ring_topology(RING))
    specs = [tr.poisson(jax.random.PRNGKey(i), RING, RING_EVENTS_PER_CHIP)
             for i in range(BATCH)]
    one = fab.run_batch(specs)
    t0 = time.perf_counter()
    four = fab.run_batch(specs, devices=4)
    jax.block_until_ready(four.log_del)
    say(f"batch: ring-{RING} x {BATCH} instances on 4 devices "
        f"({time.perf_counter() - t0} s incl. compile)")
    for i in range(BATCH):
        net.assert_results_equal(one.instance(i), four.instance(i),
                                 f"instance {i}: 4 devices vs 1")
    say(f"batch: run_batch(devices=4) bit-exact with one device on all "
        f"{BATCH} instances")


def phase_dp():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import RunConfig, get_config
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build_model
    from repro.parallel.sharding import make_rules
    from repro.runtime.train_loop import init_state, make_train_step

    full = get_config("granite_3_2b")
    cfg = full.with_(n_layers=DP_LAYERS)
    say(f"dp: {cfg.name} at published widths (d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}), depth cut {full.n_layers} "
        f"-> {DP_LAYERS} layers; data=4 mesh, one global batch of "
        f"{DP_GLOBAL_BATCH} x {DP_SEQ} tokens, {DP_STEPS} steps")
    model = build_model(cfg)
    mesh = make_host_mesh(data=4, model=1)
    rules = make_rules(mesh, fsdp=False, kv_heads=cfg.n_kv_heads,
                       d_head=cfg.d_head)
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticLM(cfg.vocab, DP_SEQ, DP_GLOBAL_BATCH,
                         seed=7).batch(0).items()}
    losses = {}
    for mode in ("psum", "aer_topk"):
        run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=2,
                            total_steps=DP_STEPS, dp_reduce=mode,
                            aer_frac=DP_AER_FRAC, aer_budget=DP_AER_BUDGET,
                            fsdp=False)
        # the state is made replicated in place: one copy per chip
        state = jax.jit(lambda k: init_state(model, k, run_cfg),
                        out_shardings=NamedSharding(mesh, P()))(
            jax.random.PRNGKey(0))
        step = make_train_step(model, run_cfg, rules)
        ls = []
        t0 = time.perf_counter()
        for _ in range(DP_STEPS):
            state, m = step(state, batch)
            ls.append(float(m["loss"]))
        say(f"dp: {mode}: losses {ls} ({time.perf_counter() - t0} s incl. "
            f"compile), wire words last step {float(m['wire_words'])}")
        losses[mode] = np.asarray(ls)
        del state, m
    check(bool(np.isfinite(losses["aer_topk"]).all()
               and np.isfinite(losses["psum"]).all()), "finite losses")
    drop = {k: float(v[0] - v[-1]) for k, v in losses.items()}
    share = drop["aer_topk"] / drop["psum"]
    say(f"dp: loss drop psum {drop['psum']}, aer_topk {drop['aer_topk']} "
        f"(share {share}, at least {DP_MIN_SHARE}); final-loss gap "
        f"{losses['aer_topk'][-1] - losses['psum'][-1]}")
    check(drop["psum"] >= DP_MIN_DROP, f"psum lowers the loss by "
          f">= {DP_MIN_DROP}")
    check(share >= DP_MIN_SHARE, "aer_topk loss tracks psum")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")

    from repro.kernels.dispatch import resolve_interpret
    from repro.runtime.compile_cache import enable_compile_cache
    if resolve_interpret():
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode "
                 "(PALLAS_INTERPRET is set)")
    say(f"chip_smoke: {len(devices)} x {dev.device_kind}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")

    phases = ([phase_batch, phase_dp] if args.chips == 4 else
              [phase_fabric, phase_anchor, phase_engines, phase_kernels,
               phase_serve])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        say(f"{phase.__name__}: ok ({time.perf_counter() - t0} s)")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
