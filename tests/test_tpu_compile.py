"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers a kernel with
``interpret=False`` for a described ``v5e:2x2`` topology and compiles it
with the TPU compiler, which refuses what interpret mode accepts
(unaligned blocks, int32 matmuls, gathers, scatters, too much VMEM).
The widths are the ones ``chip_smoke.py`` runs.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import network as net
from repro.core import traffic as tr
from repro.core.fabric import EngineSpec, Fabric
from repro.core.router import mesh2d_topology, ring_topology
from repro.kernels import fabric_queue as fqk
from repro.kernels import ops
from repro.kernels.aer_decode import aer_decode_pallas
from repro.kernels.aer_encode import aer_encode_pallas
from repro.kernels.fabric_queue import (fabric_queue_step_pallas,
                                        fabric_queue_update_pallas)
from repro.kernels.lif_step import lif_step_pallas

#: ring-16 under Poisson load, 64 events per chip: the engine-equivalence
#: check of chip_smoke.py (a (32, 1024) slot plane).
RING, EVENTS_PER_CHIP, CHUNK = 16, 64, 64
#: the fabric phase of chip_smoke.py: an 8x8 mesh, 4096 events per chip.
MESH, MESH_EVENTS_PER_CHIP = 8, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def ring_plan():
    fab = Fabric(ring_topology(RING), engine=EngineSpec(
        "pallas", kernel="multistep", chunk_size=CHUNK))
    spec = tr.poisson(jax.random.PRNGKey(0), RING, EVENTS_PER_CHIP)
    return fab._plan(spec, None)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel
    return compiled


def test_fabric_queue_scan(sds, ring_plan):
    q, c = 2 * RING, ring_plan.C
    _compile(lambda a, b, t: fabric_queue_step_pallas(a, b, t,
                                                      interpret=False),
             sds((q, c)), sds((q, c)), sds((q,)))


def test_fabric_queue_update(sds, ring_plan):
    q, c, lanes = 2 * RING, ring_plan.C, RING
    _compile(lambda *a: fabric_queue_update_pallas(*a, interpret=False),
             sds((q, c)), sds((q, c)), sds((q, c)),
             sds((lanes,)), sds((lanes,)),
             *[sds((lanes,)) for _ in range(5)])


def _multistep_plan(topo, events_per_chip):
    fab = Fabric(topo, engine=EngineSpec("pallas", kernel="multistep",
                                         chunk_size=CHUNK))
    spec = tr.poisson(jax.random.PRNGKey(0), topo.n_chips, events_per_chip)
    return fab._plan(spec, None)


def _lower_multistep(sds, plan):
    _eng, L, E, C, max_steps, mb, R, K, _kern, chunk = plan.bucket
    N = plan.route_out.shape[0]
    run = net._slot_run_multistep(L, E, C, max_steps, mb, chunk)
    args = (*[sds((2 * L, C))] * 3, sds((L, 2)), sds((L,)), sds((L, 2)),
            sds((N, R, K)), sds((N, R)), sds((N, R, K)),
            sds((L,)), sds((L,)), sds((L,)), sds(()), sds(()), sds(()))
    return jax.jit(run).lower(*args)


def test_fabric_queue_multistep_engine(sds, ring_plan, monkeypatch):
    """The whole multi-step engine at the ring-16 bucket: the chunk scan
    around the fused kernel, whose body is the full slot step."""
    monkeypatch.setenv("PALLAS_INTERPRET", "0")
    compiled = _lower_multistep(sds, ring_plan).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fabric_queue_multistep_mesh(sds, monkeypatch):
    """A mesh fabric (24 links, so the link-indexed one-hots outgrow the
    ring's) compiles within the kernel's VMEM budget."""
    monkeypatch.setenv("PALLAS_INTERPRET", "0")
    plan = _multistep_plan(mesh2d_topology(4, 4), 64)
    compiled = _lower_multistep(sds, plan).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fabric_queue_multistep_refuses_oversized_carry(sds, monkeypatch):
    """An 8x8 mesh at 256 events per chip is budgeted past the VMEM
    limit: the engine raises a ValueError naming the bytes, before Mosaic
    sees the kernel."""
    monkeypatch.setenv("PALLAS_INTERPRET", "0")
    plan = _multistep_plan(mesh2d_topology(MESH, MESH), 256)
    with pytest.raises(ValueError, match=rf"budgeted \d+ bytes .* above "
                       rf"the {fqk.VMEM_MAX_BYTES}-byte limit"):
        _lower_multistep(sds, plan)


def _while_bodies(hlo: str) -> dict[str, list[str]]:
    """Instruction lines of every while-loop body in an HLO module."""
    names = set(re.findall(r"body=%([\w.\-]+)", hlo))
    bodies, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            cur = head.group(1) if head.group(1) in names else None
            if cur:
                bodies[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur:
            bodies[cur].append(line)
    return bodies


def test_ring_engine_step_copies_no_buffer(sds):
    """The ring engine at the fabric phase's bucket (8x8 mesh, 262,144
    events): no step of its loop copies an array as large as the
    delivery log.  A layout change of the forward-stream buffer once
    copied the whole buffer on every step, which made the run's cost
    grow with the square of the event count."""
    fab = Fabric(mesh2d_topology(MESH, MESH))
    spec = tr.poisson(jax.random.PRNGKey(0), MESH * MESH,
                      MESH_EVENTS_PER_CHIP)
    _, Lp, Np, Ep, C0, Dp, Cf, Rp, Kp, chunk = fab._plan(spec, None).bucket
    run = net._ring_engine(Lp, Ep, C0, Dp, Cf, chunk)
    hlo = run.lower(
        *[sds((Lp, 2, C0))] * 3, sds((Lp, 2)), sds((Lp,)), sds((Lp, 2)),
        sds((Np, Rp, Kp)), sds((Np, Rp)), sds((Np, Rp, Kp)), sds((Lp, 2)),
        sds((Lp,)), sds((Lp,)), sds((Lp,)), *[sds(())] * 6,
    ).compile().as_text()
    bodies = _while_bodies(hlo)
    assert bodies
    for name, lines in bodies.items():
        for line in lines:
            op = re.match(r"\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]\S* "
                          r"copy\(", line)
            if op:
                size = math.prod(int(d) for d in op.group(1).split(",")
                                 if d)
                assert size < Ep, (name, line[:160])


def test_ring_engine_batch_step_keeps_one_buffer_layout(sds):
    """The batched ring engine at the batch cell's bucket (ring-16, 32
    instances of 64 Poisson events per chip, chunk 128): no step of its
    loops copies, slices, updates or reshapes an array holding one
    instance's stream buffer or more.  Carried as a (B, N) array the
    buffer was re-laid between the head gather's tiled layout and the
    tail scatter's flat one, all B instances, on every step."""
    B = 32
    fab = Fabric(ring_topology(RING), engine=EngineSpec("ring",
                                                        chunk_size=128))
    spec = tr.poisson(jax.random.PRNGKey(0), RING, EVENTS_PER_CHIP)
    _, Lp, Np, Ep, C0, Dp, Cf, Rp, Kp, chunk = fab._plan(spec, None).bucket
    run = net._ring_engine_batch(Lp, Ep, C0, Dp, Cf, chunk)
    shapes = [(Lp, 2, C0)] * 3 + [(Lp, 2), (Lp,), (Lp, 2), (Np, Rp, Kp),
              (Np, Rp), (Np, Rp, Kp), (Lp, 2), (Lp,), (Lp,), (Lp,)]
    scalars = [(B,)] * 3 + [()] + [(B,)] * 2    # max_steps is shared
    hlo = run.lower(*[sds((B, *s)) for s in shapes],
                    *[sds(s) for s in scalars]).compile().as_text()
    n_buf = 2 * Lp * Dp * Cf * 4     # one instance's stream entries
    bodies = _while_bodies(hlo)
    assert bodies
    for name, lines in bodies.items():
        for line in lines:
            op = re.match(r"\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]\S* "
                          r"(copy|dynamic-slice|dynamic-update-slice|"
                          r"reshape)\(", line)
            if op:
                size = math.prod(int(d) for d in op.group(1).split(",")
                                 if d)
                assert size < n_buf, (name, line[:160])


def test_aer_encode_decode(sds):
    nb, block, budget = 16, ops.DEFAULT_BLOCK, ops.DEFAULT_BUDGET
    _compile(lambda x, t: aer_encode_pallas(x, t, budget, interpret=False),
             sds((nb, block), jnp.float32), sds((nb,), jnp.float32))
    _compile(lambda i, v: aer_decode_pallas(i, v, block, interpret=False),
             sds((nb, budget)), sds((nb, budget), jnp.float32))


def test_lif_step(sds):
    _compile(lambda v, i: lif_step_pallas(v, i, decay=0.9, v_th=1.0,
                                          v_reset=0.0, interpret=False),
             sds((1024, 128), jnp.float32), sds((1024, 128), jnp.float32))
