"""Multi-device tests (8 forced host devices, subprocess): ring schedules
equal psum; AER sparse all-reduce converges with error feedback and ships
the promised wire volume."""

import pytest

from tests._subproc import run_with_devices

RING_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import halfduplex as hd
from jax.sharding import AxisType

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
for shape in [(8, 64), (8, 37), (8, 1), (8, 1024)]:
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def run(fn):
        f = jax.shard_map(fn, mesh=mesh, in_specs=P("data", None),
                      out_specs=P("data", None))
        return np.array(f(x))

    want = run(lambda t: jax.lax.psum(t, "data"))
    uni = run(lambda t: hd.ring_allreduce(t[0], "data")[None])
    bi = run(lambda t: hd.ring_allreduce(t[0], "data",
                                         bidirectional=True)[None])
    assert np.allclose(uni, want, rtol=1e-5, atol=1e-5), shape
    assert np.allclose(bi, want, rtol=1e-5, atol=1e-5), shape

# reduce-scatter places chunk i on device i
x = jnp.tile(jnp.arange(8.0)[None], (8, 1))  # every device holds [0..7]
@partial(jax.shard_map, mesh=mesh, in_specs=P("data", None),
         out_specs=P("data"))
def rs(t):
    return hd.ring_reduce_scatter(t[0], "data")
out = np.array(rs(x))  # (8,) — device i's chunk = 8 * i
assert np.allclose(out, 8.0 * np.arange(8)), out
print("RING-OK")
"""

AER_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import sparse_collectives as sc
from jax.sharding import AxisType

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(1)
g = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
target = np.array(g).mean(axis=0)

@partial(jax.shard_map, check_vma=False, mesh=mesh,
         in_specs=(P("data", None), P("data", None)),
         out_specs=(P("data", None), P("data", None), P("data")))
def step(gl, res):
    red, st, words = sc.aer_allreduce(
        gl[0], sc.AerState(res[0]), "data", frac=0.25, budget=1024,
        interpret=True)
    return red[None], st.residual[None], words[None]

res = jnp.zeros_like(g)
# 1) every member gets the IDENTICAL reduced tensor
red, res1, words = step(g, res)
red = np.array(red)
assert np.allclose(red, red[0:1], atol=0), "members disagree"
# 2) reduced + mean(residual) == true mean  (conservation)
recon = red[0] + np.array(res1).mean(axis=0)
assert np.allclose(recon, target, atol=1e-5), np.abs(recon-target).max()
# 3) error feedback: the TIME-AVERAGE of applied updates converges to the
# true mean at rate |r_T|/T (sum_t dec_t = T*g + r_0 - r_T)
T = 30
acc = np.zeros_like(target); cur_res = res
for t in range(T):
    red_t, cur_res, w = step(g, cur_res)
    acc += np.array(red_t[0])
err0 = np.abs(np.array(step(g, jnp.zeros_like(g))[0][0]) - target).max()
errT = np.abs(acc / T - target).max()
assert errT < err0 * 0.25, (err0, errT)
# 4) wire volume: <= budget words per block per device
nb = 4096 // 1024
assert int(np.array(words)[0]) <= nb * 1024
print("AER-OK", err0, errT)
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_ring_schedules_equal_psum():
    out = run_with_devices(RING_CODE, 8)
    assert "RING-OK" in out


@pytest.mark.slow
@pytest.mark.multidevice
def test_aer_allreduce_conservation_and_convergence():
    out = run_with_devices(AER_CODE, 8)
    assert "AER-OK" in out
