"""Checkpointing (atomic/async/keep-N/elastic) + fault-tolerance driver
(bitwise-identical restart replay, straggler detection) + data determinism.
"""

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.configs.base import RunConfig, get_smoke_config
from repro.data import SyntheticLM
from repro.models.model import build_model
from repro.runtime.fault import (FailureInjector, SimulatedFailure,
                                 StragglerMonitor, run_with_restarts)
from repro.runtime.train_loop import init_state, make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("granite_3_2b")
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=2, total_steps=30)
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab, 16, 4, seed=2)
    step = make_train_step(model, run_cfg)

    class JaxData:
        def batch(self, s):
            return {k: jnp.asarray(v) for k, v in data.batch(s).items()}

    return cfg, run_cfg, model, JaxData(), step


class TestCheckpointer:
    def test_save_restore_roundtrip(self, setup):
        cfg, run_cfg, model, data, step = setup
        state = init_state(model, jax.random.PRNGKey(0), run_cfg)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(3, state, blocking=True)
            assert ck.latest_step() == 3
            restored = ck.restore(3, state)
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(restored.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_save_and_gc_keeps_n(self, setup):
        cfg, run_cfg, model, data, step = setup
        state = init_state(model, jax.random.PRNGKey(1), run_cfg)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=2)
            for s in (1, 2, 3, 4):
                ck.save(s, state)
            ck.wait()
            ck._gc()
            assert ck.all_steps() == [3, 4]

    def test_atomic_no_partial_on_existing(self, setup):
        cfg, run_cfg, model, data, step = setup
        state = init_state(model, jax.random.PRNGKey(1), run_cfg)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, state, blocking=True)
            # a tmp dir left behind (simulated crash) is never listed
            os.makedirs(os.path.join(d, ".tmp_step_9_123"), exist_ok=True)
            assert ck.all_steps() == [1]

    def test_elastic_reshard_restore(self, setup):
        """Restore onto a different mesh: leaves re-device_put with new
        shardings (1-device container: degenerate meshes, same contract)."""
        cfg, run_cfg, model, data, step = setup
        state = init_state(model, jax.random.PRNGKey(0), run_cfg)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, state, blocking=True)
            from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
            mesh = jax.make_mesh((1, 1), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            sh = jax.tree.map(
                lambda _: NamedSharding(mesh, P()), state)
            restored = ck.restore(1, state, shardings=sh)
            leaf = jax.tree.leaves(restored.params)[0]
            assert isinstance(leaf.sharding, NamedSharding)


class TestFaultTolerance:
    def test_restart_replay_is_bitwise_identical(self, setup):
        cfg, run_cfg, model, data, step = setup
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            s0 = init_state(model, jax.random.PRNGKey(0), run_cfg)
            clean, _ = run_with_restarts(
                n_steps=20, state=s0, train_step=step, data=data,
                ckpt=Checkpointer(d1), checkpoint_every=5)
            s0 = init_state(model, jax.random.PRNGKey(0), run_cfg)
            faulty, info = run_with_restarts(
                n_steps=20, state=s0, train_step=step, data=data,
                ckpt=Checkpointer(d2), checkpoint_every=5,
                injector=FailureInjector(frozenset({7, 13, 18})))
            assert info["restarts"] == 3
            for a, b in zip(jax.tree.leaves(clean.params),
                            jax.tree.leaves(faulty.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_failure_before_checkpoint_is_fatal(self, setup):
        cfg, run_cfg, model, data, step = setup
        s0 = init_state(model, jax.random.PRNGKey(0), run_cfg)
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(SimulatedFailure):
                run_with_restarts(
                    n_steps=10, state=s0, train_step=step, data=data,
                    ckpt=Checkpointer(d), checkpoint_every=0,  # never saves
                    injector=FailureInjector(frozenset({2})))

    def test_straggler_monitor_flags_outliers(self):
        mon = StragglerMonitor(threshold=2.0, alpha=0.5)
        for s in range(10):
            assert not mon.record(s, 1.0)
        assert mon.record(10, 5.0)          # 5x the EMA
        assert len(mon.events) == 1
        assert mon.ema == pytest.approx(1.0)  # outlier didn't poison EMA

    def test_max_restarts_bound(self, setup):
        cfg, run_cfg, model, data, step = setup
        s0 = init_state(model, jax.random.PRNGKey(0), run_cfg)

        class AlwaysFail:
            def check(self, step):
                raise SimulatedFailure("flaky node")

        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(SimulatedFailure):
                run_with_restarts(
                    n_steps=10, state=s0, train_step=step, data=data,
                    ckpt=Checkpointer(d), checkpoint_every=1,
                    injector=AlwaysFail(), max_restarts=3)


class TestDataPipeline:
    def test_batches_are_pure_functions_of_step(self):
        d1 = SyntheticLM(512, 16, 4, seed=9)
        d2 = SyntheticLM(512, 16, 4, seed=9)
        for s in (0, 5, 1000):
            a, b = d1.batch(s), d2.batch(s)
            np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_local_slices_partition_global_batch(self):
        d = SyntheticLM(512, 16, 8, seed=9)
        full = d.batch(3)
        parts = [d.local_slice(3, r, 4) for r in range(4)]
        np.testing.assert_array_equal(
            np.concatenate([p["tokens"] for p in parts]), full["tokens"])

    def test_prefetch_matches_sync(self):
        d = SyntheticLM(512, 16, 4, seed=9)
        got = list(d.prefetch(2, 3))
        assert [s for s, _ in got] == [2, 3, 4]
        np.testing.assert_array_equal(got[0][1]["tokens"],
                                      d.batch(2)["tokens"])

    def test_labels_are_learnable_structure(self):
        d = SyntheticLM(512, 64, 4, seed=0, structure=1.0)
        b = d.batch(0)
        # pure ramp: next token == current + stride (mod v)
        t = b["tokens"].astype(np.int64)
        strides = (t[:, 1:] - t[:, :-1]) % 512
        assert (strides == strides[:, :1]).all()
