"""Paper technique at the training level: data-parallel training where the
gradient sync is the AER event-sparse all-reduce (top-k + error feedback)
or the bidirectional ring, compared against dense psum.

Runs 8-way manual DP on forced host devices (re-execs itself with
XLA_FLAGS) and reports loss parity + wire bytes per step.

    PYTHONPATH=src python examples/sparse_allreduce_demo.py
"""

import os
import subprocess
import sys

if "XLA_FLAGS" not in os.environ:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"    # forced host devices: never the chip
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    sys.exit(subprocess.call([sys.executable, __file__], env=env))

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig, get_smoke_config
from repro.core import sparse_collectives as sc
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.parallel.sharding import make_rules
from repro.runtime.train_loop import init_state, make_train_step

STEPS = 40


def train(dp_reduce: str):
    cfg = get_smoke_config("granite_3_2b")
    run_cfg = RunConfig(learning_rate=3e-3, warmup_steps=4,
                        total_steps=STEPS, dp_reduce=dp_reduce,
                        aer_frac=0.05, aer_budget=128, fsdp=False)
    model = build_model(cfg)
    mesh = make_host_mesh(data=8, model=1)
    rules = make_rules(mesh, fsdp=False, kv_heads=cfg.n_kv_heads,
                       d_head=cfg.d_head)
    data = SyntheticLM(cfg.vocab, 32, 16, seed=7)
    state = init_state(model, jax.random.PRNGKey(0), run_cfg)
    step = make_train_step(model, run_cfg, rules)
    losses, words = [], 0.0
    for s in range(STEPS):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        words += float(m["wire_words"])
    return losses, words


def main():
    n_params = None
    results = {}
    for mode in ("psum", "bidir_ring", "aer_topk"):
        losses, words = train(mode)
        results[mode] = (losses, words)
        print(f"{mode:11s} loss[0]={losses[0]:.4f} "
              f"loss[-1]={losses[-1]:.4f} wire_words/step="
              f"{words/STEPS:,.0f}")
    l_psum = results["psum"][0][-1]
    l_ring = results["bidir_ring"][0][-1]
    l_aer = results["aer_topk"][0][-1]
    print(f"\nbidir_ring vs psum final-loss delta: {abs(l_ring-l_psum):.5f} "
          f"(exact schedule, must be ~float noise)")
    print(f"aer_topk  vs psum final-loss delta: {abs(l_aer-l_psum):.5f} "
          f"(5% events/step + error feedback)")
    # wire economy: dense allreduce ships full grads; AER ships event slots
    cfg = get_smoke_config("granite_3_2b")
    model = build_model(cfg)
    p, _ = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(p))
    dense_b = sc.dense_allreduce_bytes(n, 8)
    aer_words = results["aer_topk"][1] / STEPS
    print(f"dense wire ≈ {dense_b:.3e} B/step/dir vs AER "
          f"{aer_words*4:.3e} B/step ({dense_b/(aer_words*4):.1f}x less)")


if __name__ == "__main__":
    main()
