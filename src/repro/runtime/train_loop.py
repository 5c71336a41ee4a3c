"""Training step factory + loop: DP/TP-sharded step with selectable
gradient-reduction schedule (the paper technique as a first-class knob).

Three execution modes share one step definition:

  * single-device (CPU tests/examples): plain ``jax.jit``;
  * SPMD "auto" (production dry-run): pjit with logical-rule shardings,
    gradient sync is XLA's psum — the paper-faithful DENSE baseline;
  * SPMD "manual DP" (ring / bidir_ring / aer_topk): ``shard_map`` manual
    over the DP axes with the model axis left automatic, so the TP einsums
    stay XLA-partitioned while the DP gradient reduction is the explicit
    schedule from ``core/halfduplex.py`` / ``core/sparse_collectives.py``.

Comm/compute overlap: gradient reduction is applied per-parameter-leaf as
the backward produces them; with microbatch accumulation
(``run_cfg.grad_accum``) reduction of accumulated grads overlaps the next
microbatch's backward (the TX/RX-FIFO double-buffering analogue).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import sparse_collectives as sc
from ..optim import adamw
from ..parallel.sharding import Rules, partition_params, use_rules


METRIC_KEYS = ("nll", "aux_loss", "z_loss", "drop_frac", "loss",
               "grad_norm", "lr", "wire_words")


class TrainState(NamedTuple):
    params: dict
    opt: adamw.AdamWState
    aer: dict | None          # error-feedback residuals (aer_topk only)
    step: jnp.ndarray


def init_state(model, key, run_cfg) -> TrainState:
    params, _ = model.init(key)
    opt = adamw.init(params)
    aer = sc.init_aer_states(params) if run_cfg.dp_reduce == "aer_topk" \
        else None
    return TrainState(params=params, opt=opt, aer=aer,
                      step=jnp.zeros((), jnp.int32))


def _loss_with_accum(model, params, batch, n_accum: int):
    """Mean loss over ``n_accum`` microbatches (scanned, grads accumulate)."""
    if n_accum <= 1:
        return model.loss(params, batch)

    def micro(carry, mb):
        loss, metrics = model.loss(params, mb)
        return carry + loss, metrics

    split = jax.tree.map(
        lambda x: x.reshape((n_accum, x.shape[0] // n_accum) + x.shape[1:]),
        batch)
    total, metrics = jax.lax.scan(micro, jnp.float32(0.0), split)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return total / n_accum, metrics


def make_train_step(model, run_cfg, rules: Rules | None = None):
    """Returns ``step(state, batch) -> (state, metrics)``.

    With ``rules`` (a mesh present), inputs/outputs carry NamedShardings;
    without, it is a plain jitted single-device step.
    """
    mode = run_cfg.dp_reduce

    def core_step(state: TrainState, batch, axis_name=None):
        def loss_fn(p):
            return _loss_with_accum(model, p, batch, run_cfg.grad_accum)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        wire_words = jnp.int32(0)
        aer = state.aer
        if axis_name is not None:
            grads, aer, wire_words = sc.reduce_gradients(
                grads, aer, axis_name, mode=mode, frac=run_cfg.aer_frac,
                budget=run_cfg.aer_budget)
            # metrics are per-shard means -> average them too
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, axis_name), metrics)
            loss = jax.lax.pmean(loss, axis_name)

        lr = adamw.warmup_cosine(
            state.step, base_lr=run_cfg.learning_rate,
            warmup_steps=run_cfg.warmup_steps,
            total_steps=run_cfg.total_steps)
        params, opt, gnorm = adamw.update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=run_cfg.weight_decay, grad_clip=run_cfg.grad_clip)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr,
                       wire_words=wire_words.astype(jnp.float32))
        return TrainState(params=params, opt=opt, aer=aer,
                          step=state.step + 1), metrics

    # ---------------- single device ----------------
    if rules is None:
        return jax.jit(core_step)

    mesh = rules.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    if mode == "psum":
        # SPMD auto: replicate-or-FSDP params; XLA inserts the gradient psum
        def step(state, batch):
            with use_rules(rules):
                return core_step(state, batch, axis_name=None)
        return jax.jit(step)

    # ---------------- manual DP (paper technique schedules) -------------
    # shard_map is MANUAL over the DP axes and every size-1 axis: a Pallas
    # kernel in the region (the aer_topk codec) cannot be partitioned
    # automatically, and a size-1 axis partitions nothing.  A model axis
    # wider than 1 stays automatic so TP constraints keep working.  Inside
    # the manual region the per-shard batch is local, and the activation
    # rules drop every manual axis (the logical "batch" axis maps to
    # nothing).
    import dataclasses

    axis_name = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    manual_axes = frozenset(dp_axes) | {
        a for a in mesh.axis_names if mesh.shape[a] == 1}

    def auto_only(v):
        axes = v if isinstance(v, tuple) else (v,)
        kept = tuple(a for a in axes if a is not None
                     and a not in manual_axes)
        if not kept:
            return None
        return kept if isinstance(v, tuple) else kept[0]

    inner_rules = dataclasses.replace(
        rules, act_map={k: auto_only(v) for k, v in rules.act_map.items()})

    def manual(state, batch):
        with use_rules(inner_rules):
            return core_step(state, batch, axis_name=axis_name)

    batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])

    def stepped(state, batch):
        in_specs = (jax.tree.map(lambda _: P(), state),
                    jax.tree.map(lambda _: batch_spec, batch))
        out_specs = (jax.tree.map(lambda _: P(), state),
                     {k: P() for k in METRIC_KEYS})
        fn = jax.shard_map(manual, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False,
                           axis_names=manual_axes)
        return fn(state, batch)

    return jax.jit(stepped)


def state_shardings(state, axes, rules: Rules):
    """NamedShardings for a TrainState given the model's logical axes tree
    (params / opt moments follow the param specs; scalars replicated)."""
    pspec = partition_params(axes, rules)
    rep = NamedSharding(rules.mesh, P())
    return TrainState(
        params=pspec,
        opt=adamw.AdamWState(step=rep, mu=pspec, nu=pspec),
        aer=None if state.aer is None else jax.tree.map(
            lambda _: rep, state.aer),
        step=rep,
    )
