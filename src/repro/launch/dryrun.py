import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell on the production meshes and extract the roofline terms.

MUST be run as its own process (the first two lines force 512 host
devices before jax initializes — never set that globally).

Per cell:
  train_*    -> full train_step (fwd+bwd+AdamW) under the paper-faithful
                psum DP reduction (the baseline; hillclimb variants via
                --dp-reduce / --remat / --sp / --no-fsdp);
  prefill_*  -> model.prefill (forward + cache build, last-token logits);
  decode_*   -> model.decode_step against a seq_len-deep cache;
  encoder prefill -> model.score (full-sequence logits).

Outputs per cell: memory_analysis, cost_analysis (FLOPs/bytes), and the
collective-bytes breakdown parsed from post-SPMD HLO — written as JSON to
experiments/dryrun/<cell>.json for benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron_8b \
      --shape train_4k --mesh pod           # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import (ALL_SHAPES, ARCH_IDS, RunConfig, get_config,
                            input_specs, shapes_for)
from ..models.model import build_model
from ..optim import adamw
from ..parallel.sharding import make_rules, partition_params, use_rules
from ..runtime.train_loop import TrainState, make_train_step
from .mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# --------------------------------------------------------------------------
# Collective-bytes extraction from post-SPMD HLO
# --------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(\w[\w.-]*) = \S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _parse_shape_bytes(type_str: str) -> int:
    """Sum bytes over a possibly-tuple HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-op-kind OUTPUT bytes of every collective in the HLO module.

    Uses the op result type (for all-gather the gathered size, for
    reduce-scatter the scattered size...) as the per-device traffic proxy.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        lhs = line.split("=", 1)
        type_part = lhs[1].strip().split("(")[0]
        b = _parse_shape_bytes(type_part)
        out[kind] = out.get(kind, 0) + b
        out[kind + "_count"] = out.get(kind + "_count", 0) + 1
    return out


# --------------------------------------------------------------------------
# Cell construction
# --------------------------------------------------------------------------

def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_axis(mesh, b: int, rules=None):
    """Mesh axis (or axes tuple) for the batch dim, honoring rule
    overrides (act:batch=none for weight-stationary serving layouts)."""
    if rules is not None and "batch" in rules.act_map:
        ax = rules.act_map["batch"]
        if ax is None:
            return None
    dp = _dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    spec = dp if len(dp) > 1 else dp[0]
    return spec if b % dp_size == 0 else None


def _batch_sharding(mesh, b: int, rules=None):
    ax = _batch_axis(mesh, b, rules)
    return P(ax) if ax is not None else P()


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
        tree, shardings)


def build_cell(arch: str, shape_name: str, mesh, run_cfg: RunConfig,
               cfg_overrides: dict | None = None):
    """Returns (lower_thunk, meta). lower_thunk() -> jax.stages.Lowered."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.with_(**cfg_overrides)
    shape = ALL_SHAPES[shape_name]
    assert shape in shapes_for(cfg), f"{arch} skips {shape_name}"
    model = build_model(cfg)
    rules = make_rules(mesh, fsdp=run_cfg.fsdp,
                       seq_parallel=getattr(run_cfg, "seq_parallel", False),
                       kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
                       overrides=dict(run_cfg.rules_overrides)
                       if run_cfg.rules_overrides else None)
    specs = input_specs(cfg, shape)
    bspec = _batch_sharding(mesh, shape.global_batch, rules)
    batch_sh = {k: NamedSharding(mesh, P(*(bspec + (None,) * (len(v.shape) - len(bspec)))))
                for k, v in specs.items()}
    batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                         sharding=batch_sh[k])
                 for k, v in specs.items()}

    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "mesh": dict(mesh.shape), "cfg_overrides": cfg_overrides or {},
            "dp_reduce": run_cfg.dp_reduce}
    return model, cfg, rules, shape, batch_abs, meta


def lower_cell(arch: str, shape_name: str, mesh, run_cfg: RunConfig,
               cfg_overrides: dict | None = None):
    model, cfg, rules, shape, batch_abs, meta = build_cell(
        arch, shape_name, mesh, run_cfg, cfg_overrides)

    # abstract params + shardings (no allocation anywhere): eval_shape
    # traces init in Python, so the STATIC axes tree is captured by side
    # effect while the array tree stays abstract.
    axes_box = {}

    def init_fn(key):
        p, a = model.init(key)
        axes_box["axes"] = a
        return p

    params_abs = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    axes = axes_box["axes"]
    param_sh = partition_params(axes, rules)

    if shape.kind == "train":
        aer_abs = None
        if run_cfg.dp_reduce == "aer_topk":
            from ..core.sparse_collectives import AerState
            rep = NamedSharding(mesh, P())
            aer_abs = jax.tree.map(
                lambda t: AerState(residual=jax.ShapeDtypeStruct(
                    t.shape, t.dtype, sharding=rep)),
                params_abs,
                is_leaf=lambda t: isinstance(t, jax.ShapeDtypeStruct))
        state_abs = TrainState(
            params=_abstract(params_abs, param_sh),
            opt=adamw.AdamWState(
                step=jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=NamedSharding(mesh, P())),
                mu=_abstract(jax.tree.map(
                    lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32),
                    params_abs), param_sh),
                nu=_abstract(jax.tree.map(
                    lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32),
                    params_abs), param_sh)),
            aer=aer_abs,
            step=jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P())))
        step_fn = make_train_step(model, run_cfg, rules)
        with jax.set_mesh(mesh):
            lowered = step_fn.lower(state_abs, batch_abs)
        return lowered, meta

    params_in = _abstract(params_abs, param_sh)

    if shape.kind == "prefill":
        if not cfg.causal:
            def score(p, b):
                with use_rules(rules):
                    return model.score(p, b)
            fn = jax.jit(score)
        else:
            def prefill(p, b):
                with use_rules(rules):
                    return model.prefill(p, b, max_len=shape.seq_len)
            fn = jax.jit(prefill)
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_in, batch_abs)
        return lowered, meta

    # decode: one token against a seq_len cache
    b = shape.global_batch
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(b, shape.seq_len))
    cache_sh = _cache_shardings(cache_abs, mesh, rules)
    cache_in = _abstract(cache_abs, cache_sh)
    bax = _batch_axis(mesh, b, rules)
    tok = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32, sharding=NamedSharding(mesh, P(bax, None)))
    pos = jax.ShapeDtypeStruct(
        (b,), jnp.int32, sharding=NamedSharding(mesh, P(bax)))

    def decode(p, c, t, q):
        with use_rules(rules):
            return model.decode_step(p, c, t, q)

    fn = jax.jit(decode)
    with jax.set_mesh(mesh):
        lowered = fn.lower(params_in, cache_in, tok, pos)
    return lowered, meta


def _bspec_tuple(mesh, b):
    dp = _dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if b % dp_size == 0:
        return ((dp if len(dp) > 1 else dp[0]),)
    return (None,)


def _cache_shardings(cache_abs, mesh, rules):
    """Cache leaves: (periods, B, S|W, K, dh) k/v; (periods, B, W) slot_pos;
    (periods, B, d_in, N) mamba h; (periods, B, d_conv-1, d_in) conv.
    All specs follow the logical rules (incl. overrides)."""
    inner = rules.act_map.get("mamba_inner", "model")

    def spec_for(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        nd = len(leaf.shape)
        b = leaf.shape[1]
        bspec = _batch_axis(mesh, b, rules)
        if name in ("k", "v") and nd == 5:
            return NamedSharding(mesh, P(None, bspec,
                                         rules.act_map.get("kv_seq"),
                                         rules.act_map.get("heads_kv"),
                                         None))
        if name == "slot_pos":
            return NamedSharding(mesh, P(None, bspec, None))
        if name == "h" and nd == 4:
            return NamedSharding(mesh, P(None, bspec, inner, None))
        if name == "conv" and nd == 4:
            return NamedSharding(mesh, P(None, bspec, None, inner))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, cache_abs)


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------

def run_cell(arch, shape_name, mesh_kind, run_cfg, cfg_overrides=None,
             out_dir=OUT_DIR, tag=""):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, run_cfg,
                               cfg_overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from . import hlo_cost
    loop_aware = hlo_cost.analyze(hlo)

    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    rec = dict(meta)
    rec.update({
        "mesh_kind": mesh_kind,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "xla_flops_once": float(cost.get("flops", -1)),
        "xla_bytes_once": float(cost.get("bytes accessed", -1)),
        "flops": loop_aware["flops"],
        "bytes_accessed": loop_aware["bytes_accessed"],
        "collectives": loop_aware["collectives"],
        "collective_bytes_total": loop_aware["collective_bytes_total"],
        "unknown_trip_count_loops": loop_aware["unknown_trip_count_loops"],
        "collectives_static_text": coll,
        "memory": {
            "argument_size_in_bytes": getattr(
                mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(
                mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    })
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}--{shape_name}--{mesh_kind}{('--' + tag) if tag else ''}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    import gzip
    with gzip.open(os.path.join(out_dir, name + ".hlo.gz"), "wt") as f:
        f.write(hlo)
    print(f"[OK] {name}: compile={t_compile:.1f}s flops={rec['flops']:.3e} "
          f"bytes={rec['bytes_accessed']:.3e} "
          f"coll={rec['collective_bytes_total']:.3e}B "
          f"loops?={rec['unknown_trip_count_loops']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dp-reduce", default="psum")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=0)
    ap.add_argument("--kv-chunk", type=int, default=0)
    ap.add_argument("--param-dtype", default=None,
                    choices=[None, "bf16", "f32"],
                    help="bf16 = inference-style weights (serve cells)")
    ap.add_argument("--rules-override", action="append", default=[],
                    help="logical rule override, e.g. "
                         "mamba_inner=data+model or act:batch=none")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args()

    def _parse_rule(s):
        k, v = s.split("=", 1)
        if v == "none":
            val = None
        elif "+" in v:
            val = tuple(v.split("+"))
        else:
            val = v
        return k, val

    run_cfg = RunConfig(dp_reduce=args.dp_reduce, fsdp=not args.no_fsdp,
                        seq_parallel=args.sp,
                        rules_overrides=tuple(
                            _parse_rule(s) for s in args.rules_override))
    overrides = {}
    if args.remat:
        overrides["remat"] = args.remat
    if args.q_chunk:
        overrides["q_chunk"] = args.q_chunk
    if args.kv_chunk:
        overrides["kv_chunk"] = args.kv_chunk
    if args.param_dtype:
        overrides["param_dtype"] = (jnp.bfloat16 if args.param_dtype == "bf16"
                                    else jnp.float32)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in shapes_for(cfg):
                cells.append((arch, shape.name))
    else:
        cells.append((args.arch, args.shape))

    failures = []
    for mesh_kind in meshes:
        for arch, shape in cells:
            try:
                run_cell(arch, shape, mesh_kind, run_cfg,
                         overrides or None, out_dir=args.out_dir,
                         tag=args.tag)
            except Exception as e:
                failures.append((arch, shape, mesh_kind, repr(e)))
                print(f"[FAIL] {arch}--{shape}--{mesh_kind}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
