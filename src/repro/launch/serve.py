"""Serving entry point: batched prefill + decode loop.

The full serving path every decode-shape dry-run cell lowers, at
published widths (or ``--smoke`` widths on a CPU): prefill a batch of prompts, then step the KV/SSM caches token by
token with greedy sampling.  The same step functions are what the
``decode_32k`` / ``long_500k`` cells compile for the production mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x22b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import get_config, get_smoke_config
from ..data import SyntheticLM
from ..models.model import build_model


class ServeResult(NamedTuple):
    tokens: jax.Array        # (batch, gen) int32 greedy tokens
    logits: jax.Array        # (batch, 1, vocab_padded) last decode logits
    param_bytes: int         # bytes of the weights on the device
    tok_per_s: float         # decode tokens per second (batch summed)


def main(argv=None) -> ServeResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert cfg.causal, f"{args.arch} is encoder-only — nothing to decode"
    model = build_model(cfg)
    # one jitted init: the full-width weights are generated in place on
    # the device, with no eager per-op temporaries beside them
    params = jax.jit(lambda k: model.init(k)[0])(
        jax.random.PRNGKey(args.seed))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {param_bytes} parameter bytes on "
          f"{jax.devices()[0].device_kind}")

    data = SyntheticLM(cfg.vocab, args.prompt_len, args.batch,
                       seed=args.seed, modality=cfg.modality,
                       d_frontend=cfg.d_frontend,
                       n_img_tokens=cfg.n_img_tokens)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()
             if k not in ("labels", "mask")}
    max_len = args.prompt_len + args.gen

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, batch)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    tok_per_s = (args.gen - 1) * args.batch / max(t_decode, 1e-9)
    print(f"{cfg.name}: prefill({args.batch}x{args.prompt_len}) "
          f"{t_prefill*1e3:.1f} ms; decode {args.gen - 1} steps "
          f"{t_decode*1e3:.1f} ms ({tok_per_s:.1f} tok/s)")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: {list(map(int, gen[b][:12]))}")
    return ServeResult(gen, logits, param_bytes, tok_per_s)


if __name__ == "__main__":
    main()
