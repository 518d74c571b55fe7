"""Serving entrypoint: prefill a batch of prompts, decode N tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --reduced --batch 4 --prompt-len 64 --decode-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import build_serve_step
from repro.core.sharding import make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    model = build_model(cfg)
    cache_len = args.prompt_len + args.decode_tokens
    ss = build_serve_step(model, mesh, batch_size=args.batch,
                          cache_len=cache_len)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree.map(jax.device_put, params, ss.param_shardings)

    rs = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rs.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_emb"] = jnp.asarray(
            0.1 * rs.randn(args.batch, cfg.n_patches, cfg.d_model),
            jnp.dtype(cfg.dtype))
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            0.1 * rs.randn(args.batch, cfg.encoder_seq, cfg.d_model),
            jnp.dtype(cfg.dtype))

    t0 = time.time()
    logits, cache = ss.prefill_fn(params, batch)
    jax.block_until_ready(logits)
    print(f"prefill {args.batch}x{args.prompt_len}: "
          f"{time.time() - t0:.2f}s")

    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None] \
        .astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.decode_tokens):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, cache = ss.decode_fn(params, tok, cache, pos)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None] \
            .astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decoded {args.decode_tokens} tokens in {dt:.2f}s "
          f"({args.decode_tokens * args.batch / dt:.1f} tok/s)")
    print("sample:", np.asarray(jnp.concatenate(out, axis=1))[0][:16])


if __name__ == "__main__":
    main()
