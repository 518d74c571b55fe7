"""Serving-step builders: prefill and single-token decode under pjit.

Serving has no gradient sync, so steps run in pure auto (GSPMD) mode
with explicit input/output shardings.  For ``long_500k`` (batch 1) the
KV cache is sharded over the data axes on its *sequence* dim (context
parallelism for decode); the optimized flash-decode path with an
explicit log-sum-exp combine lives in ``repro.core.flash_decode``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sharding


@dataclasses.dataclass
class ServeStep:
    prefill_fn: Callable
    decode_fn: Callable
    param_shardings: Any
    make_inputs: Callable


def build_serve_step(model, mesh, *, data_axes=("data",),
                     model_axis="model", batch_size: int,
                     cache_len: int, swa_variant: bool = False):
    cfg = model.cfg
    example_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sharding.param_pspecs(example_params, mesh, fsdp=False,
                                   data_axes=data_axes,
                                   model_axis=model_axis)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    dp = data_axes if len(data_axes) > 1 else data_axes[0]
    W = int(np.prod([mesh.shape[a] for a in data_axes]))
    batch_shardable = batch_size % W == 0
    bspec = P(dp) if batch_shardable else P()

    example_cache = jax.eval_shape(
        lambda: model.init_cache(batch_size, cache_len,
                                 swa_variant=swa_variant))
    # kvquant caches nest {"q","scale"} one level deeper; the path-based
    # pspec assignment handles both layouts
    cache_specs = sharding.cache_pspecs(
        example_cache, mesh, batch_axes=dp, model_axis=model_axis,
        shard_seq=not batch_shardable)
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs,
                            is_leaf=lambda x: isinstance(x, P))

    prefill = jax.jit(
        functools.partial(model.prefill, cache_len=cache_len,
                          swa_variant=swa_variant),
        out_shardings=(NamedSharding(mesh, P(bspec[0] if batch_shardable
                                             else None, None, model_axis)),
                       cache_sh))

    def _decode(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos,
                                 swa_variant=swa_variant)

    decode = jax.jit(
        _decode,
        out_shardings=(
            NamedSharding(mesh, P(bspec[0] if batch_shardable else None,
                                  None, model_axis)),
            cache_sh),
        donate_argnums=(2,))

    def make_inputs(shape_kind: str, seq_len: int):
        """ShapeDtypeStructs for dry-run lowering (no allocation)."""
        B = batch_size
        tok_sh = NamedSharding(mesh, bspec)
        extras = {}
        if cfg.family == "vlm":
            extras["patch_emb"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.dtype(cfg.dtype),
                sharding=NamedSharding(mesh, P(bspec[0] if batch_shardable
                                               else None, None, None)))
        if cfg.is_encoder_decoder:
            extras["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype),
                sharding=NamedSharding(mesh, P(bspec[0] if batch_shardable
                                               else None, None, None)))
        if shape_kind == "prefill":
            batch = {"tokens": jax.ShapeDtypeStruct((B, seq_len), jnp.int32,
                                                    sharding=tok_sh)}
            batch.update(extras)
            return batch
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh)
        cache_sds = jax.tree.map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=sh),
            example_cache, cache_sh)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        return token, cache_sds, pos

    return ServeStep(prefill_fn=prefill, decode_fn=decode,
                     param_shardings=param_sh, make_inputs=make_inputs)
