"""MLLess significance filter — Pallas TPU kernels.

Kernel 1 (``block_norms``): per-block squared-L2 norms of a
(n_blocks, block) gradient view, one VMEM pass.

Kernel 2 (``masked_filter``): given the significance mask, emits the
filtered gradient and the error-feedback residual in a single fused
elementwise pass (the operation MLLess performs per update round).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _norm_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(x * x, axis=1, keepdims=True)


def block_norms(blocks, *, tile_rows=256, interpret=None):
    """blocks: (n_blocks, block) -> squared L2 norm per block (n_blocks,).
    ``interpret=None`` auto-detects the backend via
    ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    n, b = blocks.shape
    tile_rows = min(tile_rows, n)
    pad = (-n) % tile_rows
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
    np_ = blocks.shape[0]
    out = pl.pallas_call(
        _norm_kernel,
        grid=(np_ // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
        name="block_significance_norms",
    )(blocks)
    return out[:n, 0]


def _filter_kernel(x_ref, m_ref, keep_ref, resid_ref):
    x = x_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)          # (rows, 1) 0/1
    kept = x * m
    keep_ref[...] = kept.astype(keep_ref.dtype)
    resid_ref[...] = (x - kept).astype(resid_ref.dtype)


def masked_filter(blocks, mask, *, tile_rows=256, interpret=None):
    """blocks: (n, b); mask: (n,) bool -> (kept (n,b), residual (n,b)).
    ``interpret=None`` auto-detects the backend via
    ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    n, b = blocks.shape
    tile_rows = min(tile_rows, n)
    pad = (-n) % tile_rows
    m2 = mask.astype(jnp.float32)[:, None]
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
        m2 = jnp.pad(m2, ((0, pad), (0, 0)))
    np_ = blocks.shape[0]
    kept, resid = pl.pallas_call(
        _filter_kernel,
        grid=(np_ // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((tile_rows, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile_rows, b), lambda i: (i, 0)),
                   pl.BlockSpec((tile_rows, b), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((np_, b), blocks.dtype),
                   jax.ShapeDtypeStruct((np_, b), blocks.dtype)],
        interpret=interpret,
        name="block_significance_filter",
    )(blocks, m2)
    return kept[:n], resid[:n]
