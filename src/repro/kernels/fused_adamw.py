"""Fused AdamW update — Pallas TPU kernel.

One VMEM pass reads (grad, m, v, param) tiles and writes
(update, m_new, v_new) — the TPU analogue of SPIRT's in-database model
update (state stays adjacent to compute; no separate m/v/param sweeps
over HBM).  Scalars (lr, betas, bias corrections) arrive via
scalar-prefetch-style operands broadcast into the kernel closure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adamw_kernel(g_ref, m_ref, v_ref, p_ref, c_ref,
                  u_ref, mo_ref, vo_ref, *, lr, b1, b2, eps, wd):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    p = p_ref[...].astype(jnp.float32)
    c1 = c_ref[0, 0]
    c2 = c_ref[0, 1]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    u = -lr * ((m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + wd * p)
    u_ref[...] = u.astype(u_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def fused_adamw_flat(g, m, v, p, c1, c2, *, lr, b1, b2, eps, wd,
                     tile=(256, 256), interpret=None):
    """All operands 1-D of equal length; returns (update, m_new, v_new).
    ``interpret=None`` auto-detects the backend (Mosaic on TPU, the
    interpreter elsewhere) via ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    n = g.shape[0]
    rows, cols = tile
    per = rows * cols
    pad = (-n) % per
    def prep(x, dt):
        x = x.astype(dt)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(-1, cols)
    g2 = prep(g, jnp.float32)
    m2 = prep(m, jnp.float32)
    v2 = prep(v, jnp.float32)
    p2 = prep(p, jnp.float32)
    R = g2.shape[0]
    cvec = jnp.stack([c1, c2]).astype(jnp.float32).reshape(1, 2)
    kernel = functools.partial(_adamw_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                               wd=wd)
    u2, mo2, vo2 = pl.pallas_call(
        kernel,
        grid=(R // rows,),
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))] * 4 +
                 [pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((R, cols), jnp.float32)] * 3,
        interpret=interpret,
        name="fused_adamw",
    )(g2, m2, v2, p2, cvec)
    unflat = lambda x: x.reshape(-1)[:n]
    return unflat(u2), unflat(mo2), unflat(vo2)
