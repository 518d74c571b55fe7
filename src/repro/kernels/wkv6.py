"""RWKV6 chunked WKV recurrence — Pallas TPU kernel.

The wkv state update is the compute hot-spot of the attention-free SSM
architecture (rwkv6-7b).  Tiling: grid (batch, head); each program keeps
the (N, N) state resident in VMEM and walks the sequence in chunks of
``chunk`` steps — intra-chunk pairwise-decay attention (MXU matmuls) +
inter-chunk state propagation, exactly the GLA-style parallel form of
``repro.models.rwkv6`` (whose scan carries the state through HBM every
chunk; here it never leaves VMEM).

Shapes: r, k, v, logw: (B, T, H, N); u: (H, N); returns y: (B, T, H, N).
T must be a multiple of ``chunk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, *, chunk,
                seq_len):
    c = chunk
    n_chunks = seq_len // c
    N = r_ref.shape[-1]
    u = u_ref[...].astype(jnp.float32)                   # (N,)
    tidx = jax.lax.iota(jnp.int32, c)
    mask = (tidx[:, None] > tidx[None, :]).astype(jnp.float32)  # strict LT

    def chunk_body(ci, S):
        sl = pl.ds(ci * c, c)
        r = r_ref[sl, :].astype(jnp.float32)             # (c, N)
        k = k_ref[sl, :].astype(jnp.float32)
        v = v_ref[sl, :].astype(jnp.float32)
        lw = lw_ref[sl, :].astype(jnp.float32)

        L = jnp.cumsum(lw, axis=0)                       # inclusive
        Lprev = L - lw
        # inter-chunk: y_inter = (r * exp(Lprev)) @ S
        q_dec = r * jnp.exp(Lprev)
        y_inter = jax.lax.dot_general(
            q_dec, S, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (c, N)
        # intra-chunk: a[t,s] = sum_n r_t k_s exp(Lprev_t - L_s), s < t
        diff = Lprev[:, None, :] - L[None, :, :]         # (c, c, N) <= 0
        a = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(diff),
                    axis=-1) * mask                      # (c, c)
        y_intra = jax.lax.dot_general(
            a, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # bonus: (r_t . (u * k_t)) v_t
        bonus = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)
        y = y_inter + y_intra + bonus * v
        y_ref[sl, :] = y.astype(y_ref.dtype)

        # state: S_new = exp(L_last) * S + sum_s exp(L_last - L_s) k_s v_s^T
        L_last = L[-1]
        k_dec = k * jnp.exp(L_last[None, :] - L)
        S_new = jnp.exp(L_last)[:, None] * S + jax.lax.dot_general(
            k_dec, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (N, N)
        return S_new

    S0 = jnp.zeros((N, N), jnp.float32)
    jax.lax.fori_loop(0, n_chunks, chunk_body, S0)


def wkv6_chunked(r, k, v, logw, u, *, chunk=64, interpret=None):
    """r,k,v,logw: (B, T, H, N) with T % chunk == 0; u: (H, N).
    ``interpret=None`` auto-detects the backend (Mosaic on TPU, the
    interpreter elsewhere) via ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    B, T, H, N = r.shape
    assert T % chunk == 0, (T, chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk, seq_len=T)
    spec = pl.BlockSpec((None, T, None, N), lambda b, h: (b, 0, h, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((None, N), lambda b, h: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, N), r.dtype),
        interpret=interpret,
        name="wkv6",
    )(r, k, v, logw, u)
