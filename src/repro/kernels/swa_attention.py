"""Sliding-window flash-attention forward — Pallas TPU kernel.

Tiling: grid (batch, kv_head, q_blocks).  Operands are laid out
head-major — q as (B, KV, G, S, hd), k/v as (B, KV, S, hd) — so every
block's last two dims are (rows, hd), as Mosaic requires.  Each program
holds the G query heads of one KV head for one (Bq, hd) query tile in
VMEM plus the full per-(b, kv-head) K/V strips (the window bounds how
much is ever *read*: the kv loop runs only over blocks intersecting
[q_start - window + 1, q_end], with a traced-bound ``fori_loop`` so
out-of-window blocks cost nothing).  Online softmax in fp32
accumulators over the (G * Bq) rows of the GQA group.

MXU alignment: Bq and Ck are multiples of 128 where shapes allow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, window, causal, q_block,
                 kv_block, seq_len):
    # q_ref/o_ref: (G, q_block, hd); k_ref/v_ref: (seq, hd)
    qi = pl.program_id(2)
    q_start = qi * q_block
    G, _, hd = q_ref.shape
    rows = G * q_block
    q = q_ref[...].astype(jnp.float32).reshape(rows, hd)
    scale = 1.0 / (hd ** 0.5)

    n_kv = seq_len // kv_block
    # kv block range intersecting the union of windows of this q tile
    if window is None:
        lo = 0
    else:
        lo = jnp.maximum((q_start - window + 1) // kv_block, 0)
    hi = jnp.minimum((q_start + q_block - 1) // kv_block + 1, n_kv) \
        if causal else n_kv

    # row r of the (G * Bq) group holds query position q_start + r % Bq
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, kv_block), 0) % q_block
    kv_off = jax.lax.broadcasted_iota(jnp.int32, (rows, kv_block), 1)

    def body(ki, carry):
        m, l, acc = carry
        k_start = pl.multiple_of(ki * kv_block, kv_block)
        k = k_ref[pl.ds(k_start, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(k_start, kv_block), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rows, Ck)
        kv_pos = k_start + kv_off
        mask = jnp.ones((rows, kv_block), jnp.bool_)
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (rows, hd)
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    a0 = jnp.zeros((rows, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).reshape(
        G, q_block, hd).astype(o_ref.dtype)


def swa_attention_fwd(q, k, v, *, window=None, causal=True,
                      q_block=256, kv_block=256, interpret=None):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd).  Returns (B, S, H, hd).
    ``interpret=None`` auto-detects the backend (Mosaic on TPU, the
    interpreter elsewhere) via ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    assert S % q_block == 0 and S % kv_block == 0, (S, q_block, kv_block)

    # head-major: query head kv * G + g -> (B, KV, G, S, hd)
    qh = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel, window=window, causal=causal, q_block=q_block,
        kv_block=kv_block, seq_len=S)

    q_spec = pl.BlockSpec((None, None, G, q_block, hd),
                          lambda b, h, qi: (b, h, 0, qi, 0))
    kv_spec = pl.BlockSpec((None, None, S, hd),
                           lambda b, h, qi: (b, h, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, S // q_block),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
