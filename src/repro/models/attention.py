"""Attention: GQA projections, chunked (flash-style) softmax attention with
causal / sliding-window masking, and single-token KV-cache decode.

The chunked implementation is the default lowering path (pure ``jnp`` +
``lax.scan`` with online softmax => O(seq) live memory).  Out-of-window /
fully-masked KV chunks are skipped with ``lax.cond`` so sliding-window
attention does O(S*W) work, not O(S^2).  The Pallas kernel in
``repro.kernels.swa_attention`` is the drop-in optimized path
(``use_pallas=True`` in :func:`repro.models.transformer.build_model`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def attention_init(key, cfg, dtype, cross=False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, H * hd), dtype),
        "wk": layers.dense_init(ks[1], (d, KV * hd), dtype),
        "wv": layers.dense_init(ks[2], (d, KV * hd), dtype),
        "wo": layers.dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def project_qkv(p, x, cfg):
    """x: (B, S, d) -> q (B,S,H,hd), k/v (B,S,KV,hd)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return (q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd),
            v.reshape(B, S, KV, hd))


# ---------------------------------------------------------------------------
# flash attention (train / prefill): chunked fwd + chunked two-pass bwd
# wrapped in a custom VJP so the backward never materializes O(S^2)
# residuals (the fix that makes 4k-train / 32k-prefill fit in HBM).
# ---------------------------------------------------------------------------
def _block_mask(q_pos, kv_pos, Sq, Skv, causal, window):
    mask = (kv_pos[None, :] <= Skv - 1) & (q_pos[:, None] <= Sq - 1)
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
    return mask


def _relevant(q_lo, q_hi, k_lo, k_hi, causal, window):
    """Static/traced predicate: does kv block [k_lo,k_hi) intersect the
    attention span of q block [q_lo,q_hi)?"""
    rel = jnp.asarray(True)
    if causal:
        rel = rel & (k_lo <= q_hi - 1)
    if window is not None:
        rel = rel & (k_hi > q_lo - window + 1)
    return rel


def _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk):
    """Returns (out (B,Sq,H,hd), lse (B,Sq,G,KV))."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - Skv), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, q_chunk, KV, G, hd)
    kp = kp.reshape(B, nk, kv_chunk, KV, hd)
    vp = vp.reshape(B, nk, kv_chunk, KV, hd)
    scale = 1.0 / (hd ** 0.5)

    def q_block(args):
        qi, qblk = args
        q_lo = qi * q_chunk
        q_pos = q_lo + jnp.arange(q_chunk)

        def kv_step(carry, kin):
            m, l, acc = carry
            ki, kblk, vblk = kin
            k_lo = ki * kv_chunk
            kv_pos = k_lo + jnp.arange(kv_chunk)

            def attend(_):
                s = jnp.einsum("bqkgh,bskh->bqgks", qblk, kblk,
                               preferred_element_type=jnp.float32) * scale
                mask = _block_mask(q_pos, kv_pos, Sq, Skv, causal, window)
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bqgks,bskh->bqgkh", p.astype(vblk.dtype), vblk,
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            rel = _relevant(q_lo, q_lo + q_chunk, k_lo, k_lo + kv_chunk,
                            causal, window)
            new = jax.lax.cond(rel, attend, lambda _: (m, l, acc),
                               operand=None)
            return new, None

        m0 = jnp.full((B, q_chunk, G, KV), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, G, KV), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, G, KV, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kp.swapaxes(0, 1),
                                    vp.swapaxes(0, 1)))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,qc,G,KV)
        return out, lse

    outs, lses = jax.lax.map(q_block, (jnp.arange(nq), qp.swapaxes(0, 1)))
    outs = outs.transpose(1, 0, 2, 4, 3, 5).reshape(B, nq * q_chunk, H, hd)
    lses = lses.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, G, KV)
    return outs[:, :Sq], lses[:, :Sq]


def _flash_bwd_impl(q, k, v, out, lse, do, causal, window, q_chunk,
                    kv_chunk):
    """Two-pass chunked backward (dq pass; dk/dv pass)."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    scale = 1.0 / (hd ** 0.5)

    pad4 = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    qp = pad4(q, nq * q_chunk - Sq).reshape(B, nq, q_chunk, KV, G, hd)
    dop = pad4(do, nq * q_chunk - Sq).reshape(B, nq, q_chunk, KV, G, hd)
    op = pad4(out, nq * q_chunk - Sq).reshape(B, nq, q_chunk, KV, G, hd)
    lsep = jnp.pad(lse, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)),
                   constant_values=0.0) \
        .reshape(B, nq, q_chunk, G, KV)
    kp = pad4(k, nk * kv_chunk - Skv).reshape(B, nk, kv_chunk, KV, hd)
    vp = pad4(v, nk * kv_chunk - Skv).reshape(B, nk, kv_chunk, KV, hd)

    # D = rowsum(do * out)  per (b, q, g, kv)
    Dp = jnp.einsum("bnqkgh,bnqkgh->bnqgk", dop.astype(jnp.float32),
                    op.astype(jnp.float32))

    def p_block(qblk, lseblk, kblk, vblk, q_lo, k_lo):
        q_pos = q_lo + jnp.arange(q_chunk)
        kv_pos = k_lo + jnp.arange(kv_chunk)
        s = jnp.einsum("bqkgh,bskh->bqgks", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        mask = _block_mask(q_pos, kv_pos, Sq, Skv, causal, window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        return jnp.exp(s - lseblk.transpose(0, 1, 2, 3)[..., None])

    # ---- pass 1: dq per q block ----
    def dq_block(args):
        qi, qblk, doblk, lseblk, Dblk = args
        q_lo = qi * q_chunk

        def kv_step(dq, kin):
            ki, kblk, vblk = kin
            k_lo = ki * kv_chunk

            def go(dq):
                p = p_block(qblk, lseblk, kblk, vblk, q_lo, k_lo)
                dp = jnp.einsum("bqkgh,bskh->bqgks",
                                doblk.astype(jnp.float32),
                                vblk.astype(jnp.float32))
                ds = p * (dp - Dblk[..., None])
                return dq + jnp.einsum("bqgks,bskh->bqkgh", ds,
                                       kblk.astype(jnp.float32)) * scale
            rel = _relevant(q_lo, q_lo + q_chunk, k_lo, k_lo + kv_chunk,
                            causal, window)
            return jax.lax.cond(rel, go, lambda d: d, dq), None

        dq0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        dq, _ = jax.lax.scan(kv_step, dq0,
                             (jnp.arange(nk), kp.swapaxes(0, 1),
                              vp.swapaxes(0, 1)))
        return dq

    dqs = jax.lax.map(dq_block, (jnp.arange(nq), qp.swapaxes(0, 1),
                                 dop.swapaxes(0, 1), lsep.swapaxes(0, 1),
                                 Dp.swapaxes(0, 1)))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, H, hd)

    # ---- pass 2: dk/dv per kv block ----
    def dkv_block(args):
        ki, kblk, vblk = args
        k_lo = ki * kv_chunk

        def q_step(carry, qin):
            dk, dv = carry
            qi, qblk, doblk, lseblk, Dblk = qin
            q_lo = qi * q_chunk

            def go(carry):
                dk, dv = carry
                p = p_block(qblk, lseblk, kblk, vblk, q_lo, k_lo)
                dv = dv + jnp.einsum("bqgks,bqkgh->bskh", p,
                                     doblk.astype(jnp.float32))
                dp = jnp.einsum("bqkgh,bskh->bqgks",
                                doblk.astype(jnp.float32),
                                vblk.astype(jnp.float32))
                ds = p * (dp - Dblk[..., None])
                dk = dk + jnp.einsum("bqgks,bqkgh->bskh", ds,
                                     qblk.astype(jnp.float32)) * scale
                return dk, dv
            rel = _relevant(q_lo, q_lo + q_chunk, k_lo, k_lo + kv_chunk,
                            causal, window)
            return jax.lax.cond(rel, go, lambda c: c, (dk, dv)), None

        z = jnp.zeros((B, kv_chunk, KV, hd), jnp.float32)
        (dk, dv), _ = jax.lax.scan(
            q_step, (z, z),
            (jnp.arange(nq), qp.swapaxes(0, 1), dop.swapaxes(0, 1),
             lsep.swapaxes(0, 1), Dp.swapaxes(0, 1)))
        return dk, dv

    dks, dvs = jax.lax.map(dkv_block, (jnp.arange(nk), kp.swapaxes(0, 1),
                                       vp.swapaxes(0, 1)))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_chunk, KV, hd)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_chunk, KV, hd)
    return (dq[:, :Sq].astype(q.dtype), dk[:, :Skv].astype(k.dtype),
            dv[:, :Skv].astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, q_chunk, kv_chunk):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk)
    return out


def _flash_fwd_rule(q, k, v, causal, window, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, q_chunk, kv_chunk, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, do, causal, window, q_chunk,
                           kv_chunk)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def chunked_attention(q, k, v, *, causal=True, window=None,
                      q_chunk=512, kv_chunk=512, pallas_fn=None):
    """Flash attention (see module docstring).

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H % KV == 0.
    ``window``: query at position i attends to [i-window+1, i].

    Runs under the name scope ``attention``, which a profile reads to
    attribute device time to the attention core (scores, online softmax,
    values) in the forward, the recompute and the backward.
    """
    with jax.named_scope("attention"):
        if pallas_fn is not None and causal and q.shape[1] == k.shape[1]:
            return pallas_fn(q, k, v, window=window)
        return _flash(q, k, v, causal, window, q_chunk, kv_chunk)


# ---------------------------------------------------------------------------
# decode attention (single new token vs KV cache)
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, pos, *, window=None):
    """q: (B, 1, H, hd); caches: (B, L, KV, hd) ring buffers.

    ``pos`` is the position (int32 scalar or (B,)) of the new token.  Slot
    ``s`` of a ring buffer of length L holds sequence position
    ``pos - ((pos - s) mod L)``; slots with negative positions are invalid.
    """
    B, L, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    pos = jnp.asarray(pos)
    pos_b = jnp.broadcast_to(pos, (B,))

    slots = jnp.arange(L)
    slot_pos = pos_b[:, None] - jnp.mod(pos_b[:, None] - slots[None, :], L)
    valid = slot_pos >= 0
    if window is not None:
        valid = valid & (slot_pos > pos_b[:, None] - window)

    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,blkh->bgkl", qg, k_cache,
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgkl,blkh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def decode_attention_quant(q, k_cache, v_cache, pos, *, window=None):
    """decode_attention against int8-quantized caches
    ({"q": int8, "scale": fp16} per k/v — repro.models.kvquant).
    Dequantization folds into the fp32 score/value einsums (scales are
    rank-1 per cache entry), so no full-precision cache materializes.
    """
    kq, ks = k_cache["q"], k_cache["scale"]
    vq, vs = v_cache["q"], v_cache["scale"]
    B, L, KV, hd = kq.shape
    H = q.shape[2]
    G = H // KV
    pos = jnp.asarray(pos)
    pos_b = jnp.broadcast_to(pos, (B,))
    slots = jnp.arange(L)
    slot_pos = pos_b[:, None] - jnp.mod(pos_b[:, None] - slots[None, :], L)
    valid = slot_pos >= 0
    if window is not None:
        valid = valid & (slot_pos > pos_b[:, None] - window)

    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,blkh->bgkl", qg, kq.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    s = s * ks[..., 0].transpose(0, 2, 1)[:, None]       # (B,1,KV,L)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    pv = p * vs[..., 0].transpose(0, 2, 1)[:, None]      # fold v scales
    out = jnp.einsum("bgkl,blkh->bkgh", pv, vq.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Write (B,1,KV,hd) new entries at ring slot pos % L.

    ``pos`` may be a scalar (all requests aligned) or (B,) per-slot
    positions (continuous batching — repro.serving.engine)."""
    L = k_cache.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        slot = jnp.mod(pos, L)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot,
                                                      axis=1)
        return k_cache, v_cache
    B = k_cache.shape[0]
    rows = jnp.arange(B)
    slots = jnp.mod(pos, L)
    return (k_cache.at[rows, slots].set(k_new[:, 0]),
            v_cache.at[rows, slots].set(v_new[:, 0]))
