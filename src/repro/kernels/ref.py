"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def swa_attention(q, k, v, *, window=None, causal=True):
    """Naive O(S^2) masked softmax attention. Shapes as the kernel."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, kf) / (hd ** 0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, vf)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def flash_attention(q, k, v, *, window=None):
    """Causal (optionally windowed) attention over the whole sequence:
    the oracle of ``ops.flash_attention``, float32 throughout."""
    f32 = lambda x: x.astype(jnp.float32)
    return swa_attention(f32(q), f32(k), f32(v), window=window, causal=True)


def block_norms(blocks):
    return jnp.sum(blocks.astype(jnp.float32) ** 2, axis=1)


def masked_filter(blocks, mask):
    # filter in fp32, emit in the input dtype: a bf16 gradient must
    # come back as bf16 (its wire-byte accounting depends on it)
    bf = blocks.astype(jnp.float32)
    kept = bf * mask[:, None].astype(jnp.float32)
    return kept.astype(blocks.dtype), (bf - kept).astype(blocks.dtype)


def block_significance(blocks, threshold):
    """MLLess significance mask: blocks whose RMS exceeds ``threshold``
    times the fleet-wide RMS (oracle for ``ops.block_significance``)."""
    sq = block_norms(blocks)
    rms = jnp.sqrt(jnp.mean(sq) + 1e-20)
    return jnp.sqrt(sq) > threshold * rms


def significance_filter(blocks, threshold):
    """(kept, residual, mask) in one pass (oracle for
    ``ops.significance_filter``)."""
    mask = block_significance(blocks, threshold)
    kept, resid = masked_filter(blocks, mask)
    return kept, resid, mask


def wkv6(r, k, v, logw, u):
    """Exact step-by-step RWKV6 recurrence (the kernel oracle).

    r,k,v,logw: (B, T, H, N); u: (H, N).  S_t = diag(w_t) S_{t-1} +
    k_t v_t^T;  y_t = r_t (S_{t-1} + diag(u) k_t v_t^T).
    """
    B, T, H, N = r.shape
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    w = jnp.exp(logw.astype(jnp.float32))
    uf = u.astype(jnp.float32)

    def step(S, t):
        rt, kt, vt, wt = rf[:, t], kf[:, t], vf[:, t], w[:, t]
        kv = jnp.einsum("bhn,bhm->bhnm", kt, vt)
        y = jnp.einsum("bhn,bhnm->bhm", rt,
                       S + uf[None, :, :, None] * kv)
        return wt[..., None] * S + kv, y

    S0 = jnp.zeros((B, H, N, N), jnp.float32)
    _, ys = jax.lax.scan(step, S0, jnp.arange(T))
    return ys.transpose(1, 0, 2, 3).astype(r.dtype)


# ---------------------------------------------------------------------------
# robust-aggregation reductions (oracles for kernels/robust_agg.py)
# ---------------------------------------------------------------------------
def trimmed_mean(stacked, trim=1):
    """Full-sort interior mean over axis 0 of a [W, ...] stack (the
    O(W log W)-per-coordinate reference the kernel's masked one-pass /
    sorting-network forms must match)."""
    W = stacked.shape[0]
    s = jnp.sort(stacked.astype(jnp.float32), axis=0)
    return jnp.mean(jax.lax.slice_in_dim(s, trim, W - trim, axis=0),
                    axis=0)


def coordinate_median(stacked):
    """Per-coordinate median over axis 0 (jnp.median semantics: even W
    averages the two middle order statistics)."""
    return jnp.median(stacked.astype(jnp.float32), axis=0)


def krum_pairwise(stacked):
    """W x W squared Euclidean distances via the explicit [W, W, D]
    broadcast (exactly what recovery.krum materializes today — the HBM
    blowup the kernel's Gram-accumulation form exists to avoid)."""
    W = stacked.shape[0]
    flat = stacked.reshape(W, -1).astype(jnp.float32)
    return jnp.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)


def weiszfeld_step(stacked, z, floor):
    """One naive Weiszfeld iteration: materialize the [W, D] residual,
    take row norms, re-weight (oracle for the fused kernel step)."""
    W = stacked.shape[0]
    flat = stacked.reshape(W, -1).astype(jnp.float32)
    z = z.reshape(-1).astype(jnp.float32)
    dist = jnp.linalg.norm(flat - z[None, :], axis=-1)
    w = 1.0 / jnp.maximum(dist, floor)
    return jnp.sum(w[:, None] * flat, axis=0) / jnp.sum(w)


def fused_adamw_flat(g, m, v, p, c1, c2, *, lr, b1, b2, eps, wd):
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m_new = b1 * m + (1 - b1) * gf
    v_new = b2 * v + (1 - b2) * gf * gf
    u = -lr * ((m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + wd * pf)
    return u, m_new, v_new
