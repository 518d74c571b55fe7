"""Core neural-net building blocks (functional, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def shard_hint(x, *axes):
    """with_sharding_constraint on auto mesh axes, if any are in scope.

    ``axes`` entries are mesh-axis names (or None) per tensor dim; axes
    not present in the current abstract mesh are dropped, so model code
    stays mesh-agnostic (no-op on CPU tests / 1x1 meshes)."""
    mesh = jax.sharding.get_abstract_mesh()
    # only Auto axes may appear in with_sharding_constraint specs
    names = tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                  if t == jax.sharding.AxisType.Auto)
    spec = tuple(a if (a in names) else None for a in axes)
    if not any(spec):
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w + b).astype(x.dtype)


def rmsnorm_init(d):
    return jnp.zeros((d,), jnp.float32)


def layernorm_init(d):
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim, theta):
    # head_dim may be odd-unfriendly; use the even prefix
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_frequencies(hd, theta)                      # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    rot1 = x1 * cos - x2 * sin
    rot2 = x2 * cos + x1 * sin
    out = jnp.concatenate([rot1, rot2, x[..., 2 * half:].astype(jnp.float32)],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len, d_model):
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    angle = pos / np.power(10_000.0, 2 * dim / d_model)
    out = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(out, jnp.float32)


def sinusoidal_position_at(pos, d_model):
    """Sinusoidal embedding for a traced position scalar or (B,) array."""
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)
    p = jnp.asarray(pos, jnp.float32)
    angle = p[..., None] / jnp.power(10_000.0, 2 * dim / d_model)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_init(key, d_model, d_ff, kind, dtype):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), dtype),
    }


def mlp_apply(p, x, kind):
    if kind == "swiglu":
        gate = jax.nn.silu(x @ p["w_gate"])
        return (gate * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def embedding_init(key, vocab, d_model, dtype):
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p, x):
    # separate unembedding head (vocab-parallel when sharded)
    return x @ p["table"]


def unembed_init(key, d_model, vocab, dtype):
    # std 0.02, the published llama-family initializer_range: initial
    # logits ~N(0, 0.02^2 * d_model) stay near uniform, so the step-0
    # loss sits near ln(vocab) rather than ln(vocab) + 1/2
    return {"table": dense_init(key, (d_model, vocab), dtype, scale=0.02)}
