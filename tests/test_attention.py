"""Chunked flash attention (``models/attention.py``): its masking against a
dense float32 reference, and a lowering check that the differentiated
model never stacks mask or fill arrays of the full score-block shape."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import losses
from repro.models import build_model
from repro.models.attention import chunked_attention


def dense_attention(q, k, v, *, causal, window):
    """Plain float32 masked softmax attention over the whole sequence."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, k,
                   precision="highest") / (hd ** 0.5)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision="highest")
    return o.reshape(B, Sq, H, hd)


@pytest.mark.parametrize("Sq,Skv,causal,window", [
    (256, 256, True, None),      # causal, sequence a multiple of the chunk
    (256, 256, True, 48),        # causal with a sliding window
    (256, 192, False, None),     # bidirectional / cross attention
    (200, 200, True, None),      # padding: 200 is no multiple of 64
    (200, 136, False, None),     # padding on both sides, no causal mask
])
def test_chunked_attention_matches_dense(Sq, Skv, causal, window):
    rs = np.random.RandomState(Sq + Skv)
    B, H, KV, hd = 2, 6, 2, 32
    q = jnp.asarray(rs.randn(B, Sq, H, hd), jnp.float32)
    k = jnp.asarray(rs.randn(B, Skv, KV, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, Skv, KV, hd), jnp.float32)
    w = jnp.asarray(rs.randn(B, Sq, H, hd), jnp.float32)

    def chunked(q, k, v):
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=64, kv_chunk=64)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=causal, window=window)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * w),
                        argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(np.asarray(chunked(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    for name, got, want in zip("qkv", grads(chunked), grads(dense)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("remat", [True, False])
def test_grad_stacks_no_full_mask_blocks(remat):
    """Under ``jax.grad``, a mask or fill broadcast to the score block's
    shape depends on no parameter, so JAX hoists it out of the layer scan
    as a stack of blocks: (nq, nk, B, q_chunk, G, KV, kv_chunk) at the
    scan, (nq, B, ...) at the map over q blocks.  None may be lowered."""
    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=2,
                              d_model=384, n_heads=6, n_kv_heads=2,
                              head_dim=64, d_ff=768, vocab_size=512)
    model = build_model(cfg, remat=remat)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S = 2, 2048
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def loss(p, b):
        logits, aux = model.apply(p, b)
        return losses.softmax_cross_entropy(logits, b["labels"]) + aux

    text = jax.jit(jax.grad(loss)).lower(params, batch).as_text()
    # the score block itself, (B, q_chunk, G, KV, kv_chunk), must be there
    assert "tensor<2x512x3x2x512xf32>" in text
    stacked = re.findall(
        r"tensor<(?:4x4|4)x2x512x3x2x512x(?:f32|i1)>", text)
    assert not stacked, sorted(set(stacked))
