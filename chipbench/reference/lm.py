"""Plain decoder-only LM (llama family, as SmolLM-135M describes it).

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary
positions (rotate-half convention) and a causal mask -> residual ->
RMSNorm -> SwiGLU MLP -> residual; final RMSNorm; linear head; mean
next-token cross-entropy.  Full softmax attention over the whole
sequence, no chunking, no cache, one sequence at a time, each layer
recomputed in the backward pass (``jax.checkpoint``) so that it fits.

Departures from the published model, as the configuration runs it: the
RMSNorm gain is ``1 + w`` (the published ``w`` starts at 1, here at 0),
and the head is its own matrix (``tie_word_embeddings`` false).

The parameter tree is laid out as the weights are made: ``embed/table``
(V, d), ``unembed/table`` (d, V), ``final_norm`` (d,), and one stacked
block whose leaves carry the layer index first.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import F32, Numerics, ein


def rmsnorm(x, w, eps, num: Numerics):
    xf = x.astype(F32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (out * (1.0 + w.astype(F32))).astype(num.act)


def rope(x, theta):
    """x: (S, heads, hd); rotate-half over the two halves of hd."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def layer(x, p, c: Dict[str, Any], num: Numerics):
    S = x.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    G = H // KV
    eps = c["rms_norm_eps"]
    a = p["attn"]
    h = rmsnorm(x, p["norm1"], eps, num)
    q = rope(ein("sd,de->se", h, a["wq"], num).reshape(S, H, hd),
             c["rope_theta"])
    k = rope(ein("sd,de->se", h, a["wk"], num).reshape(S, KV, hd),
             c["rope_theta"])
    v = ein("sd,de->se", h, a["wv"], num).reshape(S, KV, hd)
    q = q.reshape(S, KV, G, hd)
    s = ein("qkgh,skh->kgqs", q, k, num).astype(F32) / hd ** 0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(num.act)
    o = ein("kgqs,skh->qkgh", prob, v, num).reshape(S, H * hd)
    x = x + ein("se,ed->sd", o, a["wo"], num)
    h = rmsnorm(x, p["norm2"], eps, num)
    m = p["mlp"]
    gate = jax.nn.silu(ein("sd,df->sf", h, m["w_gate"], num).astype(F32))
    up = ein("sd,df->sf", h, m["w_up"], num).astype(F32)
    return x + ein("sf,fd->sd", (gate * up).astype(num.act), m["w_down"],
                   num)


def sequence_loss_sum(params, tokens, labels, config: Dict[str, Any],
                      num: Numerics):
    """Summed next-token cross-entropy of one sequence."""
    c = config["config"]
    x = params["embed"]["table"][tokens].astype(num.act)

    def body(x, p):
        return layer(x, p, c, num), None
    (block,) = params["blocks"]
    x, _ = jax.lax.scan(jax.checkpoint(body), x, block)
    for p in params["tail"]:
        x = layer(x, p, c, num)
    x = rmsnorm(x, params["final_norm"], c["rms_norm_eps"], num)
    logits = ein("sd,dv->sv", x, params["unembed"]["table"], num).astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


def row_blocks(batch):
    """One sequence at a time."""
    return [{"tokens": batch["tokens"][i], "labels": batch["labels"][i]}
            for i in range(batch["tokens"].shape[0])]


def block_loss_sum(params, block, config, num):
    return sequence_loss_sum(params, block["tokens"], block["labels"],
                             config, num)


def units(batch) -> int:
    """Tokens the loss is averaged over."""
    return int(batch["labels"].size)
