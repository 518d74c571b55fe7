"""Mixture-of-Experts layer: top-k routing with capacity-based dispatch.

Scalable dispatch (no (T, E, C) one-hot tensors): tokens are scattered
into per-expert capacity buffers via cumulative-sum position assignment,
expert FFNs run as a single batched einsum over (E, C, d), and results
are gathered back with router-probability weighting.  Expert weights are
tensor-parallel over the 'model' mesh axis (d_ff dim); token buffers stay
on the data shards, so no all_to_all is needed in the baseline schedule
(see DESIGN.md §5 — the all_to_all expert-parallel layout is the
hillclimb alternative).

Router load-balance auxiliary loss per Shazeer et al. / Mixtral.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def moe_init(key, cfg, dtype):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": layers.dense_init(ks[0], (d, E), jnp.float32, scale=0.02),
        "w_gate": layers.dense_init(ks[1], (E, d, f), dtype),
        "w_up": layers.dense_init(ks[2], (E, d, f), dtype),
        "w_down": layers.dense_init(ks[3], (E, f, d), dtype),
    }


def _expert_ffn_chunked(p, buf, chunk=2048):
    """buf: (E, C, d) -> (E, C, d); capacity-chunked SwiGLU experts."""
    E, C, d = buf.shape
    c = min(chunk, C)
    if C % c:
        c = C                           # small/odd capacities: one shot

    def ffn(b):
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", b, p["w_gate"]))
        up = jnp.einsum("ecd,edf->ecf", b, p["w_up"])
        return jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"])

    if c == C:
        return ffn(buf)
    chunks = buf.reshape(E, C // c, c, d).swapaxes(0, 1)   # (n, E, c, d)
    outs = jax.lax.map(ffn, chunks)
    return outs.swapaxes(0, 1).reshape(E, C, d)


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ p["router"]            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- load-balance aux loss (fraction-of-tokens * mean-prob per expert)
    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, k, E)
    ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # --- capacity-based dispatch
    capacity = int(cfg.capacity_factor * k * T / E)
    capacity = max(8, -(-capacity // 8) * 8)
    flat_idx = expert_idx.reshape(T * k)                     # slot-major? token-major
    flat_gate = gate_vals.reshape(T * k)
    # position of each (token, slot) within its expert's buffer
    eh = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)        # (T*k, E)
    pos_in_expert = (jnp.cumsum(eh, axis=0) - eh)            # (T*k, E)
    pos = jnp.sum(pos_in_expert * eh, axis=-1)               # (T*k,)
    keep = pos < capacity
    dest = flat_idx * capacity + jnp.where(keep, pos, capacity)  # overflow slot

    buf = jnp.zeros((E * capacity + 1, d), x.dtype)
    token_ids = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[jnp.where(keep, dest, E * capacity)].set(
        xf[token_ids], mode="drop")
    buf = buf[:E * capacity].reshape(E, capacity, d)

    # --- expert FFNs (batched over experts; d_ff sharded over 'model');
    # chunk the capacity dim so the (E, C, d_ff) intermediates never
    # materialize whole (C can reach ~20k at prefill_32k)
    out = _expert_ffn_chunked(p, buf)

    # --- combine
    out_flat = out.reshape(E * capacity, d)
    gathered = out_flat[jnp.minimum(dest, E * capacity - 1)]  # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered * flat_gate[:, None].astype(gathered.dtype)
    y = jnp.sum(weighted.reshape(T, k, d), axis=1)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# expert-parallel variant: move tokens, not expert weights
# ---------------------------------------------------------------------------
def moe_apply_ep(p, x, cfg, *, axis_name, ep_degree=None):
    """Expert-parallel MoE for use inside a ``jax.shard_map`` manual
    region over ``axis_name`` (the hillclimb alternative to the TP/FSDP
    layouts — expert weights stay resident on their shard group and the
    capacity buffers travel through one all_to_all each way).

    Preconditions: every shard holds the full (E, d, f) expert weights
    sliced so that shard ``i`` *uses* experts
    ``[i*E/W .. (i+1)*E/W)`` (W = ep_degree = axis size; E % W == 0).
    Tokens are locally routed, packed into per-expert capacity buffers,
    exchanged with all_to_all so each shard computes only its experts,
    and returned.  Numerics match :func:`moe_apply` up to capacity-drop
    ordering (validated in tests/test_moe_ep.py).
    """
    W = jax.lax.axis_size(axis_name)
    E, k = cfg.n_experts, cfg.experts_per_token
    assert E % W == 0, (E, W)
    E_loc = E // W
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    capacity = int(cfg.capacity_factor * k * T / E)
    capacity = max(8, -(-capacity // 8) * 8)
    flat_idx = expert_idx.reshape(T * k)
    flat_gate = gate_vals.reshape(T * k)
    eh = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(eh, axis=0) - eh) * eh, axis=-1)
    keep = pos < capacity
    dest = flat_idx * capacity + jnp.where(keep, pos, capacity)

    buf = jnp.zeros((E * capacity + 1, d), x.dtype)
    token_ids = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[jnp.where(keep, dest, E * capacity)].set(
        xf[token_ids], mode="drop")
    buf = buf[:E * capacity].reshape(E, capacity, d)

    # ship each expert's buffer to the shard that owns it; receive the
    # buffers of OUR experts from every peer: (E, C, d) -> (W*E_loc, C, d)
    shipped = jax.lax.all_to_all(
        buf.reshape(W, E_loc, capacity, d), axis_name,
        split_axis=0, concat_axis=0, tiled=True)      # (W, E_loc, C, d)

    # compute only the local experts (weights sliced to our group)
    shard = jax.lax.axis_index(axis_name)
    wg = jax.lax.dynamic_slice_in_dim(p["w_gate"], shard * E_loc, E_loc, 0)
    wu = jax.lax.dynamic_slice_in_dim(p["w_up"], shard * E_loc, E_loc, 0)
    wd = jax.lax.dynamic_slice_in_dim(p["w_down"], shard * E_loc, E_loc, 0)
    flat_in = shipped.transpose(1, 0, 2, 3).reshape(E_loc, W * capacity, d)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", flat_in, wg))
    up = jnp.einsum("ecd,edf->ecf", flat_in, wu)
    res = jnp.einsum("ecf,efd->ecd", gate * up, wd)

    # return results to the owners of the tokens
    back = res.reshape(E_loc, W, capacity, d).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(back, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)  # (W, E_loc, C, d)
    out_flat = out.reshape(E * capacity, d)

    gathered = out_flat[jnp.minimum(dest, E * capacity - 1)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered * flat_gate[:, None].astype(gathered.dtype)
    y = jnp.sum(weighted.reshape(T, k, d), axis=1)
    return y.reshape(B, S, d).astype(x.dtype), aux
