"""Config-driven model assembly for all assigned architectures.

One ``Model`` object per config, exposing:

  init(key)                          -> params pytree
  apply(params, batch)               -> (logits, aux)        [train fwd]
  prefill(params, batch, cache_len)  -> (logits_last, cache)
  decode_step(params, token, cache, pos) -> (logits, cache)

Depth is organized as ``lax.scan`` over repeating layer-pattern blocks
(homogeneous stacks => small HLO, fast multi-arch dry-runs), with the
remainder layers unrolled ("tail").  Layer kinds: global attention,
local (sliding-window) attention, RG-LRU recurrence, RWKV6 time-mix.
MoE configs replace every MLP with the top-k expert layer.  Train and
prefill run one layer body, ``_layer``; decode has its own mixers (cache
update, single-token attention) and shares its FFN half, ``_ffn``.
Attention itself picks its lowering per platform
(:func:`repro.models.attention.chunked_attention`).

Modality frontends (audio conv codec, ViT patch encoder) are stubs per
the assignment: batches carry precomputed ``frames`` / ``patch_emb``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GLOBAL, LOCAL, RGLRU, RWKV, ModelConfig
from repro.models import attention, kvquant, layers, moe, rglru, rwkv6


# What remat keeps of a layer scan besides its carry: the output and
# log-sum-exp of the TPU flash-attention kernel, which its custom VJP
# names for its backward (``flash_attention._attend_fwd``).  Without them
# the backward would run the kernel's forward again.  A layer with no
# kernel attention (RG-LRU, RWKV, the XLA ``_flash`` path) names nothing
# and keeps only the carry.
_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "attn_lse")


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------
def _layer_init(key, kind: str, cfg: ModelConfig, dtype, cross=False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": layers.rmsnorm_init(cfg.d_model),
                         "norm2": layers.rmsnorm_init(cfg.d_model)}
    if kind in (GLOBAL, LOCAL):
        p["attn"] = attention.attention_init(k1, cfg, dtype)
    elif kind == RGLRU:
        p["rglru"] = rglru.rglru_init(k1, cfg, dtype)
    elif kind == RWKV:
        p["rwkv"] = rwkv6.rwkv_init(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["norm_x"] = layers.rmsnorm_init(cfg.d_model)
        p["xattn"] = attention.attention_init(k3, cfg, dtype)
    if cfg.is_moe and kind in (GLOBAL, LOCAL):
        p["moe"] = moe.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = layers.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def _layer(p, kind, x, positions, cfg, use_rope, enc_out=None, state=None):
    """One pre-norm layer over a full sequence (train and prefill):
    mixer -> [cross-attention] -> FFN.

    Returns ``(x, aux, state, cross_kv)``: the MoE aux loss, the mixer's
    state after ``x`` (``(k, v)`` for attention; for RG-LRU and RWKV the
    recurrence's state, carried on from ``state``) and the
    cross-attention's ``(k, v)`` over ``enc_out`` (None without it)."""
    h = layers.rmsnorm(x, p["norm1"])
    if kind in (GLOBAL, LOCAL):
        q, k, v = attention.project_qkv(p["attn"], h, cfg)
        if use_rope:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        window = cfg.window if kind == LOCAL else None
        o = attention.chunked_attention(q, k, v, causal=True, window=window)
        mix, state = o.reshape(*o.shape[:2], -1) @ p["attn"]["wo"], (k, v)
    elif kind == RGLRU:
        mix, state = rglru.rglru_apply(p["rglru"], h, cfg, state=state)
    elif kind == RWKV:
        mix, state = rwkv6.rwkv_apply(p["rwkv"], h, cfg, state=state)
    else:
        raise ValueError(kind)
    x = x + mix
    cross_kv = None
    if enc_out is not None:
        h = layers.rmsnorm(x, p["norm_x"])
        q, _, _ = attention.project_qkv(p["xattn"], h, cfg)
        _, k, v = attention.project_qkv(p["xattn"], enc_out, cfg)
        o = attention.chunked_attention(q, k, v, causal=False)
        x = x + o.reshape(*o.shape[:2], -1) @ p["xattn"]["wo"]
        cross_kv = (k, v)
    x, aux = _ffn(p, x, cfg)
    return x, aux, state, cross_kv


def _ffn(p, x, cfg):
    """A layer's second half: norm2, then MoE or MLP, then the residual.
    Returns ``(x, aux)``."""
    h = layers.rmsnorm(x, p["norm2"])
    if "moe" in p:
        y, aux = moe.moe_apply(p["moe"], h, cfg)
    else:
        y, aux = layers.mlp_apply(p["mlp"], h, cfg.mlp), 0.0
    return x + y, aux


def _fill_ring(cache_leaf, k, v, kv_quant):
    """An attention layer's ring cache holding the trailing positions of
    a prompt's ``(k, v)`` (int8 with scales under ``kv_quant``)."""
    L = jax.tree.leaves(cache_leaf["k"])[0].shape[1]
    S = k.shape[1]
    take = min(L, S)
    slots = jnp.mod(jnp.arange(S - take, S), L)

    def put(leaf, val):
        val = val[:, -take:]
        if kv_quant:
            val = dict(zip(("q", "scale"), kvquant.quantize_kv(val)))
        return jax.tree.map(lambda c, n: c.at[:, slots].set(n), leaf, val)
    return {"k": put(cache_leaf["k"], k), "v": put(cache_leaf["v"], v)}


# ---------------------------------------------------------------------------
# pattern-block organization
# ---------------------------------------------------------------------------
def _split_depth(cfg: ModelConfig):
    P = len(cfg.layer_pattern)
    n_blocks = cfg.n_layers // P
    n_tail = cfg.n_layers - n_blocks * P
    tail_kinds = cfg.layer_pattern[:n_tail]
    return n_blocks, tail_kinds


class Model:
    def __init__(self, cfg: ModelConfig, remat: bool = True,
                 kv_quant: bool = False):
        self.cfg = cfg
        self.remat = remat
        # int8 KV caches (repro.models.kvquant): about half the bytes of a
        # bf16 cache, for decode shapes where reading the cache dominates
        self.kv_quant = kv_quant
        self.use_rope = not cfg.is_encoder_decoder
        self.n_blocks, self.tail_kinds = _split_depth(cfg)
        # FSDP hook: fn(param_subtree, kind in {"block","tail"}, idx) ->
        # gathered subtree.  Set by the train-step builder (manual-mesh
        # regions only); identity when None.
        self.param_hook = None

    def _hook(self, tree, kind, idx):
        if self.param_hook is None:
            return tree
        return self.param_hook(tree, kind, idx)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a 128 multiple so the embedding/unembedding can
        always shard over the model axis (standard vocab padding; the
        extra logits correspond to never-labeled classes)."""
        return -(-self.cfg.vocab_size // 128) * 128

    # --------------------------- init ---------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": layers.embedding_init(keys[0], self.padded_vocab,
                                           cfg.d_model, dtype),
            "unembed": layers.unembed_init(keys[1], cfg.d_model,
                                           self.padded_vocab, dtype),
            "final_norm": layers.rmsnorm_init(cfg.d_model),
        }
        cross = cfg.is_encoder_decoder
        # stacked block params: one stacked tree per pattern position
        block_keys = jax.random.split(keys[2], max(self.n_blocks, 1))
        blocks = []
        for j, kind in enumerate(cfg.layer_pattern):
            def one(k):
                return _layer_init(jax.random.fold_in(k, j), kind, cfg,
                                   dtype, cross=cross)
            if self.n_blocks > 0:
                blocks.append(jax.vmap(one)(block_keys))
        params["blocks"] = blocks
        params["tail"] = [
            _layer_init(jax.random.fold_in(keys[3], i), kind, cfg, dtype,
                        cross=cross)
            for i, kind in enumerate(self.tail_kinds)]
        if cfg.is_encoder_decoder:
            enc_keys = jax.random.split(keys[4], cfg.n_encoder_layers)
            def enc_one(k):
                return _layer_init(k, GLOBAL, cfg, dtype, cross=False)
            params["encoder"] = jax.vmap(enc_one)(enc_keys)
            params["enc_norm"] = layers.rmsnorm_init(cfg.d_model)
        return params

    # --------------------------- embedding ----------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"])
        if cfg.family == "vlm" and "patch_emb" in batch:
            npatch = batch["patch_emb"].shape[1]
            x = jnp.concatenate(
                [batch["patch_emb"].astype(x.dtype), x[:, npatch:]], axis=1)
        if cfg.is_encoder_decoder:
            S = x.shape[1]
            x = x + layers.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
        return x

    def _encode(self, params, frames):
        """Whisper-style encoder over stub frame embeddings."""
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.dtype))
        x = x + layers.sinusoidal_positions(x.shape[1],
                                            cfg.d_model).astype(x.dtype)

        def body(x, p):
            h = layers.rmsnorm(x, p["norm1"])
            q, k, v = attention.project_qkv(p["attn"], h, cfg)
            o = attention.chunked_attention(q, k, v, causal=False)
            x = x + o.reshape(*o.shape[:2], -1) @ p["attn"]["wo"]
            x, _ = _ffn(p, x, cfg)
            return x, None

        fn = jax.checkpoint(body, policy=_REMAT_POLICY) if self.remat \
            else body
        x, _ = jax.lax.scan(lambda c, p: fn(c, p), x, params["encoder"])
        return layers.rmsnorm(x, params["enc_norm"])

    # --------------------------- train forward ------------------------
    def apply(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"])

        def layer(x, p, kind):
            x, aux, _, _ = _layer(p, kind, x, positions, cfg, self.use_rope,
                                  enc_out=enc_out)
            return x, aux

        def block_body(carry, block_params):
            x, aux = carry
            for j, kind in enumerate(cfg.layer_pattern):
                x, a = layer(x, self._hook(block_params[j], "block", j), kind)
                aux = aux + a
            return (x, aux), None

        fn = jax.checkpoint(block_body, policy=_REMAT_POLICY) \
            if self.remat else block_body
        carry = (x, jnp.zeros((), jnp.float32))
        if self.n_blocks > 0:
            carry, _ = jax.lax.scan(fn, carry, tuple(params["blocks"]))
        x, aux = carry
        for i, (p, kind) in enumerate(zip(params["tail"], self.tail_kinds)):
            x, a = layer(x, self._hook(p, "tail", i), kind)
            aux = aux + a
        x = layers.rmsnorm(x, params["final_norm"])
        logits = layers.unembed(params["unembed"], x)
        return logits, aux

    # --------------------------- cache --------------------------------
    def _cache_len(self, kind: str, seq_len: int) -> int:
        if kind == GLOBAL:
            return seq_len
        return min(self.cfg.window, seq_len)

    def init_cache(self, batch_size: int, seq_len: int,
                   swa_variant: bool = False) -> Dict[str, Any]:
        """Empty decode cache for a maximum context of ``seq_len``."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        pattern = self._pattern(swa_variant)

        def one(kind):
            if kind in (GLOBAL, LOCAL):
                L = self._cache_len(kind, seq_len)
                if self.kv_quant:
                    return {
                        "k": kvquant.init_quant_cache(
                            batch_size, L, cfg.n_kv_heads, cfg.head_dim),
                        "v": kvquant.init_quant_cache(
                            batch_size, L, cfg.n_kv_heads, cfg.head_dim)}
                shape = (batch_size, L, cfg.n_kv_heads, cfg.head_dim)
                return {"k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype)}
            if kind == RGLRU:
                return rglru.rglru_init_state(cfg, batch_size, dtype)
            if kind == RWKV:
                return rwkv6.rwkv_init_state(cfg, batch_size, dtype)
            raise ValueError(kind)

        def stack(kind):
            leaf = one(kind)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_blocks,) + a.shape),
                leaf)

        cache: Dict[str, Any] = {
            "blocks": [stack(kind) for kind in pattern] if self.n_blocks
            else [],
            "tail": [one(kind) for kind in self._tail(swa_variant)],
        }
        if cfg.is_encoder_decoder:
            shape = (batch_size, cfg.encoder_seq, cfg.n_kv_heads,
                     cfg.head_dim)
            cache["enc_kv"] = {
                "k": jnp.zeros((cfg.n_layers,) + shape, dtype),
                "v": jnp.zeros((cfg.n_layers,) + shape, dtype)}
        return cache

    def _pattern(self, swa_variant: bool):
        if swa_variant:
            return tuple(LOCAL if k == GLOBAL else k
                         for k in self.cfg.layer_pattern)
        return self.cfg.layer_pattern

    def _tail(self, swa_variant: bool):
        if swa_variant:
            return tuple(LOCAL if k == GLOBAL else k for k in self.tail_kinds)
        return self.tail_kinds

    # --------------------------- prefill ------------------------------
    def prefill(self, params, batch, cache_len: Optional[int] = None,
                swa_variant: bool = False):
        """Forward over a prompt, returning last-token logits + filled cache."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        cache_len = cache_len or S
        positions = jnp.arange(S)[None, :]
        pattern = self._pattern(swa_variant)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"])

        cache = self.init_cache(B, cache_len, swa_variant)

        def layer(x, p, kind, cache_leaf):
            x, _, state, cross_kv = _layer(p, kind, x, positions, cfg,
                                           self.use_rope, enc_out=enc_out,
                                           state=cache_leaf)
            if kind in (GLOBAL, LOCAL):
                state = _fill_ring(cache_leaf, *state, self.kv_quant)
            if cross_kv is not None:
                state = (state, {"k": cross_kv[0], "v": cross_kv[1]})
            return x, state

        def block_body(x, xs):
            block_params, block_cache = xs
            new_cache = []
            for j, kind in enumerate(pattern):
                x, nc = layer(x, block_params[j], kind, block_cache[j])
                new_cache.append(nc)
            return x, tuple(new_cache)

        fn = jax.checkpoint(block_body) if self.remat else block_body
        enc_caches = []
        if self.n_blocks > 0:
            x, new_blocks = jax.lax.scan(
                fn, x, (tuple(params["blocks"]), tuple(cache["blocks"])))
            if cfg.is_encoder_decoder:
                new_blocks, enc_b = _split_enc(new_blocks)
                enc_caches.append(enc_b)
            cache["blocks"] = list(new_blocks)
        for i, (p, kind) in enumerate(zip(params["tail"],
                                          self._tail(swa_variant))):
            x, nc = layer(x, p, kind, cache["tail"][i])
            if cfg.is_encoder_decoder:
                nc, enc_t = nc
                enc_caches.append(jax.tree.map(lambda a: a[None], enc_t))
            cache["tail"][i] = nc
        if cfg.is_encoder_decoder and enc_caches:
            cache["enc_kv"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *enc_caches) \
                if len(enc_caches) > 1 else enc_caches[0]
        x = layers.rmsnorm(x[:, -1:], params["final_norm"])
        logits = layers.unembed(params["unembed"], x)
        return logits, cache

    # --------------------------- decode -------------------------------
    def decode_step(self, params, token, cache, pos, swa_variant=False):
        """token: (B, 1) int32; pos: scalar int32 position of this token,
        or (B,) per-request positions (continuous batching)."""
        cfg = self.cfg
        x = layers.embed(params["embed"], token)
        B = x.shape[0]
        pos = jnp.asarray(pos)
        if cfg.is_encoder_decoder:
            pe = layers.sinusoidal_position_at(pos, cfg.d_model)
            pe = pe[:, None, :] if pos.ndim == 1 else pe
            x = x + pe.astype(x.dtype)
        positions = pos.reshape(B, 1) if pos.ndim == 1 \
            else jnp.full((B, 1), pos)
        pattern = self._pattern(swa_variant)

        def layer(x, p, kind, cache_leaf, enc_kv=None):
            h = layers.rmsnorm(x, p["norm1"])
            if kind in (GLOBAL, LOCAL):
                q, k, v = attention.project_qkv(p["attn"], h, cfg)
                if self.use_rope:
                    q = layers.apply_rope(q, positions, cfg.rope_theta)
                    k = layers.apply_rope(k, positions, cfg.rope_theta)
                window = cfg.window if kind == LOCAL else None
                if self.kv_quant:
                    kc = kvquant.quant_cache_update(cache_leaf["k"], k, pos)
                    vc = kvquant.quant_cache_update(cache_leaf["v"], v, pos)
                    o = attention.decode_attention_quant(q, kc, vc, pos,
                                                         window=window)
                else:
                    kc, vc = attention.cache_update(
                        cache_leaf["k"], cache_leaf["v"], k, v, pos)
                    o = attention.decode_attention(q, kc, vc, pos,
                                                   window=window)
                x = x + o.reshape(B, 1, -1) @ p["attn"]["wo"]
                new_cache = {"k": kc, "v": vc}
            elif kind == RGLRU:
                y, new_cache = rglru.rglru_decode_step(p["rglru"], h, cfg,
                                                       cache_leaf)
                x = x + y
            elif kind == RWKV:
                y, new_cache = rwkv6.rwkv_decode_step(p["rwkv"], h, cfg,
                                                      cache_leaf)
                x = x + y
            if enc_kv is not None:
                h = layers.rmsnorm(x, p["norm_x"])
                q, _, _ = attention.project_qkv(p["xattn"], h, cfg)
                o = attention.decode_attention(q, enc_kv["k"], enc_kv["v"],
                                               enc_kv["k"].shape[1] - 1)
                x = x + o.reshape(B, 1, -1) @ p["xattn"]["wo"]
            x, _ = _ffn(p, x, cfg)
            return x, new_cache

        P = len(pattern)
        enc_kv = cache.get("enc_kv")

        def block_body(carry, xs):
            x, li = carry
            if enc_kv is None:
                block_params, block_cache = xs
                enc_slices = [None] * P
            else:
                block_params, block_cache, enc_slices = xs
            new_cache = []
            for j, kind in enumerate(pattern):
                es = enc_slices[j] if enc_kv is not None else None
                x, nc = layer(x, block_params[j], kind, block_cache[j], es)
                new_cache.append(nc)
            return (x, li + P), tuple(new_cache)

        if self.n_blocks > 0:
            xs = (tuple(params["blocks"]), tuple(cache["blocks"]))
            if enc_kv is not None:
                # reshape (n_layers, ...) -> per-pattern-position slices
                nb = self.n_blocks
                sliced = jax.tree.map(
                    lambda a: a[:nb * P].reshape(nb, P, *a.shape[1:]),
                    enc_kv)
                xs = xs + ([jax.tree.map(lambda a: a[:, j], sliced)
                            for j in range(P)],)
            (x, _), new_blocks = jax.lax.scan(block_body, (x, 0), xs)
            cache["blocks"] = list(new_blocks)
        for i, (p, kind) in enumerate(zip(params["tail"],
                                          self._tail(swa_variant))):
            es = None
            if enc_kv is not None:
                es = jax.tree.map(lambda a: a[self.n_blocks * P + i], enc_kv)
            x, nc = layer(x, p, kind, cache["tail"][i], es)
            cache["tail"][i] = nc
        x = layers.rmsnorm(x, params["final_norm"])
        logits = layers.unembed(params["unembed"], x)
        return logits, cache


def _split_enc(new_blocks):
    """Separate (cache, enc_kv) tuples produced inside the prefill scan."""
    caches = tuple(nc[0] for nc in new_blocks)
    encs = tuple(nc[1] for nc in new_blocks)
    # encs: per pattern position, stacked over blocks -> (n_layers, ...)
    enc = jax.tree.map(
        lambda *xs: jnp.stack(xs, axis=1).reshape(-1, *xs[0].shape[1:]), *encs)
    return caches, enc


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
