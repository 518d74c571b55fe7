"""Beyond-paper: int8-quantized gradient synchronization.

MLLess reduces *semantic* communication (send only significant updates);
on a TPU mesh a dense psum moves the same wire bytes regardless.  This
module realizes actual byte savings with the standard compressed
all-reduce decomposition:

    quantize (int8, per-chunk scale) -> all_to_all (1/4 wire bytes)
    -> local dequant + reduce -> requantize -> all_gather (1/4 bytes)

with input-side error feedback (EF-SGD) so convergence is preserved.
Wire bytes: 2·G/4·(W-1)/W versus the fp32-ring 2·G·(W-1)/W — a 4x
reduction visible in the dry-run HLO (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.strategies import Strategy, _leaf_bytes


def _quant(x, axis=-1):
    """Symmetric int8 quantization with per-row fp32 scales."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


@dataclasses.dataclass(frozen=True)
class QuantizedScatterReduce(Strategy):
    """int8 compressed scatter-reduce + all-gather with error feedback."""
    name: str = "quantized_scatterreduce"
    chunk: int = 512

    def init_state(self, grads_like):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                            grads_like)

    def sync(self, grads, state, axis_names):
        # normalize to a tuple once and hand the SAME normalized axes
        # to every collective: W (the row count of the scatter layout)
        # and the all_to_all/all_gather device ordering must agree, or
        # chunks reassemble permuted.  jax collectives accept a tuple
        # of mesh axis names and treat it as the combined axis, so a
        # multi-axis data mesh (e.g. ("data", "fsdp")) reduces over the
        # full product — pinned by the 4-device parity test.
        axes = (axis_names,) if isinstance(axis_names, str) \
            else tuple(axis_names)
        if not axes:
            raise ValueError("QuantizedScatterReduce.sync needs at "
                             "least one mesh axis name")
        axis_names = axes if len(axes) > 1 else axes[0]
        W = math.prod(jax.lax.axis_size(a) for a in axes)

        new_resid, out = [], []
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(state)):
            acc = g.astype(jnp.float32) + r
            flat = acc.reshape(-1)
            per = W * self.chunk
            pad = (-flat.shape[0]) % per
            flat = jnp.pad(flat, (0, pad))
            rows = flat.reshape(W, -1, self.chunk)        # (W, nc, c)

            q, scale = _quant(rows)                       # int8 + fp32/row
            # input-side error feedback
            deq = _dequant(q, scale).reshape(-1)
            resid = (flat - deq)[:flat.shape[0] - pad] if pad \
                else flat - deq
            new_resid.append(resid.reshape(g.shape))

            # exchange: device i receives every peer's row i
            qx = jax.lax.all_to_all(q, axis_names, split_axis=0,
                                    concat_axis=0, tiled=True)
            sx = jax.lax.all_to_all(scale, axis_names, split_axis=0,
                                    concat_axis=0, tiled=True)
            part = jnp.sum(_dequant(qx, sx), axis=0) / W  # (nc, c)

            q2, s2 = _quant(part)
            qg = jax.lax.all_gather(q2, axis_names, axis=0, tiled=False)
            sg = jax.lax.all_gather(s2, axis_names, axis=0, tiled=False)
            full = _dequant(qg, sg).reshape(-1)
            full = full[:flat.shape[0] - pad] if pad else full
            out.append(full.reshape(g.shape).astype(jnp.float32))
        treedef = jax.tree.structure(grads)
        return (jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, new_resid), {})

    def comm_bytes(self, grads_like, n_workers):
        G = _leaf_bytes(grads_like)
        # int8 payload both phases + fp32 scales (1/chunk overhead)
        payload = G / 4 * (1 + 4.0 / self.chunk)
        return int(2 * payload * (n_workers - 1) / n_workers)
