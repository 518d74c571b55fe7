"""Plain ResNet-18, CIFAR variant (He et al. 2016, arXiv:1512.03385).

3x3 stem at stride 1 with no max-pool, four stages of two basic blocks
(3x3 conv -> norm -> ReLU -> 3x3 conv -> norm, plus a 1x1 projection with
its norm where the shape changes, then ReLU of the sum), global average
pool, linear head, mean cross-entropy.

Departures from the published model, as the configuration runs it:
GroupNorm of ``groups`` groups in place of BatchNorm, and "SAME" padding,
which for a stride-2 3x3 convolution pads only the bottom and right.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import F32, Numerics, conv, ein


def groupnorm(x, gn, groups: int, eps: float, num: Numerics):
    B, H, W, C = x.shape
    xg = x.astype(F32).reshape(B, H, W, groups, C // groups)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    out = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(B, H, W, C)
    return (out * gn["scale"].astype(F32)
            + gn["bias"].astype(F32)).astype(num.act)


def forward(params, images, config: Dict[str, Any], num: Numerics):
    c = config["config"]
    g, eps = c["norm_groups"], c["norm_eps"]

    def norm(x, gn):
        return groupnorm(x, gn, g, eps, num)
    x = conv(images.astype(num.act), params["stem"]["w"], 1, num)
    x = jax.nn.relu(norm(x, params["stem"]["gn"]))
    for stage, (_, stride) in zip(params["stages"], c["stages"]):
        for b, blk in enumerate(stage):
            s = stride if b == 0 else 1
            h = jax.nn.relu(norm(conv(x, blk["c1"]["w"], s, num),
                                 blk["c1"]["gn"]))
            h = norm(conv(h, blk["c2"]["w"], 1, num), blk["c2"]["gn"])
            if "proj" in blk:
                x = norm(conv(x, blk["proj"]["w"], s, num),
                         blk["proj"]["gn"])
            x = jax.nn.relu(x + h)
    x = jnp.mean(x.astype(F32), axis=(1, 2)).astype(num.act)
    return ein("bc,ck->bk", x, params["head"]["w"], num).astype(F32) + \
        params["head"]["b"].astype(F32)


def block_loss_sum(params, block, config, num):
    logits = forward(params, block["images"], config, num)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, block["labels"][:, None], axis=-1)
    return jnp.sum(lse - gold[:, 0])


ROWS = 128


def row_blocks(batch):
    """Blocks of ``ROWS`` images."""
    n = batch["images"].shape[0]
    return [{"images": batch["images"][i:i + ROWS],
             "labels": batch["labels"][i:i + ROWS]}
            for i in range(0, n, ROWS)]


def units(batch) -> int:
    return int(batch["labels"].size)
