"""Plain ResNet-18, CIFAR variant (He et al. 2016, arXiv:1512.03385).

3x3 stem at stride 1 with no max-pool, four stages of two basic blocks
(3x3 conv -> norm -> ReLU -> 3x3 conv -> norm, plus a 1x1 projection with
its norm where the shape changes, then ReLU of the sum), global average
pool, linear head, mean cross-entropy.

Departures from the published model, as the configuration runs it:
GroupNorm of ``groups`` groups in place of BatchNorm, and "SAME" padding,
which for a stride-2 3x3 convolution pads only the bottom and right.

Besides the reference, the module binds a configuration of this family to
the benchmark: ``program_config`` lays the file's sizes onto the program's
config, ``init_leaf`` makes each weight, and ``train_flops_per_unit``
counts a training step's model FLOPs.  Only ``program_config`` reads the
program; the reference never calls it.
"""
from __future__ import annotations

import dataclasses
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


def program_config(config: Dict[str, Any]):
    """The program's config object, its sizes taken from the file."""
    from repro.configs.base import get_config
    c = config["config"]
    return dataclasses.replace(
        get_config(config["program"]), num_classes=c["num_classes"],
        image_size=c["image_size"], channels=c["channels"],
        width_mult=c["width_mult"], dtype=config["precision"]["params"])


def init_leaf(key, name, shape, config):
    parts = name.split("/")
    last = parts[-1]
    if last == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if last == "bias" or (parts[0] == "head" and last == "b"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if parts[0] == "head":
        return jax.random.normal(key, shape, jnp.float32) / shape[0] ** 0.5
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def forward_flops(c: Dict[str, Any], batch: int) -> float:
    """Model FLOPs of a forward pass (1 MAC = 2 FLOPs): every convolution
    and the head, from the stage list."""
    size, cin = c["image_size"], c["channels"]
    total = 0.0

    def conv(hw, k, ci, co):
        return 2.0 * hw * hw * k * k * ci * co
    width = c["stem_width"]
    total += conv(size, 3, cin, width)
    hw, cin = size, width
    for cout, stride in c["stages"]:
        for b in range(c["blocks_per_stage"]):
            s = stride if b == 0 else 1
            out_hw = -(-hw // s)
            total += conv(out_hw, 3, cin, cout) + conv(out_hw, 3, cout, cout)
            if s != 1 or cin != cout:
                total += conv(out_hw, 1, cin, cout)
            hw, cin = out_hw, cout
    total += 2.0 * cin * c["num_classes"]
    return batch * total


def train_flops_per_unit(config: Dict[str, Any],
                         traffic: Dict[str, Any]) -> float:
    """Model FLOPs of one training step per image: three forward passes
    (forward, and backward at twice that)."""
    return 3.0 * forward_flops(config["config"], 1)
