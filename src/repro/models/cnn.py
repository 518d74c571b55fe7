"""The paper's CNN models in pure JAX: MobileNet-style (depthwise-
separable) and ResNet-18, adapted to 32x32 CIFAR inputs.

GroupNorm replaces BatchNorm (functional purity — no running stats to
thread through the five sync strategies; convergence comparisons between
strategies are unaffected, noted in DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _conv_init(key, shape, dtype=jnp.float32):
    fan_in = np.prod(shape[:-1])
    return jax.random.normal(key, shape, dtype) * np.sqrt(2.0 / fan_in)


def conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def groupnorm(x, scale, bias, groups=8, eps=1e-5):
    """GroupNorm over NHWC, under the name scope ``norm``, which a profile
    reads to attribute device time to the normalisation in the forward
    and the backward."""
    with jax.named_scope("norm"):
        B, H, W, C = x.shape
        g = min(groups, C)
        while C % g:
            g -= 1
        xg = x.reshape(B, H, W, g, C // g)
        mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
        var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
        xg = (xg - mu) * jax.lax.rsqrt(var + eps)
        return xg.reshape(B, H, W, C) * scale + bias


def _gn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


# ---------------------------------------------------------------------------
# MobileNet (v1-style, CIFAR stride schedule) — ~4.2M params at width 1.0
# ---------------------------------------------------------------------------
_MOBILENET_CFG = [  # (out_channels, stride)
    (64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
    (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2), (1024, 1),
]


def mobilenet_init(key, cfg):
    wm = cfg.width_mult
    ch = lambda c: max(8, int(c * wm))
    ks = jax.random.split(key, 2 + 2 * len(_MOBILENET_CFG))
    params = {"stem": {"w": _conv_init(ks[0], (3, 3, cfg.channels, ch(32))),
                       "gn": _gn_init(ch(32))}}
    blocks = []
    c_in = ch(32)
    for i, (c_out, stride) in enumerate(_MOBILENET_CFG):
        c_out = ch(c_out)
        blocks.append({
            "dw": {"w": _conv_init(ks[1 + 2 * i], (3, 3, 1, c_in)),
                   "gn": _gn_init(c_in)},
            "pw": {"w": _conv_init(ks[2 + 2 * i], (1, 1, c_in, c_out)),
                   "gn": _gn_init(c_out)},
        })
        c_in = c_out
    params["blocks"] = blocks
    params["head"] = {
        "w": jax.random.normal(ks[-1], (c_in, cfg.num_classes)) *
        (1.0 / np.sqrt(c_in)),
        "b": jnp.zeros((cfg.num_classes,))}
    return params


def mobilenet_apply(params, images):
    x = conv(images, params["stem"]["w"], stride=1)
    x = jax.nn.relu(groupnorm(x, **params["stem"]["gn"]))
    for blk, (_, s) in zip(params["blocks"], _MOBILENET_CFG):
        x = conv(x, blk["dw"]["w"], stride=s, groups=x.shape[-1])
        x = jax.nn.relu(groupnorm(x, blk["dw"]["gn"]["scale"],
                                  blk["dw"]["gn"]["bias"]))
        x = conv(x, blk["pw"]["w"])
        x = jax.nn.relu(groupnorm(x, blk["pw"]["gn"]["scale"],
                                  blk["pw"]["gn"]["bias"]))
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]["w"] + params["head"]["b"]


# ---------------------------------------------------------------------------
# ResNet-18 (CIFAR variant: 3x3 stem, no maxpool) — 11.7M params
# ---------------------------------------------------------------------------
_RESNET_STAGES = [(64, 1), (128, 2), (256, 2), (512, 2)]  # 2 blocks each


def resnet18_init(key, cfg):
    wm = cfg.width_mult
    ch = lambda c: max(8, int(c * wm))
    keys = iter(jax.random.split(key, 64))
    params = {"stem": {"w": _conv_init(next(keys), (3, 3, cfg.channels,
                                                    ch(64))),
                       "gn": _gn_init(ch(64))}}
    stages = []
    c_in = ch(64)
    for c_out, stride in _RESNET_STAGES:
        c_out = ch(c_out)
        blocks = []
        for b in range(2):
            s = stride if b == 0 else 1
            blk = {
                "c1": {"w": _conv_init(next(keys), (3, 3, c_in, c_out)),
                       "gn": _gn_init(c_out)},
                "c2": {"w": _conv_init(next(keys), (3, 3, c_out, c_out)),
                       "gn": _gn_init(c_out)},
            }
            if s != 1 or c_in != c_out:
                blk["proj"] = {"w": _conv_init(next(keys),
                                               (1, 1, c_in, c_out)),
                               "gn": _gn_init(c_out)}
            blocks.append(blk)
            c_in = c_out
        stages.append(blocks)
    params["stages"] = stages
    params["head"] = {
        "w": jax.random.normal(next(keys), (c_in, cfg.num_classes)) *
        (1.0 / np.sqrt(c_in)),
        "b": jnp.zeros((cfg.num_classes,))}
    return params


def resnet18_apply(params, images):
    x = conv(images, params["stem"]["w"])
    x = jax.nn.relu(groupnorm(x, **params["stem"]["gn"]))
    for stage, (_, stride) in zip(params["stages"], _RESNET_STAGES):
        for b, blk in enumerate(stage):
            s = stride if b == 0 else 1
            h = conv(x, blk["c1"]["w"], stride=s)
            h = jax.nn.relu(groupnorm(h, blk["c1"]["gn"]["scale"],
                                      blk["c1"]["gn"]["bias"]))
            h = conv(h, blk["c2"]["w"])
            h = groupnorm(h, blk["c2"]["gn"]["scale"], blk["c2"]["gn"]["bias"])
            if "proj" in blk:
                x = conv(x, blk["proj"]["w"], stride=s)
                x = groupnorm(x, blk["proj"]["gn"]["scale"],
                              blk["proj"]["gn"]["bias"])
            x = jax.nn.relu(x + h)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]["w"] + params["head"]["b"]


class CNNModel:
    """Uniform interface used by the training/serverless layers."""

    def __init__(self, cfg):
        self.cfg = cfg
        if cfg.kind == "mobilenet":
            self._init, self._apply = mobilenet_init, mobilenet_apply
        elif cfg.kind == "resnet18":
            self._init, self._apply = resnet18_init, resnet18_apply
        else:
            raise ValueError(cfg.kind)

    def init(self, key):
        return self._init(key, self.cfg)

    def apply(self, params, batch):
        return self._apply(params, batch["images"]), jnp.zeros((),
                                                               jnp.float32)


def build_cnn(cfg) -> CNNModel:
    return CNNModel(cfg)
