"""Model FLOPs from a configuration file's sizes (1 MAC = 2 FLOPs).

``lm_forward_flops`` is the benchmark's copy of
``repro.costmodel.flops.forward_flops`` for a dense model of global
attention layers: every matmul of the forward pass, attention scores
counted causally (each query against half the sequence on average), the
embedding lookup not counted.  ``resnet_forward_flops`` counts every
convolution and the head from the stage list.  A training step's model
FLOPs are three forward passes (forward, and backward at twice that);
recomputation is not counted.
"""
from __future__ import annotations

from typing import Any, Dict


def lm_forward_flops(c: Dict[str, Any], batch: int, seq_len: int) -> float:
    d, f, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    tokens = batch * seq_len
    proj = 2 * tokens * d * (H + 2 * KV) * hd + 2 * tokens * H * hd * d
    scores = 2 * tokens * (seq_len / 2) * H * hd * 2      # QK^T and PV
    mlp = 2 * tokens * 3 * d * f                           # SwiGLU
    head = 2 * tokens * d * V
    return c["num_hidden_layers"] * (proj + scores + mlp) + head


def resnet_forward_flops(c: Dict[str, Any], batch: int) -> float:
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
    """Model FLOPs of one training step per token (LM) or image."""
    c = config["config"]
    if traffic["kind"] == "lm":
        S = traffic["seq_len"]
        return 3.0 * lm_forward_flops(c, 1, S) / S
    return 3.0 * resnet_forward_flops(c, 1)
