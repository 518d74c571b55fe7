"""Plain float32 references of the benchmark's models, and their training
readings: each step's loss, the first gradient's norm per leaf, the norm
per leaf of the parameters' change after the steps, and the first
gradient itself."""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import lm, resnet
from chipbench.reference.numerics import (F32, Numerics, diff_norms,
                                          leaf_norms, opt_init, opt_update)

MODELS = {"lm": lm, "images": resnet}


def train_readings(config: Dict[str, Any], traffic: Dict[str, Any], params,
                   batches: List[dict], num: Numerics,
                   against=None) -> Dict[str, Any]:
    """Train the reference from ``params`` (the benchmark's weights, in the
    program's layout and dtypes) on ``batches``, one update each.  Given
    ``against``, another first gradient in the same layout, also return
    the norm per leaf of its difference from this one's.

    Each batch's loss and gradient are summed over blocks of rows and
    divided by the batch's units (tokens or images), so that it fits.
    """
    mod = MODELS[traffic["kind"]]
    opt = traffic["optimizer"]
    if num.params is not None:
        params = jax.tree.map(lambda p: p.astype(num.params), params)

    def loss_grad(p, block):
        with jax.default_matmul_precision(
                "highest" if num.highest else "default"):
            return jax.value_and_grad(mod.block_loss_sum)(p, block, config,
                                                          num)
    loss_grad = jax.jit(loss_grad)
    add = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x + y.astype(F32), a, b))
    scale = jax.jit(lambda t, n: jax.tree.map(lambda x: x / n, t))
    update = jax.jit(functools.partial(opt_update, opt))

    p0, p = params, params
    state = opt_init(opt, params)
    losses, first = [], None
    for k, batch in enumerate(batches):
        total, g = jnp.zeros((), F32), None
        for block in mod.row_blocks(batch):
            block = jax.tree.map(jnp.asarray, block)
            l, gb = loss_grad(p, block)
            total = total + l
            g = jax.tree.map(lambda x: x.astype(F32), gb) if g is None \
                else add(g, gb)
        n = mod.units(batch)
        g = scale(g, float(n))
        losses.append(float(total) / n)
        if k == 0:
            first = g
        p, state = update(p, g, state, jnp.float32(k + 1))
    norms, diffs = jax.jit(leaf_norms), jax.jit(diff_norms)
    out = {"losses": losses, "grad": first,
           "grad_norms": np.asarray(norms(first)).tolist(),
           "change_norms": np.asarray(diffs(p, p0)).tolist()}
    if against is not None:
        out["grad_diff_norms"] = np.asarray(diffs(
            jax.tree.map(jnp.asarray, against), first)).tolist()
    return out
