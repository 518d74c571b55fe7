"""Arithmetic of the plain references, and of their lower-precision controls.

The reference computes in float32 with every matmul and convolution at
``Precision.HIGHEST``.  A control is the same code in a lower precision:
activations held in ``act``, matmul and convolution operands rounded to
``matmul`` (fp8 with one amax scale per tensor, as fp8 training does), and
parameters held in ``params``.  The reference imports nothing of the
program under test.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Numerics:
    act: str = "float32"
    matmul: Optional[str] = None     # None: operands stay in ``act``
    params: Optional[str] = None     # None: as the weights were made
    highest: bool = True

    @property
    def precision(self):
        return (jax.lax.Precision.HIGHEST if self.highest
                else jax.lax.Precision.DEFAULT)


REFERENCE = Numerics()


def control(spec: Dict[str, Any]) -> Numerics:
    """The control a configuration file names under ``control``."""
    return Numerics(act=spec["act"], matmul=spec.get("matmul"),
                    params=spec.get("params"), highest=False)


def operand(x, num: Numerics):
    """A matmul operand in the numerics' operand precision.  The rounding
    is straight-through: the backward pass's cotangents stay in ``act``."""
    act = jnp.dtype(num.act)
    x = x.astype(act)
    if num.matmul is None:
        return x
    dt = jnp.dtype(num.matmul)
    xf = x.astype(F32)
    if dt.itemsize == 1:
        top = float(jnp.finfo(dt).max)
        scale = top / jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
        rounded = (xf * scale).astype(dt).astype(F32) / scale
    else:
        rounded = xf.astype(dt).astype(F32)
    return x + jax.lax.stop_gradient(rounded.astype(act) - x)


def ein(spec: str, a, b, num: Numerics):
    out = jnp.einsum(spec, operand(a, num), operand(b, num),
                     precision=num.precision, preferred_element_type=F32)
    return out.astype(num.act)


def conv(x, w, stride: int, num: Numerics):
    """Output in ``act``: the convolution's transpose takes no
    ``preferred_element_type`` that differs from its operands'."""
    return jax.lax.conv_general_dilated(
        operand(x, num), operand(w, num), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=num.precision)


# ---------------------------------------------------------------------------
# optimizers, in float32; parameters are held in their own dtype
# ---------------------------------------------------------------------------
def opt_init(spec: Dict[str, Any], params):
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    if spec["name"] == "adamw":
        return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, zeros)}
    if spec["name"] == "sgd":
        return {"mu": zeros}
    raise ValueError(spec["name"])


def opt_update(spec: Dict[str, Any], params, grads, state, step: int):
    """One update (``step`` counts from 1); returns (params, state), each
    parameter rounded once to its own dtype."""
    lr = spec["lr"]
    if spec["name"] == "adamw":
        b1, b2, eps, wd = spec["b1"], spec["b2"], spec["eps"], \
            spec["weight_decay"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"],
                         grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                         state["v"], grads)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(p, m, v):
            pf = p.astype(F32)
            u = -lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * pf)
            return (pf + u).astype(p.dtype)
        return jax.tree.map(upd, params, m, v), {"m": m, "v": v}
    mu = jax.tree.map(lambda mu, g: spec["momentum"] * mu + g, state["mu"],
                      grads)
    new = jax.tree.map(lambda p, mu: (p.astype(F32) - lr * mu).astype(
        p.dtype), params, mu)
    return new, {"mu": mu}


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


def diff_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))
