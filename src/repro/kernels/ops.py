"""Jit'd public wrappers for the Pallas kernels.

``flash_attention`` is causal self-attention on TPU, a forward kernel
and one fused backward kernel (``flash_attention.py``);
``models.attention.chunked_attention`` selects it there.

``default_interpret()`` is the shared backend auto-detect every kernel
module resolves its ``interpret=None`` default through: Mosaic lowering
on TPU, the Pallas interpreter everywhere else (the validation mode for
this container).  Production call paths must never hard-code
``interpret=True`` — the ``kernel-interpret-default`` lint rule pins
this; pass ``interpret=`` explicitly only in parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import block_significance as _bs
from repro.kernels import flash_attention as _flash
from repro.kernels import fused_adamw as _fa


def default_interpret() -> bool:  # repro: allow[kernel-ref-parity] -- backend helper, not a kernel
    """True off-TPU: only Mosaic can lower these kernels natively."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:  # repro: allow[kernel-ref-parity] -- backend helper, not a kernel
    """Resolve an ``interpret=`` escape hatch: None -> auto-detect."""
    return default_interpret() if interpret is None else bool(interpret)


def _whole_on_auto_axes(fn, *args):
    """Call ``fn`` on whole operands on every device of the ambient
    mesh's Auto axes.

    Mosaic kernels cannot be partitioned by GSPMD.  Inside the train
    step's ``shard_map`` the data axes are manual and ``model`` is left
    Auto, so a kernel there is wrapped in a ``shard_map`` over the Auto
    axes with replicated operands; with no Auto axis in scope ``fn`` is
    called as is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}
    if not auto:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * len(args),
                         out_specs=P(), axis_names=auto,
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# causal flash attention (forward and fused backward)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, window=None):
    """Causal self-attention on whole operands (``flash_attention.py``);
    shapes as ``ref.flash_attention``."""
    return _whole_on_auto_axes(
        functools.partial(_flash.flash_attention, window=window), q, k, v)


# ---------------------------------------------------------------------------
# MLLess block significance
# ---------------------------------------------------------------------------
def block_significance(blocks, threshold):
    """blocks: (n, b) -> bool mask of significant blocks."""
    sq = _whole_on_auto_axes(_bs.block_norms, blocks)
    rms = jnp.sqrt(jnp.mean(sq) + 1e-20)
    return jnp.sqrt(sq) > threshold * rms


def significance_filter(blocks, threshold):
    """Returns (kept, residual, mask) in one fused pass."""
    mask = block_significance(blocks, threshold)
    kept, resid = _whole_on_auto_axes(_bs.masked_filter, blocks, mask)
    return kept, resid, mask


# ---------------------------------------------------------------------------
# RWKV6 chunked WKV
# ---------------------------------------------------------------------------
def wkv6(r, k, v, logw, u, *, chunk=64):
    """Chunked WKV recurrence (state VMEM-resident). Shapes as ref.wkv6."""
    from repro.kernels import wkv6 as _w
    T = r.shape[1]
    c = chunk
    while T % c:
        c //= 2
    return _w.wkv6_chunked(r, k, v, logw, u, chunk=max(c, 1))


# ---------------------------------------------------------------------------
# fused AdamW
# ---------------------------------------------------------------------------
def fused_adamw(g, m, v, p, *, lr, b1, b2, eps, wd, c1, c2):
    """Pytree-leaf update: any-shape operands, flattened internally."""
    shape = g.shape
    out = _whole_on_auto_axes(
        functools.partial(_fa.fused_adamw_flat, lr=lr, b1=b1, b2=b2,
                          eps=eps, wd=wd),
        g.reshape(-1), m.reshape(-1), v.reshape(-1), p.reshape(-1),
        jnp.asarray(c1), jnp.asarray(c2))
    u, m_new, v_new = (x.reshape(shape) for x in out)
    return u.astype(p.dtype), m_new, v_new
