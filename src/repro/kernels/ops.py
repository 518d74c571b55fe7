"""Jit'd public wrappers for the Pallas kernels.

``swa_attention`` carries a custom VJP whose backward recomputes
attention with the pure-jnp reference (flash-style recompute — no
O(S^2) residuals saved), so the kernel is usable inside ``jax.grad``.

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
from repro.kernels import ref as _ref
from repro.kernels import swa_attention as _swa


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
# sliding-window flash attention
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _swa_core(q, k, v, window, causal):
    S = q.shape[1]
    qb = 256 if S % 256 == 0 else (128 if S % 128 == 0 else S)
    kb = qb
    return _swa.swa_attention_fwd(q, k, v, window=window, causal=causal,
                                  q_block=qb, kv_block=kb)


def _swa_fwd(q, k, v, window, causal):
    return _swa_core(q, k, v, window, causal), (q, k, v)


def _swa_bwd(window, causal, res, g):
    # memory-light backward: the chunked flash bwd from the model library
    from repro.models import attention as _att
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _att.chunked_attention(q_, k_, v_, window=window,
                                                  causal=causal), q, k, v)
    return vjp(g)


_swa_core.defvjp(_swa_fwd, _swa_bwd)


def swa_attention(q, k, v, *, window=None, causal=True):
    return _swa_core(q, k, v, window, causal)


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
