"""Causal flash attention, forward and fused backward — Pallas TPU kernels.

Every score tile lives in VMEM from the QK^T dot to its last use; only
q, k, v, the output, the log-sum-exp and the gradients touch HBM.

Layout: head-major, the G query heads of one kv head together — q as
(B, KV, G, S, hd) in the ``kv * G + g`` order of
``attention.project_qkv``, k/v as (B, KV, S, hd) — so one tile stacks the
G heads' queries (``rows = G * bq``) against one kv block and each k/v
block is read once for the group.  The kernels scale each q tile by
``1 / sqrt(hd)`` in its dtype (exact in bf16 for hd 64) before its
dots.

Both kernels hold a tile transposed, keys on sublanes and queries on
lanes: ``sT`` (bkv, rows).  A query's softmax statistics then reduce over
sublanes, elementwise across vregs, and come out as lane-major rows
(1, rows), which is also how the log-sum-exp is stored; dots that need a
transposed operand get it pre-transposed from XLA (v^T, k^T, small).

Forward, grid (B, KV, q blocks): the group's whole k and v^T strips sit
in VMEM; a loop over the kv blocks the causal (and local) mask leaves
runs online softmax and accumulates O^T (hd, rows), turned once at the
end.  Backward, grid (B, KV, kv blocks), one kernel: the group's whole q
and dO strips sit in VMEM; for its kv block it loops over the q blocks
that see it, recomputes ``sT`` once and takes five dots from it (QK^T,
dV, dP, dK, dQ^T).  dK and dV accumulate in the loop, dQ^T in a VMEM
accumulator across the grid's kv axis, so no partial gradient goes to
HBM.

Only tiles the mask hides whole are skipped; tiles it hides in part are
masked element-wise, the rest run unmasked.  Softmax statistics and
every accumulator are f32; the MXU takes operands in the inputs' dtype,
``p`` and ``ds`` rounded to it before their dots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MIN_BLOCK = 128          # the smallest tile: the lanes of a vreg
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_VMEM_LIMIT = 64 * 2 ** 20         # of the v5e's 128 MiB


def _block(S: int) -> int:
    """The q and kv tile of both kernels: the largest power of two up to
    512 that divides ``S`` (a v5e sweep at S 2048, hd 64 found 512 best
    for both kernels, PERF.md §6)."""
    b = 512
    while S % b:
        b //= 2
    return b


def flash_attention_fits(S: int, G: int, hd: int, dtype) -> bool:
    """Whether the backward's working set fits its VMEM limit: the whole
    q, dO and dQ strips of a group (double-buffered, hd padded to the
    lanes), the f32 dQ^T accumulator and four f32 tiles."""
    b = _block(S)
    strips = 6 * G * S * max(hd, MIN_BLOCK) * jnp.dtype(dtype).itemsize
    return strips + 4 * G * S * hd + 16 * b * G * b <= _VMEM_LIMIT


def _split(a, b, full_lo, full_hi):
    """Blocks ``[a, b)`` that the mask leaves, of which ``[full_lo,
    full_hi)`` it leaves whole: (masked, unmasked, masked) ranges in
    order, as the bounds ``a <= lo <= hi <= d``."""
    lo = jnp.clip(full_lo, a, b)
    hi = jnp.clip(full_hi, lo, b)
    return a, lo, hi, b


def _mask(sT, G, bq, offset, window):
    """``sT`` (bkv, G * bq) with the keys its queries may not see set to
    ``NEG_INF``: key ``ka + r`` (sublane r) against query ``qa + t`` (lane
    ``g * bq + t``), ``offset = qa - ka``."""
    bkv = sT.shape[0]
    d = lax.broadcasted_iota(jnp.int32, (bkv, bq), 0) - \
        lax.broadcasted_iota(jnp.int32, (bkv, bq), 1)     # key - query
    d = jnp.concatenate([d] * G, axis=1)
    ok = d <= offset
    if window is not None:
        ok &= d > offset - window
    return jnp.where(ok, sT, NEG_INF)


def _rows(x):
    """(G, bq) -> (1, G * bq), rows in the tile's (g, t) order."""
    return jnp.concatenate([x[g:g + 1] for g in range(x.shape[0])], axis=1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, vT_ref, o_ref, lse_ref, *, bq, bkv, window):
    # q_ref/o_ref: (G, bq, hd); k_ref: (S, hd); vT_ref: (nk, hd, bkv);
    # lse_ref: (G, bq)
    G, _, hd = q_ref.shape
    rows = G * bq
    qa = pl.program_id(2) * bq
    qb = qa + bq - 1
    q = (q_ref[...] * hd ** -0.5).reshape(rows, hd)

    def tile(j, carry, masked):
        m, l, accT = carry
        ka = pl.multiple_of(j * bkv, bkv)
        sT = lax.dot_general(k_ref[pl.ds(ka, bkv), :], q, _NT,
                             preferred_element_type=jnp.float32)
        if masked:
            sT = _mask(sT, G, bq, qa - ka, window)
        m_new = jnp.maximum(m, jnp.max(sT, axis=0, keepdims=True))
        pT = jnp.exp(sT - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(pT, axis=0, keepdims=True)
        vT = vT_ref[j]
        accT = accT * corr + jnp.dot(vT, pT.astype(vT.dtype),
                                     preferred_element_type=jnp.float32)
        return m_new, l, accT

    # kv blocks some query of this block sees, and those every query sees
    hi = qb // bkv + 1
    full_hi = (qa + 1) // bkv
    if window is None:
        lo = full_lo = 0
    else:
        lo = jnp.maximum(qa - window + 1, 0) // bkv
        full_lo = (jnp.maximum(qb - window + 1, 0) + bkv - 1) // bkv
    a, b, c, d = _split(lo, hi, full_lo, full_hi)
    carry = (jnp.full((1, rows), NEG_INF, jnp.float32),
             jnp.zeros((1, rows), jnp.float32),
             jnp.zeros((hd, rows), jnp.float32))
    carry = lax.fori_loop(a, b, functools.partial(tile, masked=True), carry)
    carry = lax.fori_loop(b, c, functools.partial(tile, masked=False), carry)
    m, l, accT = lax.fori_loop(c, d, functools.partial(tile, masked=True),
                               carry)
    o_ref[...] = jnp.transpose(accT / l).reshape(G, bq, hd) \
        .astype(o_ref.dtype)
    lse = m + jnp.log(l)
    for g in range(G):
        lse_ref[g:g + 1, :] = lse[:, g * bq:(g + 1) * bq]


def _forward(q, k, v, window, bq, bkv, interpret):
    """q: (B, KV, G, S, hd); k/v: (B, KV, S, hd).  Returns the output in
    q's layout and the log-sum-exp as (B, KV, G, S) f32."""
    B, KV, G, S, hd = q.shape
    nq, nk = S // bq, S // bkv
    vT = v.reshape(B, KV, nk, bkv, hd).swapaxes(-1, -2)
    q_spec = pl.BlockSpec((None, None, G, bq, hd),
                          lambda b, h, i: (b, h, 0, i, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bkv=bkv, window=window),
        grid=(B, KV, nq),
        in_specs=[q_spec,
                  pl.BlockSpec((None, None, S, hd),
                               lambda b, h, i: (b, h, 0, 0)),
                  pl.BlockSpec((None, None, nk, hd, bkv),
                               lambda b, h, i: (b, h, 0, 0, 0))],
        out_specs=[q_spec,
                   pl.BlockSpec((None, None, None, G, bq),
                                lambda b, h, i: (b, h, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, KV, nq, G, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, vT)
    return o, lse.transpose(0, 1, 3, 2, 4).reshape(B, KV, G, S)


# ---------------------------------------------------------------------------
# fused backward
# ---------------------------------------------------------------------------
def _bwd_kernel(q_ref, k_ref, kT_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dqT_acc, *, bq, bkv, window):
    # q_ref/do_ref/dq_ref: (G, S, hd); k_ref/v_ref/dk_ref/dv_ref: (bkv, hd);
    # kT_ref: (hd, bkv); lse_ref/di_ref: (nq, G, bq);
    # dqT_acc: (nq, hd, G * bq) f32
    G, S, hd = q_ref.shape
    rows = G * bq
    nq = S // bq
    j = pl.program_id(2)
    ka = j * bkv
    kb = ka + bkv - 1
    k = k_ref[...]
    kT = kT_ref[...]
    v = v_ref[...]

    @pl.when(j == 0)
    def _():
        dqT_acc[...] = jnp.zeros_like(dqT_acc)

    def tile(i, carry, masked):
        dk, dv = carry
        qa = pl.multiple_of(i * bq, bq)
        q = (q_ref[:, pl.ds(qa, bq), :] * hd ** -0.5).reshape(rows, hd)
        do = do_ref[:, pl.ds(qa, bq), :].reshape(rows, hd)
        sT = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        if masked:
            sT = _mask(sT, G, bq, qa - ka, window)
        pT = jnp.exp(sT - _rows(lse_ref[i]))
        dv = dv + jnp.dot(pT.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dpT = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dsT = (pT * (dpT - _rows(di_ref[i]))).astype(q.dtype)
        dk = dk + jnp.dot(dsT, q, preferred_element_type=jnp.float32)
        dqT_acc[i] += jnp.dot(kT, dsT, preferred_element_type=jnp.float32)
        return dk, dv

    # q blocks with a query that sees a key of this block, and those whose
    # every query sees every key of it
    lo = ka // bq
    full_lo = (kb + bq - 1) // bq
    if window is None:
        hi = full_hi = nq
    else:
        hi = jnp.minimum((kb + window - 1) // bq + 1, nq)
        full_hi = (ka + window) // bq
    a, b, c, d = _split(lo, hi, full_lo, full_hi)
    zero = jnp.zeros((bkv, hd), jnp.float32)
    carry = lax.fori_loop(a, b, functools.partial(tile, masked=True),
                          (zero, zero))
    carry = lax.fori_loop(b, c, functools.partial(tile, masked=False), carry)
    dk, dv = lax.fori_loop(c, d, functools.partial(tile, masked=True), carry)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for i in range(nq):
            dq_ref[:, i * bq:(i + 1) * bq, :] = jnp.transpose(
                dqT_acc[i] * hd ** -0.5).reshape(G, bq, hd) \
                .astype(dq_ref.dtype)


def _backward(q, k, v, do, lse, di, window, bq, bkv, interpret):
    """Gradients of ``_forward``'s output; ``lse`` and ``di`` as
    (B, KV, G, S) f32."""
    B, KV, G, S, hd = q.shape
    nq = S // bq

    def by_block(x):                      # -> (B, KV, nq, G, bq)
        return x.reshape(B, KV, G, nq, bq).transpose(0, 1, 3, 2, 4)
    strip = pl.BlockSpec((None, None, G, S, hd),
                         lambda b, h, j: (b, h, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bkv, hd), lambda b, h, j: (b, h, j, 0))
    stat = pl.BlockSpec((None, None, nq, G, bq),
                        lambda b, h, j: (b, h, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bkv=bkv, window=window),
        grid=(B, KV, S // bkv),
        in_specs=[strip, kv_spec,
                  pl.BlockSpec((None, None, hd, bkv),
                               lambda b, h, j: (b, h, 0, j)),
                  kv_spec, strip, stat, stat],
        out_specs=[strip, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((nq, hd, G * bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention_bwd",
    )(q, k, k.swapaxes(-1, -2), v, do, by_block(lse), by_block(di))


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, window, block, interpret):
    return _forward(q, k, v, window, block, block, interpret)[0]


def _attend_fwd(q, k, v, window, block, interpret):
    o, lse = _forward(q, k, v, window, block, block, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(window, block, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.einsum("bkgsh,bkgsh->bkgs", o.astype(jnp.float32),
                    do.astype(jnp.float32))
    return _backward(q, k, v, do, lse, di, window, block, block, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, *, window=None, interpret=None):
    """Causal self-attention; q: (B, S, H, hd), k/v: (B, S, KV, hd) with
    ``S`` a multiple of ``MIN_BLOCK`` that ``flash_attention_fits``.
    ``window``: query ``i`` sees keys
    ``[i - window + 1, i]``.  Returns (B, S, H, hd) in q's dtype.
    ``interpret=None`` auto-detects the backend (Mosaic on TPU, the
    interpreter elsewhere) via ``ops.resolve_interpret``."""
    from repro.kernels import ops as _ops
    interpret = _ops.resolve_interpret(interpret)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    assert S % MIN_BLOCK == 0 and k.shape[1] == S, (q.shape, k.shape)
    qh = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    o = _attend(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                window, _block(S), interpret)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
