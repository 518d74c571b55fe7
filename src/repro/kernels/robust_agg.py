"""Byzantine-robust aggregation reductions — Pallas TPU kernels.

The PR 5 robust aggregators (``repro.serverless.recovery``) are the hot
numeric path of every converges-under-attack row and real-JAX recovery
run: per sync step the fleet's ``[W, D]`` gradient stack (W workers,
D = flat model size) is reduced with a byzantine-robust statistic.
SPIRT's argument (arXiv 2309.14148) — keep state adjacent to compute
instead of bouncing it through a master — is exactly the roofline
argument for fusing these reductions: every statistic below is
bandwidth-bound (touch W*D floats, emit D), so the kernel's job is to
touch HBM once per operand, with the worker axis resident in VMEM.

Four kernels, tiled over the D axis with the full W axis per tile:

  ``trimmed_mean``       trim == 1: one fused pass masking the per-
                         coordinate min and max entries and summing the
                         interior (the cancellation-safe form — NOT
                         (sum-min-max)/(W-2); see recovery.trimmed_mean).
                         trim >= 2: a Batcher odd-even compare-exchange
                         network sorts the W lane-vectors inside the
                         tile (O(W log^2 W) min/max ops, no gathers —
                         the "masked partial-sort" a D-tiled layout
                         wants) and the interior rows are averaged.
  ``coordinate_median``  the same sorting network; median = middle row
                         (odd W) or mean of the two middle rows (even).
  ``krum_pairwise``      the W x W squared-distance matrix, accumulated
                         across D tiles as ||xi||^2 + ||xj||^2 - 2 Gram
                         (one MXU contraction per tile) instead of
                         materializing the [W, W, D] broadcast in HBM.
  ``weiszfeld_step``     one geometric-median (Weiszfeld) iteration,
                         fused distance + reweight: pass 1 accumulates
                         per-row squared distances to z across D tiles,
                         pass 2 emits the re-weighted combination.

Dispatch contract (shared with ``repro.kernels.ops``): ``interpret=``
is the escape hatch —

  ``None``   auto-detect: Mosaic-compiled Pallas on TPU, otherwise the
             *fused jnp twin* of the kernel body (same tile math on the
             whole array).  Production code therefore never runs the
             Pallas interpreter silently (the ``kernel-interpret-
             default`` lint rule pins this contract).
  ``True``   force the Pallas interpreter — the validation mode parity
             tests use on CPU.
  ``False``  force Mosaic lowering.

Pure-jnp oracles live in ``repro.kernels.ref`` (kernel-ref-parity);
the batched numpy twins driving the adversarial sweep stay in
``repro.serverless.adversarial``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128                 # last-dim tile multiple (TPU lane width)
_DEFAULT_TILE_D = 4096


def _auto_interpret(interpret):
    """Resolve the ``interpret=`` escape hatch; None -> backend
    auto-detect (the shared helper in ops.py)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        return default_interpret()
    return bool(interpret)


def _flatten_stack(stacked):
    """[W, ...] -> (W, D) fp32 view + trailing shape for un-flattening."""
    W = stacked.shape[0]
    trailing = stacked.shape[1:]
    return stacked.reshape(W, -1).astype(jnp.float32), trailing


def _pad_tiles(flat, tile_d):
    """Pad the D axis to a tile multiple (zeros; padded columns are
    sliced off / distance-neutral)."""
    D = flat.shape[1]
    tile = min(tile_d, max(_LANE, D))
    tile += (-tile) % _LANE
    pad = (-D) % tile
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, tile, flat.shape[1] // tile


# ---------------------------------------------------------------------------
# shared tile math (the kernel bodies AND the fused jnp twins)
# ---------------------------------------------------------------------------
def _batcher_pairs(n: int):
    """Compare-exchange pairs of a Batcher odd-even/bitonic sorting
    network for ``n`` a power of two; (lo, hi) means "row lo receives
    the minimum".  Static python ints — fully unrolled at trace time."""
    pairs = []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            for i in range(n):
                partner = i ^ j
                if partner > i:
                    pairs.append((i, partner) if (i & k) == 0
                                 else (partner, i))
            j //= 2
        k *= 2
    return pairs


def _sorted_rows(x):
    """Sort a (W, d) fp32 block along axis 0 via the compare-exchange
    network (rows held as a python list — no gathers, VPU min/max
    only).  Non-power-of-two W pads with +inf rows that sink to the
    bottom and are dropped."""
    W = x.shape[0]
    P = 1
    while P < W:
        P *= 2
    rows = [x[i] for i in range(W)]
    rows += [jnp.full_like(rows[0], jnp.inf) for _ in range(P - W)]
    for lo, hi in _batcher_pairs(P):
        a, b = rows[lo], rows[hi]
        rows[lo] = jnp.minimum(a, b)
        rows[hi] = jnp.maximum(a, b)
    return rows[:W]


def _tile_trimmed_mean(x, trim: int):
    """(W, d) fp32 -> (d,) trimmed interior mean.  trim == 1 is the
    masked one-pass form (cancellation-safe under a scaled byzantine
    row); trim >= 2 runs the sorting network."""
    W = x.shape[0]
    if trim == 1:
        imin = jnp.argmin(x, axis=0)
        imax = jnp.argmax(x, axis=0)
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        keep = (idx != imin[None, :]) & (idx != imax[None, :])
        mid = jnp.sum(x * keep, axis=0) / (W - 2)
        # argmin == argmax only when the whole column is constant
        return jnp.where(imin == imax, x[0], mid)
    rows = _sorted_rows(x)
    interior = rows[trim:W - trim]
    return functools.reduce(jnp.add, interior) / len(interior)


def _tile_median(x):
    """(W, d) fp32 -> (d,) per-coordinate median via the network."""
    W = x.shape[0]
    rows = _sorted_rows(x)
    if W % 2:
        return rows[W // 2]
    return 0.5 * (rows[W // 2 - 1] + rows[W // 2])


def _tile_sqdist(x):
    """(W, d) fp32 -> (W, W) partial squared distances via the Gram
    matrix: one MXU contraction instead of a [W, W, d] broadcast."""
    n = jnp.sum(x * x, axis=1)
    g = jax.lax.dot_general(x, x, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    d = n[:, None] + n[None, :] - 2.0 * g
    return jnp.maximum(d, 0.0)          # Gram cancellation never < 0


# ---------------------------------------------------------------------------
# trimmed mean / coordinate median
# ---------------------------------------------------------------------------
def _rowstat_kernel(x_ref, o_ref, *, stat, trim):
    x = x_ref[...].astype(jnp.float32)
    out = _tile_trimmed_mean(x, trim) if stat == "trim" else _tile_median(x)
    o_ref[...] = out[None, :]


_ROWSTAT_NAMES = {"trim": "robust_agg_trimmed_mean",
                  "median": "robust_agg_median"}


def _rowstat_pallas(flat, tile_d, interpret, *, stat, trim=0):
    W = flat.shape[0]
    padded, tile, n_tiles = _pad_tiles(flat, tile_d)
    kernel = functools.partial(_rowstat_kernel, stat=stat, trim=trim)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((W, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded.shape[1]), jnp.float32),
        interpret=interpret,
        name=_ROWSTAT_NAMES[stat],
    )(padded)
    return out[0]


def trimmed_mean(stacked, trim: int = 1, *, tile_d: int = _DEFAULT_TILE_D,
                 interpret=None):
    """Mean over axis 0 of a ``[W, ...]`` stack after dropping the
    ``trim`` smallest and largest values per coordinate.  Returns fp32
    with the stack's trailing shape; needs ``W > 2*trim``."""
    W = stacked.shape[0]
    if trim < 1:
        raise ValueError(f"trimmed_mean kernel needs trim >= 1, got "
                         f"trim={trim}")
    if W <= 2 * trim:
        raise ValueError(f"trimmed_mean needs W > 2*trim, got W={W}, "
                         f"trim={trim}")
    flat, trailing = _flatten_stack(stacked)
    if interpret is None and _auto_interpret(None):
        red = _tile_trimmed_mean(flat, trim)        # fused jnp twin
    else:
        red = _rowstat_pallas(flat, tile_d, _auto_interpret(interpret),
                              stat="trim", trim=trim)
    return red[:flat.shape[1]].reshape(trailing)


def coordinate_median(stacked, *, tile_d: int = _DEFAULT_TILE_D,
                      interpret=None):
    """Per-coordinate median over axis 0 of a ``[W, ...]`` stack
    (fp32; even W averages the two middle order statistics, matching
    ``jnp.median``)."""
    flat, trailing = _flatten_stack(stacked)
    if interpret is None and _auto_interpret(None):
        red = _tile_median(flat)                    # fused jnp twin
    else:
        red = _rowstat_pallas(flat, tile_d, _auto_interpret(interpret),
                              stat="median")
    return red[:flat.shape[1]].reshape(trailing)


# ---------------------------------------------------------------------------
# Krum pairwise distances
# ---------------------------------------------------------------------------
def _sqdist_kernel(x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += _tile_sqdist(x)


def krum_pairwise(stacked, *, tile_d: int = _DEFAULT_TILE_D,
                  interpret=None):
    """``[W, ...]`` stack -> (W, W) fp32 matrix of squared Euclidean
    distances between rows (diagonal ~0), accumulated in a single pass
    over D tiles.  The selection/scoring layer on top is cheap (W is
    the fleet size); the O(W^2 D) distance work is the hot part."""
    flat, _ = _flatten_stack(stacked)
    W = flat.shape[0]
    if interpret is None and _auto_interpret(None):
        return _tile_sqdist(flat)                   # fused jnp twin
    padded, tile, n_tiles = _pad_tiles(flat, tile_d)
    return pl.pallas_call(
        _sqdist_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((W, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((W, W), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((W, W), jnp.float32),
        interpret=_auto_interpret(interpret),
        name="robust_agg_krum_sqdist",
    )(padded)


# ---------------------------------------------------------------------------
# Weiszfeld inner step (geometric median)
# ---------------------------------------------------------------------------
def _accum_sqdist_kernel(x_ref, z_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum((x - z) ** 2, axis=1, keepdims=True)


def _wsum_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)              # (W, 1)
    o_ref[...] = jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (1, tile)


def weiszfeld_step(stacked, z, floor, *, row_sqnorms=None,
                   tile_d: int = _DEFAULT_TILE_D, interpret=None):
    """One Weiszfeld iteration on a ``[W, D]`` stack: distances of
    every row to ``z``, inverse-distance weights floored at ``floor``
    (the tolerance guard recovery.geometric_median uses), re-weighted
    combination.  Returns the new fp32 ``(D,)`` estimate.

    ``row_sqnorms`` (the per-row ``||x_i||^2``, constant across
    iterations) lets the fused jnp twin use the cached-Gram form —
    ``d_i^2 = ||x_i||^2 - 2 x_i.z + ||z||^2`` — touching the stack
    twice per step instead of three times; the Pallas path computes
    the numerically-safer ``sum((x - z)^2)`` in-tile and ignores it."""
    flat, _ = _flatten_stack(stacked)
    W, D = flat.shape
    z = z.reshape(-1).astype(jnp.float32)
    if z.shape[0] != D:
        raise ValueError(f"weiszfeld_step needs z of length {D}, got "
                         f"{z.shape[0]}")
    if interpret is None and _auto_interpret(None):
        if row_sqnorms is None:
            sq = jnp.sum((flat - z[None, :]) ** 2, axis=1)
        else:
            sq = jnp.maximum(
                row_sqnorms - 2.0 * (flat @ z) + jnp.dot(z, z), 0.0)
        w = 1.0 / jnp.maximum(jnp.sqrt(sq), floor)
        return (w @ flat) / jnp.sum(w)
    interp = _auto_interpret(interpret)
    padded, tile, n_tiles = _pad_tiles(flat, tile_d)
    zp = jnp.pad(z, (0, padded.shape[1] - D)).reshape(1, -1)
    sq = pl.pallas_call(
        _accum_sqdist_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((W, tile), lambda i: (0, i)),
                  pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((W, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 1), jnp.float32),
        interpret=interp,
        name="robust_agg_weiszfeld_sqdist",
    )(padded, zp)
    w = 1.0 / jnp.maximum(jnp.sqrt(sq), floor)       # (W, 1)
    wsum = pl.pallas_call(
        _wsum_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((W, tile), lambda i: (0, i)),
                  pl.BlockSpec((W, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded.shape[1]), jnp.float32),
        interpret=interp,
        name="robust_agg_weiszfeld_wsum",
    )(padded, w)
    return wsum[0, :D] / jnp.sum(w)
