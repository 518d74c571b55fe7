"""Divisibility-aware automatic sharding for parameter/cache pytrees.

Rules (DESIGN.md §4):
  * 'model' goes on the widest eligible dim of each leaf (tensor
    parallelism); stacked-block leading dims (the ``lax.scan`` axis) are
    never sharded.
  * with ``fsdp=True``, block/tail leaves additionally shard their widest
    remaining dim over the data axes (ZeRO-3); the train step all-gathers
    per block inside the scan and autodiff transposes that into a
    reduce-scatter of the gradients.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """The one mesh builder: every axis ``AxisType.Auto``.

    The train step runs its data axes manual inside ``jax.shard_map`` and
    leaves ``model`` to GSPMD, which needs Auto axes (``jax.make_mesh``
    defaults to Explicit).  ``devices`` lays the given devices out in
    order; ``None`` lets ``jax.make_mesh`` pick them.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes),
                axis_types=types)


def _axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def leaf_pspec(shape: Sequence[int], mesh, *, model_axis="model",
               data_axes=None, skip_leading=False, fsdp=False) -> P:
    """Assign mesh axes to tensor dims by divisibility, widest-first.
    ``model_axis=None`` disables tensor parallelism (pure-DP profile)."""
    ndim = len(shape)
    assign: list = [None] * ndim
    start = 1 if (skip_leading and ndim > 1) else 0
    order = sorted(range(start, ndim), key=lambda i: -shape[i])
    if model_axis is not None:
        msize = _axis_size(mesh, model_axis)
        for i in order:
            if shape[i] % msize == 0 and shape[i] >= msize:
                assign[i] = model_axis
                break
    if fsdp and data_axes is not None:
        dsize = _axis_size(mesh, data_axes)
        for i in order:
            if assign[i] is None and shape[i] % dsize == 0 \
                    and shape[i] >= dsize:
                assign[i] = data_axes
                break
    return P(*assign)


def param_pspecs(params, mesh, *, fsdp=False, data_axes=("data",),
                 model_axis="model"):
    """PartitionSpec pytree for a Model params tree."""
    def one(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        in_blocks = any(k in ("blocks", "tail", "encoder") for k in keys)
        return leaf_pspec(
            leaf.shape, mesh, model_axis=model_axis,
            data_axes=data_axes if in_blocks else None,
            skip_leading=in_blocks, fsdp=fsdp and in_blocks)
    return jax.tree_util.tree_map_with_path(one, params)


def cache_pspecs(cache, mesh, *, batch_axes=("data",), model_axis="model",
                 shard_seq=False):
    """KV caches: batch over data axes when divisible; for batch=1
    (long_500k) optionally shard the sequence dim instead (context
    parallelism for decode)."""
    bsize = _axis_size(mesh, batch_axes)

    def one(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        stacked = any(k in ("blocks", "tail") for k in keys) or \
            "enc_kv" in keys
        shape = leaf.shape
        bdim = 1 if stacked else 0
        assign: list = [None] * len(shape)
        if shape[bdim] % bsize == 0 and shape[bdim] >= bsize:
            assign[bdim] = batch_axes
        elif shard_seq and len(shape) > bdim + 1:
            # ring-buffer/seq dim
            sdim = bdim + 1
            if shape[sdim] % bsize == 0 and shape[sdim] >= bsize:
                assign[sdim] = batch_axes
        # model axis on a head/width dim if divisible; prefer the KV-heads
        # dim (-2) so int8 payloads and their (.., KV, 1) scale tensors
        # shard identically (no resharding between them at dequant)
        if model_axis is not None:
            msize = _axis_size(mesh, model_axis)
            ndim = len(shape)
            prefer = [ndim - 2, ndim - 1] + list(range(ndim - 3, bdim, -1))
            for i in prefer:
                if i <= bdim or i >= ndim:
                    continue
                if assign[i] is None and shape[i] % msize == 0 \
                        and shape[i] >= msize:
                    assign[i] = model_axis
                    break
        return P(*assign)
    return jax.tree_util.tree_map_with_path(one, cache)


def survivor_mesh(mesh, dead: int, *, data_axis: str = "data"):
    """Mesh with the ``dead`` data-parallel slice removed.

    The surviving devices keep their original order (so the collective
    reduction order over survivors is stable) and every other mesh axis
    is untouched.  Used by the resilience harness
    (``repro.resilience``) to re-mesh the fleet after a mid-step worker
    loss; ``param_pspecs`` evaluated on the survivor mesh degrades any
    dim that is no longer divisible to replication, so restoring a
    checkpoint — or adopting a dead peer's in-DB partition — onto the
    smaller mesh is always well-defined.
    """
    if data_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {data_axis!r}; axes are "
                         f"{tuple(mesh.axis_names)}")
    axis = list(mesh.axis_names).index(data_axis)
    devs = np.asarray(mesh.devices)
    n = devs.shape[axis]
    if not 0 <= dead < n:
        raise ValueError(
            f"dead worker {dead} out of range for {data_axis}={n}")
    if n < 2:
        raise ValueError(
            f"cannot remove the last {data_axis!r} shard (size {n}); "
            "a one-worker fleet has no survivors to re-mesh")
    keep = np.delete(devs, dead, axis=axis)
    return make_mesh(keep.shape, mesh.axis_names, devices=keep)


def shardings(tree_pspecs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def make_gather_hook(pspecs_blocks, data_axes):
    """Per-block FSDP all-gather hook for Model block params.

    ``pspecs_blocks``: pspec pytree for ONE block's params (leading stack
    dim removed).  Returns fn(block_params) -> gathered block params.
    """
    def hook(block_params, block_pspecs):
        def one(g, spec):
            for dim, ax in enumerate(spec):
                if ax == data_axes or (isinstance(ax, tuple)
                                       and set(ax) == set(data_axes)):
                    return jax.lax.all_gather(g, axis_name=data_axes,
                                              axis=dim, tiled=True)
            return g
        return jax.tree.map(one, block_params, block_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    return hook
