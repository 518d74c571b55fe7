"""Gradient-synchronization strategies — the paper's core contribution as
a composable JAX module.

Each of the paper's five architectures becomes a ``Strategy`` whose
``sync`` runs inside a ``jax.shard_map`` manual region over the
data-parallel mesh axes and emits that architecture's collective
schedule (DESIGN.md §5 maps serverless mechanism -> TPU collective):

  allreduce        ring all-reduce (`psum`)           [GPU baseline / ideal]
  parameter_server all-gather-to-all + local reduce   [λML AllReduce master]
  scatterreduce    psum_scatter + all_gather (tiled)  [λML ScatterReduce]
  spirt            K-step on-device grad accumulation + psum
                   (in-database accumulation -> HBM-resident accumulator)
  mlless           block-significance filtering w/ error feedback + psum
                   (significant-update filtering; effective-bytes model)

``comm_bytes`` gives the per-step logical communication volume used by
the serverless simulator and the cost model (Fig. 2/3 reproduction).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_bytes(tree) -> int:
    return sum(np.prod(l.shape) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


@dataclasses.dataclass(frozen=True)
class Strategy:
    """Base: subclasses override ``sync`` (and optionally state hooks)."""
    name: str = "base"
    microbatches: int = 1          # >1 => train_step accumulates (SPIRT)

    def init_state(self, grads_like) -> Any:
        return ()

    def sync(self, grads, state, axis_names) -> Tuple[Any, Any, Dict]:
        raise NotImplementedError

    def comm_bytes(self, grads_like, n_workers: int) -> int:
        """Logical bytes moved per sync per worker (serverless channel)."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# AllReduce (ring) — the idealized / GPU-baseline schedule
# ---------------------------------------------------------------------------
def _pmean32(g, axis_names):
    """fp32 ring all-reduce (fp32 grad reduction is standard practice;
    also works around an XLA:CPU AllReducePromotion crash on bf16 —
    DESIGN.md §6)."""
    return jax.lax.pmean(g.astype(jnp.float32),
                         axis_name=axis_names).astype(g.dtype)


@dataclasses.dataclass(frozen=True)
class AllReduce(Strategy):
    name: str = "allreduce"

    def sync(self, grads, state, axis_names):
        out = jax.tree.map(lambda g: _pmean32(g, axis_names), grads)
        return out, state, {}

    def comm_bytes(self, grads_like, n_workers):
        # ring: 2 * G * (W-1)/W  per worker
        G = _leaf_bytes(grads_like)
        return int(2 * G * (n_workers - 1) / n_workers)


# ---------------------------------------------------------------------------
# ParameterServer — the paper's λML "AllReduce" (master aggregates)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParameterServer(Strategy):
    """Master-worker aggregation.  On SPMD hardware every worker receives
    every other worker's full gradient (all_gather) and reduces locally —
    the W× byte blowup IS the master bottleneck the paper measures."""
    name: str = "parameter_server"

    def sync(self, grads, state, axis_names):
        def one(g):
            stacked = jax.lax.all_gather(g, axis_name=axis_names, axis=0,
                                         tiled=False)
            return jnp.mean(stacked.astype(jnp.float32),
                            axis=0).astype(g.dtype)
        return jax.tree.map(one, grads), state, {}

    def comm_bytes(self, grads_like, n_workers):
        # every worker uploads G and downloads (W-1) gradients
        G = _leaf_bytes(grads_like)
        return int(G * n_workers)


# ---------------------------------------------------------------------------
# ScatterReduce — chunked ownership (λML ScatterReduce)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ScatterReduce(Strategy):
    name: str = "scatterreduce"

    def sync(self, grads, state, axis_names):
        axes = (axis_names,) if isinstance(axis_names, str) else axis_names
        W = np.prod([jax.lax.axis_size(a) for a in axes])

        def one(g):
            flat = g.reshape(-1).astype(jnp.float32)
            pad = (-flat.shape[0]) % W
            flat = jnp.pad(flat, (0, pad))
            chunk = jax.lax.psum_scatter(flat, axis_name=axis_names,
                                         scatter_dimension=0, tiled=True)
            full = jax.lax.all_gather(chunk, axis_name=axis_names, axis=0,
                                      tiled=True)
            out = full[:flat.shape[0] - pad] if pad else full
            return (out / W).reshape(g.shape).astype(g.dtype)
        return jax.tree.map(one, grads), state, {}

    def comm_bytes(self, grads_like, n_workers):
        # each worker sends (W-1)/W chunks twice (reduce phase + gather)
        G = _leaf_bytes(grads_like)
        return int(2 * G * (n_workers - 1) / n_workers)


# ---------------------------------------------------------------------------
# SPIRT — P2P with in-database (on-device) gradient accumulation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Spirt(Strategy):
    """K-microbatch accumulation handled by the train-step builder (the
    accumulator lives in HBM next to compute — the in-database analogue);
    the cross-worker sync is a single psum per K microbatches."""
    name: str = "spirt"
    microbatches: int = 4

    def sync(self, grads, state, axis_names):
        out = jax.tree.map(lambda g: _pmean32(g, axis_names), grads)
        return out, state, {}

    def comm_bytes(self, grads_like, n_workers):
        # same ring volume, amortized over K local minibatches
        G = _leaf_bytes(grads_like)
        return int(2 * G * (n_workers - 1) / n_workers / self.microbatches)


# ---------------------------------------------------------------------------
# MLLess — significance-driven update filtering with error feedback
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MLLess(Strategy):
    """Block-wise significance filter: only gradient blocks whose L2 norm
    (including the error-feedback residual) exceeds ``threshold`` times
    the leaf RMS-norm-per-block are synchronized; the rest accumulate in
    the residual (error feedback => convergence is preserved).

    On TPU a dense psum moves the same wire bytes regardless of masking,
    so ``info["significant_fraction"]`` reports the *effective* (semantic)
    communication volume — the quantity MLLess bills for — while the
    quantized variant (``repro.core.compression``) realizes actual byte
    savings (beyond-paper).
    """
    name: str = "mlless"
    threshold: float = 0.5
    block: int = 256
    # None -> auto-detect like recovery.py's robust statistics: the
    # Pallas block_significance kernel on TPU (where Mosaic lowers it
    # natively), the bit-exact inline jnp path everywhere else
    use_kernel: Optional[bool] = None

    def _kernel_enabled(self) -> bool:
        if self.use_kernel is not None:
            return self.use_kernel
        from repro.kernels import ops as kops
        return not kops.default_interpret()

    def init_state(self, grads_like):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                            grads_like)

    def sync(self, grads, state, axis_names):
        use_kernel = self._kernel_enabled()
        if use_kernel:
            from repro.kernels import ops as kops
        sig_count = jnp.zeros((), jnp.float32)
        tot_count = jnp.zeros((), jnp.float32)
        new_resid = []
        filtered = []
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(state)):
            acc = g.astype(jnp.float32) + r
            flat = acc.reshape(-1)
            pad = (-flat.shape[0]) % self.block
            flat = jnp.pad(flat, (0, pad))
            blocks = flat.reshape(-1, self.block)
            if use_kernel:
                mask = kops.block_significance(blocks, self.threshold)
            else:
                bn = jnp.sqrt(jnp.sum(blocks * blocks, axis=1))
                rms = jnp.sqrt(jnp.mean(bn * bn) + 1e-20)
                mask = bn > self.threshold * rms
            keep = blocks * mask[:, None]
            kept = keep.reshape(-1)[:flat.shape[0] - pad] if pad \
                else keep.reshape(-1)
            kept = kept.reshape(g.shape)
            filtered.append(kept)
            new_resid.append(acc - kept)
            sig_count = sig_count + jnp.sum(mask)
            tot_count = tot_count + mask.shape[0]
        treedef = jax.tree.structure(grads)
        filtered = jax.tree.unflatten(treedef, filtered)
        new_resid = jax.tree.unflatten(treedef, new_resid)
        out = jax.tree.map(
            lambda g: jax.lax.pmean(g, axis_name=axis_names).astype(g.dtype),
            filtered)
        frac = sig_count / jnp.maximum(tot_count, 1)
        return out, new_resid, {"significant_fraction": frac}

    def comm_bytes(self, grads_like, n_workers, significant_fraction=0.3):
        G = _leaf_bytes(grads_like)
        return int(2 * G * (n_workers - 1) / n_workers
                   * significant_fraction)


STRATEGIES = {
    "allreduce": AllReduce,
    "parameter_server": ParameterServer,
    "scatterreduce": ScatterReduce,
    "spirt": Spirt,
    "mlless": MLLess,
}


def get_strategy(name: str, **kw) -> Strategy:
    if name == "quantized_scatterreduce":    # beyond-paper (lazy import)
        from repro.core.compression import QuantizedScatterReduce
        return QuantizedScatterReduce(**kw)
    if name in ("trimmed_mean", "coordinate_median", "krum",
                "geometric_median"):
        # byzantine-robust aggregation (SPIRT §5 / Blanchard et al. /
        # Weiszfeld) — lazy import to keep core free of a hard
        # serverless dependency
        from repro.serverless.recovery import (CoordinateMedian,
                                               GeometricMedian, Krum,
                                               TrimmedMean)
        cls = {"trimmed_mean": TrimmedMean,
               "coordinate_median": CoordinateMedian,
               "krum": Krum,
               "geometric_median": GeometricMedian}[name]
        return cls(**kw)
    if name == "byzantine":
        # fault-injection wrapper: get_strategy("byzantine",
        #   inner=get_strategy("trimmed_mean"), workers=(0,))
        from repro.serverless.faults import ByzantineGradients
        return ByzantineGradients(**kw)
    if name in STRATEGIES:
        return STRATEGIES[name](**kw)
    # simulated architecture names resolve through the ArchSpec registry
    # (sim-arch and real-training-arch are one object): e.g. "gpu" is a
    # ring allreduce, "hier_spirt"/"spirt_s3" ride SPIRT accumulation.
    # Lazy import keeps core usable without the serverless package.
    from repro.serverless.archs import _REGISTRY
    spec = _REGISTRY.get(name)
    if spec is not None and spec.jax_strategy is not None:
        return spec.make_strategy(**kw)
    raise KeyError(name)
