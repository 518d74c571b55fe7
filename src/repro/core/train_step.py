"""Distributed train-step builder.

Composes model × optimizer × gradient-sync strategy × mesh into a jit'd
step.  The whole step runs inside one ``jax.shard_map`` whose *manual*
axes are the data-parallel mesh axes ('pod', 'data'); the 'model' axis
stays *auto* so GSPMD provides tensor parallelism inside the body.  Local
(per-data-shard) gradients therefore exist explicitly, and the strategy's
collective schedule is exactly what appears in the lowered HLO — this is
what makes the paper's AllReduce/ScatterReduce/SPIRT/MLLess comparison
real on a TPU mesh (DESIGN.md §4/§5).

FSDP (ZeRO-3): block/tail params shard over the data axes; a per-block
all-gather hook runs inside the layer scan, and autodiff transposes it
into a reduce-scatter — those leaves arrive pre-reduced and are excluded
from the strategy sync (divided by W to turn the sum into a mean).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import losses, sharding
from repro.core.strategies import Strategy
from repro.optim.optimizers import Optimizer, apply_updates


def _strip_auto(spec: P, manual_axes) -> P:
    """Keep only manual-axis entries of a PartitionSpec."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in manual_axes)
            return kept if kept else None
        return entry if entry in manual_axes else None
    return P(*[keep(e) for e in spec])


def _make_fsdp_gather(data_axes, gdim, rs_dtype=jnp.float32):
    """all_gather with a custom transpose: bf16 gather on the forward
    wire, ``rs_dtype`` psum_scatter backward.  fp32 reduce-scatter is the
    numerically safe default (and works around an XLA:CPU
    AllReducePromotion crash on bf16 reduce-scatter under partial-manual
    meshes — DESIGN.md §6); bf16 halves the backward wire bytes
    (EXPERIMENTS.md §Perf iteration HC2b)."""
    @jax.custom_vjp
    def gather(w):
        return jax.lax.all_gather(w, axis_name=data_axes, axis=gdim,
                                  tiled=True)

    def fwd(w):
        return gather(w), None

    def bwd(_, g):
        gs = jax.lax.psum_scatter(g.astype(rs_dtype),
                                  axis_name=data_axes,
                                  scatter_dimension=gdim, tiled=True)
        return (gs.astype(g.dtype),)

    gather.defvjp(fwd, bwd)

    def named(w):
        # checkpoint_name lets a remat policy SAVE gathered params so the
        # backward does not re-gather (EXPERIMENTS.md §Perf HC3f)
        from jax.ad_checkpoint import checkpoint_name
        return checkpoint_name(gather(w), "fsdp_gather")
    return named


def _fsdp_dims(spec: P, data_axes) -> Optional[int]:
    dset = set(data_axes) if isinstance(data_axes, tuple) else {data_axes}
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        eset = set(entry) if isinstance(entry, tuple) else {entry}
        if eset == dset:
            return dim
    return None


@dataclasses.dataclass
class TrainStep:
    step_fn: Callable            # jit'd (state, batch) -> (state, metrics)
    init_state: Callable         # (rng, batch_like) -> state
    state_shardings: Any
    batch_shardings: Any
    mesh: Any
    lower_kwargs: Dict
    state_sds: Callable = None   # () -> ShapeDtypeStruct state pytree
    batch_sds: Callable = None   # (batch_shape_dict) -> SDS batch pytree


def build_train_step(model, optimizer: Optimizer, strategy: Strategy,
                     mesh, *, data_axes: Tuple[str, ...] = ("data",),
                     model_axis: Optional[str] = "model",
                     fsdp: bool = False, loss_fn=None,
                     fsdp_rs_dtype=jnp.float32) -> TrainStep:
    """``model_axis=None`` disables tensor parallelism (pure-DP/ZeRO
    profiles — the mesh axes named in ``data_axes`` all become
    data-parallel)."""
    manual_axes = set(data_axes)
    W = int(np.prod([mesh.shape[a] for a in data_axes]))
    K = strategy.microbatches

    if loss_fn is None:
        def loss_fn(params, batch):
            logits, aux = model.apply(params, batch)
            return losses.softmax_cross_entropy(
                logits, batch["labels"]) + aux

    # Name scopes a profile reads to split the step's device time into
    # phases: "forward" (differentiated, so the backward reads
    # transpose(jvp(forward)) and the remat recompute
    # .../rematted_computation/...), "sync" and "optimizer".
    def scoped_loss(params, batch):
        with jax.named_scope("forward"):
            return loss_fn(params, batch)

    # ---------------- parameter pspecs / fsdp bookkeeping ----------------
    example_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sharding.param_pspecs(example_params, mesh, fsdp=fsdp,
                                   data_axes=data_axes,
                                   model_axis=model_axis)

    if fsdp:
        blocks_specs = [jax.tree.map(lambda s: s,
                                     pspecs["blocks"][j])
                        for j in range(len(pspecs.get("blocks", [])))]
        tail_specs = list(pspecs.get("tail", []))

        def param_hook(tree, kind, idx):
            specs = (blocks_specs[idx] if kind == "block"
                     else tail_specs[idx])

            def one(g, spec):
                dim = _fsdp_dims(spec, data_axes)
                if dim is None:
                    return g
                gdim = dim - 1 if kind == "block" else dim  # scan slice
                return _make_fsdp_gather(data_axes, gdim,
                                         fsdp_rs_dtype)(g)
            return jax.tree.map(one, tree, specs,
                                is_leaf=lambda x: isinstance(x, P))
    else:
        param_hook = None

    flat_specs, spec_treedef = jax.tree.flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    fsdp_mask = [(_fsdp_dims(s, data_axes) is not None) for s in flat_specs]

    # ---------------- the shard_map body ----------------
    def step_body(state, batch):
        params, opt_state, strat_state, step = (
            state["params"], state["opt"], state["strat"], state["step"])
        # strat state carries a leading dp dim (worker-local state)
        strat_local = jax.tree.map(lambda x: x[0], strat_state)

        # microbatch over the local batch dim, clamped to what it
        # supports (SPIRT's accumulation needs >= K local minibatches —
        # a single local sample cannot be split without changing the
        # loss's attention-context semantics, so K degrades gracefully
        # to 1 under pure-DP meshes with B_local=1)
        B_local = jax.tree.leaves(batch)[0].shape[0]
        Ke = int(np.gcd(K, B_local)) if K > 1 else 1

        if Ke > 1:
            def mb_slice(i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // Ke), x.shape[0] // Ke,
                        axis=0),
                    batch)

            def acc_body(i, carry):
                acc, _ = carry
                l, g = jax.value_and_grad(scoped_loss)(params, mb_slice(i))
                return (jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g), l)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            gsum, loss = jax.lax.fori_loop(
                0, Ke, acc_body, (zeros, jnp.zeros((), jnp.float32)))
            grads = jax.tree.map(lambda g: g / Ke, gsum)
        else:
            loss, grads = jax.value_and_grad(scoped_loss)(params, batch)

        # --- split FSDP (pre-reduced) leaves from strategy-synced leaves
        gleaves, gdef = jax.tree.flatten(grads)
        sync_leaves = [g for g, m in zip(gleaves, fsdp_mask) if not m]
        with jax.named_scope("sync"):
            synced, new_strat_local, info = strategy.sync(
                sync_leaves, strat_local, data_axes if len(data_axes) > 1
                else data_axes[0])
            out_leaves, si = [], 0
            for g, m in zip(gleaves, fsdp_mask):
                if m:
                    out_leaves.append(g / W)   # reduce-scatter sum -> mean
                else:
                    out_leaves.append(synced[si])
                    si += 1
        grads = jax.tree.unflatten(gdef, out_leaves)

        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        metrics = {"loss": jax.lax.pmean(loss, data_axes if
                                         len(data_axes) > 1
                                         else data_axes[0]),
                   "step": step + 1}
        metrics.update({k: jax.lax.pmean(
            v, data_axes if len(data_axes) > 1 else data_axes[0])
            for k, v in info.items()})
        new_state = {"params": params, "opt": opt_state,
                     "strat": jax.tree.map(lambda x: x[None],
                                           new_strat_local),
                     "step": step + 1}
        return new_state, metrics

    # ---------------- spec plumbing ----------------
    manual_pspecs = jax.tree.map(lambda s: _strip_auto(s, manual_axes),
                                 pspecs, is_leaf=lambda x: isinstance(x, P))

    def opt_specs_like(opt_state):
        def one(path, leaf):
            # m/v follow their param's spec; scalars replicated
            return P()
        # build by matching structure: m and v mirror params
        specs = {}
        for k, v in opt_state.items():
            if k in ("m", "v", "mu"):
                specs[k] = manual_pspecs
            else:
                specs[k] = P()
        return specs

    example_opt = jax.eval_shape(optimizer.init, example_params)
    opt_manual = opt_specs_like(example_opt)

    sync_like = [l for l, m in zip(jax.tree.leaves(example_params),
                                   fsdp_mask) if not m]
    example_strat = jax.eval_shape(
        functools.partial(strategy.init_state), sync_like)
    strat_manual = jax.tree.map(
        lambda _: P(data_axes if len(data_axes) > 1 else data_axes[0]),
        example_strat)

    state_manual = {"params": manual_pspecs, "opt": opt_manual,
                    "strat": strat_manual, "step": P()}
    dp = data_axes if len(data_axes) > 1 else data_axes[0]
    dp_spec = dp
    batch_manual = {"tokens": P(dp), "labels": P(dp)}
    # optional modality inputs share the batch-dim sharding
    metrics_manual = {"loss": P(), "step": P()}
    if hasattr(strategy, "threshold"):
        metrics_manual["significant_fraction"] = P()

    def make_sm(batch_keys):
        bspec = {k: P(dp) for k in batch_keys}
        return jax.shard_map(
            step_body, mesh=mesh,
            in_specs=(state_manual, bspec),
            out_specs=(state_manual, metrics_manual),
            axis_names=manual_axes, check_vma=False)

    @functools.partial(jax.jit, static_argnames=())
    def step_fn(state, batch):
        # one model may serve several builders (a survivor mesh, serving):
        # install this step's FSDP hook only while this step traces
        prev = getattr(model, "param_hook", None)
        model.param_hook = param_hook
        try:
            return make_sm(tuple(sorted(batch)))(state, batch)
        finally:
            model.param_hook = prev

    # ---------------- full (auto+manual) shardings for placement -------
    full_pspecs = pspecs
    state_full = {
        "params": full_pspecs,
        "opt": {k: (full_pspecs if k in ("m", "v", "mu") else P())
                for k in example_opt},
        "strat": strat_manual,
        "step": P(),
    }
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_full,
        is_leaf=lambda x: isinstance(x, P))

    def init_state(rng, dtype_params=None):
        params = model.init(rng) if dtype_params is None else dtype_params
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: isinstance(x, P))
        # counters go on the mesh too, where the step returns them, or
        # the second step's new input types compile the step again
        opt_state = jax.device_put(optimizer.init(params),
                                   state_shardings["opt"])
        sync_like_r = [l for l, m in zip(jax.tree.leaves(params), fsdp_mask)
                       if not m]
        # worker-local strategy state: leading dim = dp world size,
        # sharded one slice per data shard
        strat_state = jax.tree.map(
            lambda x: jax.device_put(
                jnp.zeros((W,) + x.shape, x.dtype),
                NamedSharding(mesh, P(dp_spec))),
            strategy.init_state(sync_like_r))
        return {"params": params, "opt": opt_state, "strat": strat_state,
                "step": jax.device_put(jnp.zeros((), jnp.int32),
                                       state_shardings["step"])}

    batch_shardings = {k: NamedSharding(mesh, P(dp))
                       for k in ("tokens", "labels")}

    def state_sds():
        """ShapeDtypeStruct state pytree (no allocation) for dry-runs."""
        def sds(tree, shard_tree):
            return jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s),
                tree, shard_tree)
        strat_like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((W,) + x.shape, x.dtype),
            jax.eval_shape(strategy.init_state, sync_like))
        strat_sh = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(mesh, P(dp))), strat_like)
        return {
            "params": sds(example_params, state_shardings["params"]),
            "opt": sds(example_opt, state_shardings["opt"]),
            "strat": strat_sh,
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def batch_sds(extra_shapes: Optional[Dict] = None):
        """SDS batch: tokens/labels (B, S) + optional modality inputs."""
        out = {}
        for k, (shape, dtype) in (extra_shapes or {}).items():
            out[k] = jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, P(dp)))
        return out

    return TrainStep(step_fn=step_fn, init_state=init_state,
                     state_shardings=state_shardings,
                     batch_shardings=batch_shardings, mesh=mesh,
                     lower_kwargs={}, state_sds=state_sds,
                     batch_sds=batch_sds)
