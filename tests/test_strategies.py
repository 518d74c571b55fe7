"""Gradient-sync strategy semantics (the paper's core, §2/Table 1).

Key invariants:
  * allreduce == scatterreduce == parameter_server (exact same mean)
  * spirt(K) equals allreduce when the global batch is identical
    (mean of microbatch means == full-batch mean)
  * mlless with threshold=0 equals allreduce; with threshold>0 the
    filtered+residual decomposition conserves the gradient
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy
from repro.core.sharding import make_mesh
from repro.core.strategies import MLLess
from repro.models import build_model


@pytest.fixture(scope="module")
def setting():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    r = np.random.RandomState(1)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (8, 32)).astype(
        np.int32)}
    batch["labels"] = batch["tokens"]
    return cfg, model, mesh, batch


def _run(model, mesh, batch, strategy, steps=2):
    ts = build_train_step(model, optim.sgd(0.1), strategy, mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    for _ in range(steps):
        state, metrics = ts.step_fn(state, batch)
    flat = np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(state["params"])])
    return flat, metrics


def test_reduce_strategies_agree(setting):
    cfg, model, mesh, batch = setting
    base, _ = _run(model, mesh, batch, get_strategy("allreduce"))
    for name in ("scatterreduce", "parameter_server", "spirt"):
        other, _ = _run(model, mesh, batch, get_strategy(name))
        np.testing.assert_allclose(base, other, atol=1e-5, err_msg=name)


def test_mlless_zero_threshold_equals_allreduce(setting):
    cfg, model, mesh, batch = setting
    base, _ = _run(model, mesh, batch, get_strategy("allreduce"))
    ml, metrics = _run(model, mesh, batch, MLLess(threshold=0.0))
    # threshold 0 keeps every non-zero block (zero-gradient blocks, e.g.
    # unseen vocabulary rows, are dropped but contribute nothing anyway)
    assert float(metrics["significant_fraction"]) > 0.5
    np.testing.assert_allclose(base, ml, atol=1e-5)


def test_mlless_filters_and_converges_direction(setting):
    cfg, model, mesh, batch = setting
    _, metrics = _run(model, mesh, batch, MLLess(threshold=1.0), steps=3)
    frac = float(metrics["significant_fraction"])
    assert 0.0 < frac < 1.0  # actually filtering something
    assert np.isfinite(float(metrics["loss"]))


def test_mlless_kernel_path_matches_inline_path(setting):
    """The Pallas block-significance path (interpret mode here), run
    inside the train step's shard_map with ``model`` left Auto, filters
    exactly as the inline jnp path does."""
    cfg, model, mesh, batch = setting
    inline, m0 = _run(model, mesh, batch, MLLess(threshold=1.0,
                                                 use_kernel=False))
    kernel, m1 = _run(model, mesh, batch, MLLess(threshold=1.0,
                                                 use_kernel=True))
    assert float(m1["significant_fraction"]) == float(
        m0["significant_fraction"])
    np.testing.assert_allclose(inline, kernel, atol=1e-6)


def test_strategy_comm_bytes_ordering():
    """Paper §4.2: PS(master) moves W× bytes; ring strategies 2G(W-1)/W;
    MLLess a fraction; SPIRT amortizes by K."""
    grads = [np.zeros(1000, np.float32)]
    W = 8
    ar = get_strategy("allreduce").comm_bytes(grads, W)
    sr = get_strategy("scatterreduce").comm_bytes(grads, W)
    ps = get_strategy("parameter_server").comm_bytes(grads, W)
    sp = get_strategy("spirt").comm_bytes(grads, W)
    ml = get_strategy("mlless").comm_bytes(grads, W,
                                           significant_fraction=0.25)
    assert ar == sr                 # scatter-reduce IS decomposed ring
    assert ps > ar                  # master blowup
    assert sp < ar                  # K-step amortization
    assert ml < ar                  # filtering
