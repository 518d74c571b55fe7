"""Integration tests: convergence, checkpointing, simulator, HLO parser."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import restore, save
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy, losses
from repro.core.sharding import make_mesh
from repro.data import cifar_like, lm_batches, token_stream
from repro.models import build_cnn, build_model
from repro.serverless import paper_cost_check, simulate_epoch


def test_lm_loss_decreases():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    ts = build_train_step(model, optim.adamw(3e-3),
                          get_strategy("allreduce"), mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    stream = token_stream(100_000, cfg.vocab_size)
    batches = lm_batches(stream, 16, 64)
    losses_seen = []
    for i, b in zip(range(25), batches):
        state, metrics = ts.step_fn(state, jax.tree.map(jnp.asarray, b))
        losses_seen.append(float(metrics["loss"]))
    assert np.mean(losses_seen[-5:]) < np.mean(losses_seen[:5]) - 0.3


@pytest.mark.parametrize("strategy", ["allreduce", "mlless"])
def test_init_state_types_are_the_steps_own(strategy):
    """``init_state`` places every leaf, step counters included, where
    the step returns it: the second step reuses the first's program."""
    cfg = get_config("smollm-135m").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    ts = build_train_step(build_model(cfg, remat=False), optim.adamw(1e-3),
                          get_strategy(strategy), mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    toks = np.zeros((2, 16), np.int32)
    new, _ = ts.step_fn(state, {"tokens": toks, "labels": toks})
    assert jax.tree.map(jax.typeof, new) == jax.tree.map(jax.typeof, state)


def test_cnn_learns_synthetic_cifar():
    cfg = get_config("mobilenet-cifar").reduced()
    model = build_cnn(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))

    def loss_fn(params, b):
        logits, _ = model.apply(params, b)
        return losses.classification_loss(logits, b["labels"])

    ts = build_train_step(model, optim.sgd(0.05, momentum=0.9),
                          get_strategy("spirt"), mesh, loss_fn=loss_fn)
    state = ts.init_state(jax.random.PRNGKey(0))
    imgs, labels = cifar_like(2048, seed=0)
    rs = np.random.RandomState(0)
    for step in range(40):
        idx = rs.randint(0, len(imgs), 64)
        b = {"images": jnp.asarray(imgs[idx]),
             "labels": jnp.asarray(labels[idx])}
        state, metrics = ts.step_fn(state, b)
    test_imgs, test_labels = cifar_like(512, seed=7)
    logits, _ = jax.jit(model.apply)(state["params"],
                                     {"images": jnp.asarray(test_imgs)})
    acc = float(losses.accuracy(logits, jnp.asarray(test_labels)))
    assert acc > 0.25, acc           # well above 10% chance


def test_checkpoint_roundtrip():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        save(path, params)
        zeros = jax.tree.map(jnp.zeros_like, params)
        back = restore(path, zeros)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_rejects_mismatch():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "x.msgpack")
        save(path, {"a": jnp.zeros((3,))})
        with pytest.raises(ValueError):
            restore(path, {"a": jnp.zeros((4,))})
        with pytest.raises(ValueError):
            restore(path, {"a": jnp.zeros((3,)), "b": jnp.zeros((1,))})


# ---------------------------------------------------------------------------
# serverless simulator + cost model
# ---------------------------------------------------------------------------
def test_paper_table2_arithmetic_reproduces():
    """Our cost formulas must reproduce the paper's Table 2 USD numbers
    from its reported times/RAM (GPU exact; Lambda within rounding)."""
    for model in ("mobilenet", "resnet18"):
        for arch in ("spirt", "scatterreduce", "allreduce", "mlless"):
            r = paper_cost_check(model, arch)
            rel = abs(r["our_total"] - r["paper_total"]) / r["paper_total"]
            assert rel < 0.12, (model, arch, r)
        r = paper_cost_check(model, "gpu")
        assert abs(r["our_total"] - r["paper_total"]) / r["paper_total"] \
            < 0.01


def test_simulator_stage_structure():
    """Table 1 structure: every architecture decomposes into
    fetch/compute/sync/update; statelessness costs MLLess per batch while
    SPIRT amortizes (gradient accumulation)."""
    kw = dict(n_params=4_200_000, compute_s_per_batch=2.0)
    spirt = simulate_epoch("spirt", **kw)
    mlless = simulate_epoch("mlless", **kw)
    gpu = simulate_epoch("gpu", **kw)
    assert spirt.stages.fetch < mlless.stages.fetch   # fewer invocations
    # at accumulation=24 SPIRT runs a single invocation per epoch — its
    # load cost matches the stateful GPU baseline's one-time load
    assert gpu.stages.fetch <= spirt.stages.fetch
    for rep in (spirt, mlless, gpu):
        assert rep.stages.compute == pytest.approx(24 * 2.0)
        assert rep.total_cost > 0


def test_gpu_cheaper_for_heavy_models_crossover():
    """The paper's headline: serverless wins for light models, GPU wins
    as the model grows (Table 2 MobileNet vs ResNet-18 pattern)."""
    def costs(npar, comp_sls, comp_gpu, ram):
        from repro.serverless import ServerlessSetup
        s = simulate_epoch("scatterreduce", n_params=npar,
                           compute_s_per_batch=comp_sls,
                           setup=ServerlessSetup(ram_gb=ram))
        g = simulate_epoch("gpu", n_params=npar,
                           compute_s_per_batch=comp_gpu)
        return s.total_cost, g.total_cost
    # MobileNet anchor: serverless competitive
    s_small, g_small = costs(4_200_000, 14.3, 92 / 24, 2.0)
    # 10x heavier model: Lambda time×RAM grows, GPU hourly doesn't
    s_big, g_big = costs(42_000_000, 143.0, 920 / 24, 6.0)
    assert (s_small / g_small) < (s_big / g_big)
    assert s_big > g_big


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------
def test_hlo_collective_parser_counts_scan_trips():
    import re
    from repro.costmodel.hlo_analysis import analyze_collectives
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device")

    mesh = make_mesh((2,), ("data",))

    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "data"), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    sm = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False,
                       axis_names={"data"})
    hlo = jax.jit(sm).lower(
        jnp.ones((2, 64), jnp.float32)).compile().as_text()
    stats = analyze_collectives(hlo)
    assert stats.counts["all-reduce"] >= 7   # 7 loop iterations counted
    assert stats.total_bytes >= 7 * 64 * 4


def test_trainstate_checkpoint_resume_equivalence():
    """save at step k, restore, continue == uninterrupted training."""
    from repro.core import build_train_step, get_strategy
    from repro import optim
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    ts = build_train_step(model, optim.adamw(1e-3),
                          get_strategy("mlless"), mesh)
    r = np.random.RandomState(3)
    batches = [{"tokens": r.randint(0, cfg.vocab_size, (4, 16)).astype(
        np.int32)} for _ in range(6)]
    for b in batches:
        b["labels"] = b["tokens"]

    state = ts.init_state(jax.random.PRNGKey(0))
    for b in batches[:3]:
        state, _ = ts.step_fn(state, b)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "state.msgpack")
        save(path, state)
        resumed = restore(path, jax.tree.map(jnp.zeros_like, state))
    for b in batches[3:]:
        state, m1 = ts.step_fn(state, b)
        resumed, m2 = ts.step_fn(resumed, b)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-6)
    for a, b_ in zip(jax.tree.leaves(state["params"]),
                     jax.tree.leaves(resumed["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
