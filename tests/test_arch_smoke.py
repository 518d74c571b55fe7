"""Per-architecture smoke tests (deliverable f).

Each assigned architecture is instantiated as a REDUCED same-family
variant (2 layers, d_model <= 512, <= 4 experts) and runs one forward
plus one train step on CPU, asserting output shapes and the absence of
NaNs.  Prefill/decode consistency is covered in test_serving.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy, losses
from repro.core.sharding import make_mesh
from repro.models import attention, build_cnn, build_model

ARCHS = [
    "mixtral-8x22b", "gemma3-4b", "mixtral-8x7b", "rwkv6-7b", "pixtral-12b",
    "smollm-135m", "whisper-small", "phi3-mini-3.8b", "recurrentgemma-2b",
    "qwen1.5-4b",
]


def _batch(cfg, B=2, S=32, seed=0):
    r = np.random.RandomState(seed)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)}
    batch["labels"] = batch["tokens"].copy()
    if cfg.family == "vlm":
        batch["patch_emb"] = r.randn(B, cfg.n_patches, cfg.d_model).astype(
            np.float32) * 0.1
    if cfg.is_encoder_decoder:
        batch["frames"] = r.randn(B, cfg.encoder_seq, cfg.d_model).astype(
            np.float32) * 0.1
    return jax.tree.map(jnp.asarray, batch)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nans(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.n_experts <= 4
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = jax.jit(model.apply)(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits, np.float32)).any()
    assert not np.isnan(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg, remat=False)
    ts = build_train_step(model, optim.adamw(1e-3),
                          get_strategy("allreduce"), mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    new_state, metrics = ts.step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state["step"]) == 1
    # params actually changed
    before = jax.tree.leaves(state["params"])[0]
    after = jax.tree.leaves(new_state["params"])[0]
    assert not np.allclose(np.asarray(before, np.float32),
                           np.asarray(after, np.float32))


@pytest.mark.parametrize("arch,n_layers", [
    ("smollm-135m", 2), ("recurrentgemma-2b", 3), ("whisper-small", 2)])
def test_remat_keeps_loss_and_grads(arch, n_layers, monkeypatch):
    """Remat's policy keeps the flash kernel's output and log-sum-exp: the
    loss and every gradient equal those without remat at f32.  Causal
    self-attention is steered onto the kernel (interpret mode here).
    Three layers give recurrentgemma one RG-LRU, RG-LRU, local-attention
    block; whisper adds the encoder's scan and cross-attention."""
    steered = []

    def uses_flash_kernel(q, k, causal):
        steered.append(causal and k.shape[1] == q.shape[1])
        return steered[-1]
    monkeypatch.setattr(attention, "uses_flash_kernel", uses_flash_kernel)
    cfg = get_config(arch).reduced(n_layers=n_layers)
    batch = _batch(cfg, S=128)
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    def value_and_grad(remat):
        model = build_model(cfg, remat=remat)

        def loss(p):
            logits, aux = model.apply(p, batch)
            return losses.softmax_cross_entropy(logits, batch["labels"]) + aux
        return jax.jit(jax.value_and_grad(loss))(params)

    (got, g_got), (want, g_want) = value_and_grad(True), value_and_grad(False)
    assert any(steered)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    # each leaf on the larger of its own norm and the median leaf's, as
    # the chip benchmark's check: some leaves' gradients are 0 but for
    # rounding (a key bias under softmax)
    g_got, g_want = ({jax.tree_util.keystr(k): np.asarray(x, np.float64)
                      for k, x in jax.tree_util.tree_leaves_with_path(g)}
                     for g in (g_got, g_want))
    median = np.median([np.linalg.norm(x) for x in g_want.values()])
    for name, b in g_want.items():
        err = np.linalg.norm(g_got[name] - b)
        assert err <= 1e-5 * max(np.linalg.norm(b), median), name


@pytest.mark.parametrize("kind", ["mobilenet-cifar", "resnet18-cifar"])
def test_cnn_smoke(kind):
    cfg = get_config(kind).reduced()
    model = build_cnn(cfg)
    params = model.init(jax.random.PRNGKey(0))
    imgs = jnp.asarray(np.random.RandomState(0).randn(4, 32, 32, 3),
                       jnp.float32)
    logits, _ = jax.jit(model.apply)(params, {"images": imgs})
    assert logits.shape == (4, 10)
    assert not np.isnan(np.asarray(logits)).any()
