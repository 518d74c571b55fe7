"""Quantized scatter-reduce (beyond-paper): accuracy + byte accounting."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy
from repro.core.compression import QuantizedScatterReduce, _dequant, _quant
from repro.core.sharding import make_mesh
from repro.models import build_model


def test_quant_roundtrip_accuracy():
    x = jnp.asarray(np.random.RandomState(0).randn(16, 4, 512), jnp.float32)
    q, s = _quant(x)
    err = jnp.abs(_dequant(q, s) - x)
    assert float(err.max()) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_quantized_sync_close_to_allreduce():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    r = np.random.RandomState(0)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (8, 32)).astype(
        np.int32)}
    batch["labels"] = batch["tokens"]

    outs = {}
    for name in ("allreduce", "quantized_scatterreduce"):
        ts = build_train_step(model, optim.sgd(0.1), get_strategy(name),
                              mesh)
        state = ts.init_state(jax.random.PRNGKey(0))
        for _ in range(3):
            state, metrics = ts.step_fn(state, batch)
        outs[name] = (np.concatenate(
            [np.asarray(l, np.float32).ravel()
             for l in jax.tree.leaves(state["params"])]),
            float(metrics["loss"]))
    a, qz = outs["allreduce"][0], outs["quantized_scatterreduce"][0]
    # int8 quantization error is small relative to the update magnitude
    rel = np.abs(a - qz).max() / (np.abs(a).max() + 1e-9)
    assert rel < 5e-2, rel
    assert np.isfinite(outs["quantized_scatterreduce"][1])


def test_quantized_comm_bytes_quarter_of_ring():
    grads = [np.zeros(10**6, np.float32)]
    ring = get_strategy("allreduce").comm_bytes(grads, 16)
    qz = get_strategy("quantized_scatterreduce").comm_bytes(grads, 16)
    assert qz < ring / 3.5   # ~4x minus scale overhead


def test_quant_dequant_deterministic():
    """Same input -> bitwise identical quantization, jitted twice (the
    compressed sweeps are a pure function of (grid, seed))."""
    x = jnp.asarray(np.random.RandomState(1).randn(8, 16, 512),
                    jnp.float32)
    f = jax.jit(lambda a: _quant(a))
    q1, s1 = f(x)
    q2, s2 = f(x)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    d = jax.jit(_dequant)
    np.testing.assert_array_equal(np.asarray(d(q1, s1)),
                                  np.asarray(d(q2, s2)))


def test_ef_residual_roundtrip_padded_tail():
    """G=1030 floats with chunk=512 pads 2x512-1030=… a 1018-element
    tail; the residual must be the error-feedback term of the ORIGINAL
    (unpadded) slice, reshaped to the gradient's shape."""
    from jax.sharding import PartitionSpec as P
    qsr = QuantizedScatterReduce(chunk=512)
    mesh = make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.RandomState(2).randn(2, 515), jnp.float32)

    def body(g):
        out, resid, info = qsr.sync([g], [jnp.zeros_like(g)], "data")
        return out[0], resid[0]

    out, resid = jax.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)(x)
    assert out.shape == x.shape and resid.shape == x.shape
    # the residual is exactly acc - dequant(quant(acc)) on the unpadded
    # slice (the padded tail quantizes but never feeds back)
    flat = jnp.pad(x.reshape(-1), (0, (-x.size) % 512))
    q, s = _quant(flat.reshape(1, -1, 512))
    want = (flat - _dequant(q, s).reshape(-1))[:x.size].reshape(x.shape)
    np.testing.assert_array_equal(np.asarray(resid), np.asarray(want))
    # W=1 round trip: output = double-quantized input, error bounded by
    # two quantization steps
    step = float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               atol=2 * step + 1e-6)
    # error feedback conserves the signal: out + resid ~ x at the same
    # tolerance
    np.testing.assert_allclose(np.asarray(out + resid), np.asarray(x),
                               atol=2 * step + 1e-6)


def test_comm_bytes_matches_compiled_entry_io():
    """The analytic wire-byte formula vs the compiler: the ENTRY result
    bytes of the quantization stage (the exact payload the all_to_all
    ships) must equal G/4 * (1 + 4/chunk) — the factor comm_bytes and
    archs.COMPRESSION_SCHEMES['int8'] both charge."""
    from repro.costmodel.hlo_analysis import entry_io_bytes
    W, chunk, n = 4, 512, 4 * 512 * 8            # divides evenly
    x = jnp.asarray(np.random.RandomState(3).randn(n), jnp.float32)

    def quant_stage(flat):
        rows = flat.reshape(W, -1, chunk)
        return _quant(rows)

    hlo = jax.jit(quant_stage).lower(x).compile().as_text()
    _, result_bytes = entry_io_bytes(hlo)
    G = n * 4
    want_payload = G / 4 * (1 + 4.0 / chunk)
    assert result_bytes == want_payload
    # and the strategy's end-to-end formula is 2 phases x (W-1)/W of it
    qsr = QuantizedScatterReduce(chunk=chunk)
    assert qsr.comm_bytes([x], W) == int(2 * want_payload * (W - 1) / W)
    # which is exactly what the analytic int8 scheme bills per byte
    from repro.serverless.archs import COMPRESSION_SCHEMES
    assert COMPRESSION_SCHEMES["int8"](0.3) == want_payload / G


def test_mlless_converges_with_compression():
    """PR 5's converges-under-attack pattern, compression edition: real
    training with the significance-filtered strategy (the arch
    spirt_sf's jax_strategy) must still reduce the loss."""
    from repro.serverless.archs import get_arch
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    r = np.random.RandomState(0)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (8, 32)).astype(
        np.int32)}
    batch["labels"] = batch["tokens"]
    strategy = get_arch("spirt_sf").make_strategy(use_kernel=False)
    ts = build_train_step(model, optim.sgd(0.1), strategy, mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(6):
        state, metrics = ts.step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        assert 0.0 < float(metrics["significant_fraction"]) <= 1.0
    assert losses[-1] < losses[0]


def test_quantized_converges_with_compression():
    """Same row for the int8 path (async_spirt_q8's jax_strategy)."""
    from repro.serverless.archs import get_arch
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    r = np.random.RandomState(0)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (8, 32)).astype(
        np.int32)}
    batch["labels"] = batch["tokens"]
    ts = build_train_step(model, optim.sgd(0.1),
                          get_arch("async_spirt_q8").make_strategy(),
                          mesh)
    state = ts.init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(6):
        state, metrics = ts.step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
