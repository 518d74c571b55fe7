"""Multi-device tests run in subprocesses (the main pytest process must
keep the default 1-device backend — see conftest)."""
import subprocess
import sys
import textwrap

import pytest

from repro.launch._subprocess import child_env


def _run(code, devices=8, timeout=560):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=child_env(devices))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_flash_decode_sharded():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.flash_decode import flash_decode_attention
    from repro.core.sharding import make_mesh
    from repro.models.attention import decode_attention
    mesh = make_mesh((4,), ("data",))
    B, L, KV, G, hd = 2, 64, 2, 3, 32
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, 1, KV*G, hd), jnp.float32)
    k = jnp.asarray(rs.randn(B, L, KV, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, L, KV, hd), jnp.float32)
    for window, pos in ((None, L-1), (48, L+7)):
        expect = decode_attention(q, k, v, jnp.asarray(pos), window=window)
        fn = jax.shard_map(
            lambda q_, k_, v_: flash_decode_attention(
                q_, k_, v_, jnp.asarray(pos), axis_name="data",
                total_len=L, window=window),
            mesh=mesh, in_specs=(P(), P(None, "data"), P(None, "data")),
            out_specs=P(), check_vma=False, axis_names={"data"})
        got = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   atol=2e-5)
    print("OK")
    """)


def test_strategies_agree_across_real_data_shards():
    """4-way data parallel: allreduce == scatterreduce == PS, and dp
    sharding equals single-device training."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.core import build_train_step, get_strategy
    from repro.core.sharding import make_mesh
    from repro import optim
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    r = np.random.RandomState(0)
    batch = {"tokens": r.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    batch["labels"] = batch["tokens"]
    sums = {}
    for name in ("allreduce", "scatterreduce", "parameter_server",
                 "quantized_scatterreduce"):
        ts = build_train_step(model, optim.sgd(0.1), get_strategy(name),
                              mesh, data_axes=("data",))
        state = ts.init_state(jax.random.PRNGKey(0))
        b = {k: jax.device_put(v, ts.batch_shardings[k])
             for k, v in batch.items()}
        for _ in range(2):
            state, m = ts.step_fn(state, b)
        sums[name] = sum(float(jnp.sum(l.astype(jnp.float32)))
                         for l in jax.tree.leaves(state["params"]))
    assert abs(sums["allreduce"] - sums["scatterreduce"]) < 1e-4
    assert abs(sums["allreduce"] - sums["parameter_server"]) < 1e-4
    assert abs(sums["allreduce"] - sums["quantized_scatterreduce"]) < 0.5
    print("OK", sums)
    """)


def test_quantized_scatterreduce_tuple_axis_parity():
    """QuantizedScatterReduce on a REAL 4-device fleet, string axis vs
    tuple-of-axes (2x2 mesh): both must agree with the fp32 ring mean
    to quantization tolerance, and with each other bitwise — W (the
    scatter row count) and the collectives' device ordering come from
    the same normalized axes, so a 2-axis data mesh cannot reassemble
    chunks permuted."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.compression import QuantizedScatterReduce

    g = jnp.asarray(np.random.RandomState(0).randn(4, 1030),
                    jnp.float32)
    qsr = QuantizedScatterReduce(chunk=64)

    def run(mesh, axes, spec):
        def body(x):
            out, resid, _ = qsr.sync([x[0]], [jnp.zeros_like(x[0])],
                                     axes)
            return out[0]
        f = jax.shard_map(body, mesh=mesh, in_specs=P(spec),
                          out_specs=P(), check_vma=False)
        return np.asarray(f(g))

    flat = run(Mesh(np.array(jax.devices()), ("data",)), "data", "data")
    grid = run(Mesh(np.array(jax.devices()).reshape(2, 2), ("a", "b")),
               ("a", "b"), ("a", "b"))
    want = np.asarray(jnp.mean(g, axis=0))
    # fp32 ring baseline within two quantization steps
    step = float(np.abs(np.asarray(g)).max()) / 127.0
    np.testing.assert_allclose(flat, want, atol=2 * step)
    np.testing.assert_allclose(grid, want, atol=2 * step)
    # same normalized layout -> bitwise identical across mesh shapes
    np.testing.assert_array_equal(flat, grid)
    print("OK")
    """, devices=4)


def test_quantized_scatterreduce_rejects_empty_axes():
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.compression import QuantizedScatterReduce
    try:
        QuantizedScatterReduce().sync([jnp.ones(8)], [jnp.zeros(8)], ())
    except ValueError as e:
        assert "at least one mesh axis" in str(e)
        print("OK")
    else:
        raise SystemExit("expected ValueError")
    """, devices=1)


@pytest.mark.slow
def test_dryrun_one_combo_small():
    """End-to-end dry-run driver on the real 512-device production mesh
    for the cheapest (arch, shape) pair."""
    out = _run("""
    from repro.launch import dryrun
    r = dryrun.dryrun_one("smollm-135m", "long_500k", save=False)
    assert r["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert r["memory"]["peak_estimate_gb"] < 16.0
    print("OK", r["roofline"]["dominant"])
    """, devices=512)
    assert "OK" in out
