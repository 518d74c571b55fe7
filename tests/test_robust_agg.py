"""Byzantine-robust aggregation: statistics, Strategy wiring, and a
real 4-way data-parallel training run under an active byzantine worker
(subprocess via repro.launch.byzantine_train — needs its own XLA
device-count flag, same pattern as test_multidevice)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_strategy
from repro.serverless.recovery import (coordinate_median, geometric_median,
                                       krum, trimmed_mean)

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def test_trimmed_mean_drops_outliers():
    rs = np.random.RandomState(0)
    honest = rs.randn(3, 64).astype(np.float32)
    evil = honest[0:1] * -50.0
    stacked = jnp.asarray(np.concatenate([evil, honest], axis=0))
    robust = np.asarray(trimmed_mean(stacked, trim=1))
    # the poisoned row never dominates: every coordinate stays inside
    # the honest span
    lo, hi = honest.min(axis=0), honest.max(axis=0)
    assert (robust >= lo - 1e-6).all() and (robust <= hi + 1e-6).all()
    # and the statistic tracks the honest mean far better than the
    # contaminated mean does
    contaminated = np.asarray(stacked).mean(axis=0)
    err_r = np.abs(robust - honest.mean(axis=0)).mean()
    err_c = np.abs(contaminated - honest.mean(axis=0)).mean()
    assert err_r < 0.2 * err_c


def test_trimmed_mean_validates_width():
    with pytest.raises(ValueError):
        trimmed_mean(jnp.ones((2, 4)), trim=1)


def test_trimmed_mean_fast_path_matches_sort_reference():
    """trim=1 masks one min and one max entry and sums the middle
    values (O(W), no sort — and deliberately NOT the cancellation-prone
    (sum - min - max)/(W - 2) form); it must agree with the full-sort
    reference path on random stacks."""
    from repro.serverless.recovery import trimmed_mean_sort
    rs = np.random.RandomState(3)
    for W, shape in ((3, (16,)), (4, (8, 5)), (7, (4, 3, 2)), (16, (64,))):
        stacked = jnp.asarray(rs.randn(W, *shape).astype(np.float32)
                              * rs.choice([1.0, 50.0], size=(W,) + tuple(
                                  1 for _ in shape)))
        fast = np.asarray(trimmed_mean(stacked, trim=1))
        slow = np.asarray(trimmed_mean_sort(stacked, trim=1))
        np.testing.assert_allclose(fast, slow, rtol=1e-5, atol=1e-5)
    # trim > 1 still routes through the sort path
    stacked = jnp.asarray(rs.randn(7, 11).astype(np.float32))
    np.testing.assert_allclose(np.asarray(trimmed_mean(stacked, trim=2)),
                               np.asarray(trimmed_mean_sort(stacked, 2)),
                               rtol=1e-6)
    # all-equal coordinates (argmin == argmax) return the common value
    np.testing.assert_allclose(
        np.asarray(trimmed_mean(jnp.full((5, 3), 2.5), trim=1)),
        np.full(3, 2.5))


def test_trimmed_mean_fast_path_survives_huge_outliers():
    """The adversarial case the aggregator exists for: a byzantine
    worker shipping a 1e8-scaled gradient must not destroy the honest
    mean through fp32 cancellation (a naive (sum-min-max)/(W-2) returns
    0 here)."""
    from repro.serverless.recovery import trimmed_mean_sort
    honest = np.asarray([[1e-3], [2e-3], [3e-3], [4e-3]], np.float32)
    for evil in (1e8, -1e8, 3e7):
        stacked = jnp.asarray(np.concatenate(
            [honest, np.full((1, 1), evil, np.float32)]))
        fast = np.asarray(trimmed_mean(stacked, trim=1))
        slow = np.asarray(trimmed_mean_sort(stacked, trim=1))
        np.testing.assert_allclose(fast, slow, rtol=1e-6)
        # the outlier is fully masked: result stays in the honest span
        assert honest.min() <= fast[0] <= honest.max(), (evil, fast)


def test_flat_buffer_sync_matches_per_leaf_reference():
    """_RobustAggregate.sync flattens the gradient pytree into one
    contiguous fp32 buffer before the all-gather; under a real
    multi-device shard_map it must agree with the per-leaf reference
    path and round-trip shapes/dtypes."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.sharding import make_mesh
        from repro.serverless.recovery import TrimmedMean, CoordinateMedian
        mesh = make_mesh((4,), ("data",))
        r = np.random.RandomState(0)
        grads = {"a": jnp.asarray(r.randn(4, 8, 3), jnp.float32),
                 "b": jnp.asarray(r.randn(4, 5), jnp.bfloat16),
                 "c": jnp.asarray(r.randn(4, 1, 2, 2), jnp.float32)}
        specs = jax.tree.map(lambda g: P("data"), grads)
        for strat in (TrimmedMean(trim=1), CoordinateMedian()):
            f = jax.shard_map(lambda g: strat.sync(g, (), "data")[0],
                              mesh=mesh, in_specs=(specs,),
                              out_specs=specs, check_vma=False)
            fr = jax.shard_map(
                lambda g: strat.sync_per_leaf(g, (), "data")[0],
                mesh=mesh, in_specs=(specs,), out_specs=specs,
                check_vma=False)
            a, b = f(grads), fr(grads)
            for k in grads:
                assert a[k].dtype == grads[k].dtype
                assert a[k].shape == grads[k].shape
                np.testing.assert_allclose(
                    np.asarray(a[k], np.float32),
                    np.asarray(b[k], np.float32), rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    from repro.launch._subprocess import child_env
    out = subprocess.run([sys.executable, "-c", code], env=child_env(4),
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_coordinate_median_ignores_minority():
    stacked = jnp.asarray([[1.0, 2.0], [1.2, 2.2], [0.8, 1.8],
                           [1e6, -1e6]])
    med = np.asarray(coordinate_median(stacked))
    np.testing.assert_allclose(med, [1.1, 2.1], atol=0.2)


def test_get_strategy_wires_robust_and_byzantine():
    tm = get_strategy("trimmed_mean", trim=1, microbatches=4)
    assert tm.name == "trimmed_mean" and tm.microbatches == 4
    cm = get_strategy("coordinate_median")
    byz = get_strategy("byzantine", inner=tm, workers=(0,), scale=-8.0)
    assert byz.microbatches == 4            # rides SPIRT accumulation
    like = [jnp.ones((8, 8))]
    assert byz.comm_bytes(like, 4) == tm.comm_bytes(like, 4)
    assert cm.comm_bytes(like, 4) == 4 * 8 * 8 * 4
    with pytest.raises(ValueError):
        get_strategy("byzantine")           # inner is required
    with pytest.raises(ValueError):         # conflicting accumulation
        get_strategy("byzantine", inner=get_strategy("allreduce"),
                     microbatches=4)


def _stats_for(W):
    """Every (statistic, kwargs) applicable at fleet width W."""
    out = [(trimmed_mean, dict(trim=1)), (coordinate_median, {}),
           (geometric_median, dict(tol=1e-6, max_iter=60))]
    if W > 4:
        out.append((trimmed_mean, dict(trim=2)))
    if W >= 5:
        out.append((krum, dict(f=1, m=2)))
    return out


def test_use_pallas_paths_match_jnp_paths():
    """The kernel-backed reductions (use_pallas=True) must agree with
    the original jnp formulations — the paths golden snapshots and
    BENCH_adversarial.json pin — including under a scaled byzantine
    row and with a non-flat trailing shape."""
    rs = np.random.RandomState(7)
    for W, shape in ((5, (257,)), (8, (33, 5)), (12, (40,))):
        x = rs.randn(W, *shape).astype(np.float32)
        x[0] *= 1e4                     # adversarial scaled row
        stacked = jnp.asarray(x)
        for fn, kw in _stats_for(W):
            a = np.asarray(fn(stacked, **kw))
            b = np.asarray(fn(stacked, use_pallas=True, **kw))
            scale = np.abs(a).max() + 1e-12
            np.testing.assert_allclose(b, a, rtol=5e-5,
                                       atol=5e-5 * scale,
                                       err_msg=f"{fn.__name__} {kw}")


def test_use_pallas_matches_adversarial_numpy_twins():
    """Both recovery paths stay pinned to the vectorized numpy twins
    the adversarial sweep simulates with (SIM_AGGREGATORS)."""
    from repro.serverless import adversarial as adv
    rs = np.random.RandomState(11)
    x = rs.randn(9, 128).astype(np.float32)
    x[-1] = -40.0 * x[:-1].mean(axis=0)
    stacked = jnp.asarray(x)
    cases = [
        (trimmed_mean, dict(trim=2), adv.np_trimmed_mean, dict(f=2)),
        (coordinate_median, {}, adv.np_coordinate_median, {}),
        (krum, dict(f=2, m=3), adv.np_krum, dict(f=2, m=3)),
        (geometric_median, dict(tol=1e-7, max_iter=200),
         adv.np_geometric_median, dict(tol=1e-7, max_iter=200)),
    ]
    for fn, kw, np_fn, np_kw in cases:
        want = np_fn(x, **np_kw)
        for use_pallas in (False, True):
            got = np.asarray(fn(stacked, use_pallas=use_pallas, **kw))
            np.testing.assert_allclose(
                got, want, rtol=1e-4, atol=1e-4,
                err_msg=f"{fn.__name__} use_pallas={use_pallas}")


def test_krum_boundary_width_both_paths():
    """W = 2f + 3 is the tightest legal fleet; one fewer worker must
    raise on both paths."""
    rs = np.random.RandomState(5)
    for f in (1, 2):
        W = 2 * f + 3
        stacked = jnp.asarray(rs.randn(W, 64).astype(np.float32))
        a = np.asarray(krum(stacked, f=f))
        b = np.asarray(krum(stacked, f=f, use_pallas=True))
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5)
        for use_pallas in (False, True):
            with pytest.raises(ValueError):
                krum(stacked[:-1], f=f, use_pallas=use_pallas)


def test_robust_stats_nan_free_under_extremes():
    """Degenerate stacks the aggregators meet in practice — identical
    rows (zero Weiszfeld distances), an all-zero stack, and near-fp32-
    overflow magnitudes — must yield finite results on both paths."""
    ones = np.ones((5, 33), np.float32)
    extremes = [
        jnp.asarray(ones * 3.25),                      # identical rows
        jnp.asarray(np.zeros((5, 33), np.float32)),    # all-zero
        jnp.asarray(ones * np.asarray(
            [[1e15], [-1e15], [2.0], [3.0], [5.0]], np.float32)),
    ]
    for stacked in extremes:
        for fn, kw in _stats_for(5):
            for use_pallas in (False, True):
                out = np.asarray(fn(stacked, use_pallas=use_pallas,
                                    **kw))
                assert np.isfinite(out).all(), (fn.__name__, kw,
                                                use_pallas)


def test_strategy_use_pallas_wiring():
    """use_pallas threads through get_strategy into _reduce; None
    auto-detects (off on CPU) so golden paths stay bit-identical."""
    rs = np.random.RandomState(2)
    stacked = jnp.asarray(rs.randn(7, 90).astype(np.float32))
    for name, kw in (("trimmed_mean", dict(trim=1)),
                     ("coordinate_median", {}),
                     ("krum", dict(f=1, m=1)),
                     ("geometric_median", dict(tol=1e-6, max_iter=40))):
        auto = get_strategy(name, **kw)
        on = get_strategy(name, use_pallas=True, **kw)
        off = get_strategy(name, use_pallas=False, **kw)
        assert auto.use_pallas is None and not auto._kernels_enabled()
        assert on._kernels_enabled() and not off._kernels_enabled()
        a = np.asarray(off._reduce(stacked))
        b = np.asarray(on._reduce(stacked))
        np.testing.assert_allclose(b, a, rtol=5e-5, atol=5e-5)
        # auto on CPU takes the exact jnp path
        np.testing.assert_array_equal(np.asarray(auto._reduce(stacked)),
                                      a)


def test_pallas_twin_deterministic_sweep():
    """Deterministic stand-in for the hypothesis fuzz below (always
    runs): (W, D, trim, dtype) grid over both reduction paths."""
    rs = np.random.RandomState(13)
    for W, D in ((3, 1), (4, 17), (5, 129), (7, 128), (9, 150),
                 (11, 64)):
        x = rs.randn(W, D).astype(np.float32) * rs.choice(
            [1.0, 100.0], size=(W, 1))
        for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)):
            stacked = jnp.asarray(x, dtype)
            for trim in (1, 2, 3):
                if W <= 2 * trim:
                    continue
                a = np.asarray(trimmed_mean(stacked, trim=trim))
                b = np.asarray(trimmed_mean(stacked, trim=trim,
                                            use_pallas=True))
                scale = np.abs(a).max() + 1e-12
                np.testing.assert_allclose(
                    b, a, rtol=tol, atol=tol * scale,
                    err_msg=f"W={W} D={D} trim={trim} {dtype}")
            a = np.asarray(coordinate_median(stacked))
            b = np.asarray(coordinate_median(stacked, use_pallas=True))
            np.testing.assert_allclose(b, a, rtol=tol, atol=tol,
                                       err_msg=f"W={W} D={D} {dtype}")


if HAVE_HYPOTHESIS:
    @given(W=st.integers(3, 11), D=st.integers(1, 150),
           trim=st.integers(1, 3), seed=st.integers(0, 2**31 - 1),
           bf16=st.booleans())
    @settings(max_examples=40, deadline=None)
    def test_trimmed_mean_pallas_twin_fuzz(W, D, trim, seed, bf16):
        if W <= 2 * trim:
            return
        rs = np.random.RandomState(seed)
        x = rs.randn(W, D).astype(np.float32) * rs.choice(
            [1.0, 100.0], size=(W, 1))
        stacked = jnp.asarray(x, jnp.bfloat16 if bf16 else jnp.float32)
        a = np.asarray(trimmed_mean(stacked, trim=trim))
        b = np.asarray(trimmed_mean(stacked, trim=trim, use_pallas=True))
        tol = 3e-2 if bf16 else 1e-5
        scale = np.abs(a).max() + 1e-12
        np.testing.assert_allclose(b, a, rtol=tol, atol=tol * scale)

    @given(W=st.integers(2, 11), D=st.integers(1, 150),
           seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=40, deadline=None)
    def test_coordinate_median_pallas_twin_fuzz(W, D, seed):
        rs = np.random.RandomState(seed)
        stacked = jnp.asarray(rs.randn(W, D).astype(np.float32))
        a = np.asarray(coordinate_median(stacked))
        b = np.asarray(coordinate_median(stacked, use_pallas=True))
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)


def test_byzantine_training_converges_only_with_robust_agg():
    """SPIRT accumulation + trimmed mean trains through a -8x byzantine
    worker; plain allreduce under the same attack diverges.  Shares the
    harness with benchmarks/fault_tolerance.py (shorter runs here)."""
    from repro.launch.byzantine_train import run_in_subprocess
    robust = run_in_subprocess("trimmed_mean", steps=40, data_size=2048,
                               timeout=560)
    plain = run_in_subprocess("allreduce", steps=15, data_size=2048,
                              timeout=560)
    # robust: bounded + trending down (averaged tail below head)
    assert robust["max_loss"] < 4.0, robust
    assert robust["tail_loss"] < robust["head_loss"], robust
    # plain averaging under the same attack blows up
    assert plain["final_loss"] > 10.0 * robust["final_loss"], (plain,
                                                               robust)
