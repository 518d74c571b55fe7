"""Adversarial robustness lab: attack-model registry contract, numpy/
JAX aggregator parity, breakdown-point properties (every robust
aggregator stays near the honest mean under <= f adversarial rows for
EVERY registered attack, while plain averaging violates the same
bound), error paths, and seeded-determinism regressions for the
byzantine-fraction sweep and the real-training harness."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_strategy
from repro.serverless import adversarial as adv
from repro.serverless.recovery import (GeometricMedian, Krum, TrimmedMean,
                                       coordinate_median,
                                       geometric_median, krum,
                                       trimmed_mean_sort)
from repro.serverless.sweep import (AdversarialGrid, adversarial_curve,
                                    adversarial_sweep)

ROBUST = ("trimmed_mean", "coordinate_median", "krum",
          "geometric_median")
# magnitudes the property tests drive each attack at: large enough that
# an unfiltered mean is dragged far outside the honest cluster
# (sign_flip and zero carry their own fixed displacement)
ATTACK_TEST_SCALE = {"scale": -1e4, "gaussian_noise": 1e4,
                     "little_is_enough": 1e4, "sign_flip": 1.0,
                     "zero": 1.0}


# ---------------------------------------------------------------------------
# Attack-model registry contract (mirrors the ArchSpec registry's)
# ---------------------------------------------------------------------------
def test_registry_lists_the_paper_attacks():
    names = adv.list_attacks()
    for expected in ("sign_flip", "scale", "gaussian_noise",
                     "little_is_enough", "zero"):
        assert expected in names, names
    lie = adv.get_attack("little_is_enough")
    assert lie.colluding and lie.default_scale == 1.5


def test_registry_unknown_name_is_actionable():
    with pytest.raises(ValueError, match="little_is_enough"):
        adv.get_attack("nope")
    with pytest.raises(ValueError, match="registered"):
        adv.get_attack("")


def test_registry_register_round_trip_and_duplicates():
    spec = adv.AttackSpec(name="test_attack",
                          apply_rows=lambda s, b, r, k: s,
                          jax_apply=lambda g, b, a, k, s: g)
    try:
        assert adv.register_attack(spec) is spec
        assert adv.get_attack("test_attack") is spec
        with pytest.raises(ValueError, match="already registered"):
            adv.register_attack(spec)
        adv.register_attack(spec, overwrite=True)     # explicit is fine
    finally:
        adv.unregister_attack("test_attack")
    assert "test_attack" not in adv.list_attacks()


def test_attack_specs_are_frozen():
    with pytest.raises(dataclasses.FrozenInstanceError):
        adv.get_attack("scale").default_scale = 0.0


def test_attacks_leave_honest_rows_bit_identical():
    rng = np.random.default_rng(0)
    stacked = rng.standard_normal((4, 9, 6))
    mask = np.arange(9) < np.array([0, 2, 3, 4])[:, None]
    for name in adv.list_attacks():
        out = adv.get_attack(name).rows(stacked, mask,
                                        np.random.default_rng(1))
        assert out.shape == stacked.shape
        honest = ~mask[..., None] & np.ones_like(stacked, bool)
        assert (out[honest] == stacked[honest]).all(), name
        assert np.array_equal(out[0], stacked[0]), name  # no byz row


# ---------------------------------------------------------------------------
# numpy twins agree with the JAX statistics (the sweep measures what
# real training applies)
# ---------------------------------------------------------------------------
def test_np_trimmed_mean_matches_jax_reference():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((9, 17))
    for f in (1, 2, 4):
        np.testing.assert_allclose(
            adv.np_trimmed_mean(x, f),
            np.asarray(trimmed_mean_sort(jnp.asarray(x), f)),
            rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        adv.np_coordinate_median(x),
        np.asarray(coordinate_median(jnp.asarray(x))), rtol=1e-6)


def test_np_krum_matches_jax():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((11, 4, 3)).astype(np.float32)
    for f, m in ((0, 1), (1, 1), (2, 3), (4, 2)):
        np.testing.assert_allclose(
            adv.np_krum(x.reshape(11, -1), f, m=m).reshape(4, 3),
            np.asarray(krum(jnp.asarray(x), f=f, m=m)),
            rtol=1e-5, atol=1e-5)


def test_np_geometric_median_matches_jax():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((9, 5)).astype(np.float32)
    x[0] *= 200.0                       # one far outlier
    np.testing.assert_allclose(
        adv.np_geometric_median(x, tol=1e-10, max_iter=500),
        np.asarray(geometric_median(jnp.asarray(x), tol=1e-7,
                                    max_iter=500)),
        rtol=1e-4, atol=1e-4)
    # symmetric configuration -> the exact center
    pts = np.array([[1., 0], [-1., 0], [0, 1.], [0, -1.]])
    np.testing.assert_allclose(adv.np_geometric_median(pts),
                               [0.0, 0.0], atol=1e-6)


def test_batched_aggregators_match_per_row_loop():
    """The fraction-axis vectorization (per-row f budgets) must agree
    with scalar calls row by row."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((4, 11, 6))
    f = np.array([0, 1, 2, 4])
    for name in ("trimmed_mean", "krum"):
        fn = adv.SIM_AGGREGATORS[name]
        batched = fn(x, f)
        for i in range(len(f)):
            np.testing.assert_allclose(batched[i], fn(x[i], int(f[i])),
                                       rtol=1e-9, err_msg=name)


# ---------------------------------------------------------------------------
# Breakdown-point property: <= f adversaries never drag a robust
# aggregate far from the honest mean; plain averaging always is
# ---------------------------------------------------------------------------
def _breakdown_case(agg_name, attack_name, W, n_byz, D, seed):
    """Returns (robust_err, plain_err, bound) for one drawn fleet."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal(D)
    base *= 200.0 / max(np.linalg.norm(base), 1e-12)
    rows = base + 0.02 * rng.standard_normal((W, D))
    mask = np.arange(W) < n_byz
    spec = adv.get_attack(attack_name)
    stacked = spec.apply_rows(rows, mask, np.random.default_rng(seed + 1),
                              ATTACK_TEST_SCALE[attack_name])
    mu = rows[n_byz:].mean(axis=0)      # the honest workers' mean
    spread = np.linalg.norm(rows[n_byz:] - mu, axis=-1).max()
    f = max(n_byz, 1)
    bound = 6.0 * (spread + 1e-3) * (np.sqrt(W) + W / (W - 2 * f))
    est = adv.SIM_AGGREGATORS[agg_name](stacked, f)
    return (float(np.linalg.norm(est - mu)),
            float(np.linalg.norm(stacked.mean(axis=0) - mu)), bound)


def _check_breakdown(agg_name, attack_name, W, n_byz, D, seed):
    assert W >= 2 * max(n_byz, 1) + 3   # krum's strictest feasibility
    err, plain_err, bound = _breakdown_case(agg_name, attack_name, W,
                                            n_byz, D, seed)
    assert err <= bound, (
        f"{agg_name} left the honest cluster under {attack_name}: "
        f"err={err:.3g} > bound={bound:.3g} "
        f"(W={W}, n_byz={n_byz}, D={D}, seed={seed})")
    if n_byz > 0:
        assert plain_err > bound, (
            f"plain mean survived {attack_name} (W={W}, n_byz={n_byz}, "
            f"seed={seed}): err={plain_err:.3g} <= bound={bound:.3g}")


BREAKDOWN_CASES = [(7, 0), (7, 2), (9, 3), (13, 5)]


@pytest.mark.parametrize("attack",
                         ["sign_flip", "scale", "gaussian_noise",
                          "little_is_enough", "zero"])
@pytest.mark.parametrize("agg", ROBUST)
def test_breakdown_point_fixed_cases(agg, attack):
    for W, n_byz in BREAKDOWN_CASES:
        for seed in (0, 1, 2):
            _check_breakdown(agg, attack, W, n_byz, D=12, seed=seed)


def test_breakdown_point_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=60, deadline=None)
    @hyp.given(W=st.integers(5, 13), frac=st.floats(0.0, 1.0),
               D=st.integers(2, 16), seed=st.integers(0, 2 ** 31))
    def run(W, frac, D, seed):
        n_byz = int(round(frac * ((W - 3) // 2)))
        for agg in ROBUST:
            for attack in adv.list_attacks():
                _check_breakdown(agg, attack, W, n_byz, D, seed)

    run()


# ---------------------------------------------------------------------------
# Error paths (mirror get_arch's actionable-error style)
# ---------------------------------------------------------------------------
def test_trimmed_mean_width_validation():
    with pytest.raises(ValueError, match="W > 2"):
        adv.np_trimmed_mean(np.ones((4, 3)), 2)
    with pytest.raises(ValueError):     # 2*trim >= n_workers, jax side
        TrimmedMean(trim=2)._reduce(jnp.ones((4, 3)))


def test_krum_validation():
    # f too large names the largest feasible budget
    with pytest.raises(ValueError, match="max feasible f is 1"):
        krum(jnp.ones((5, 2)), f=2)
    with pytest.raises(ValueError, match="max feasible f"):
        adv.np_krum(np.ones((5, 2)), 2)
    with pytest.raises(ValueError, match="f >= 0"):
        adv.np_krum(np.ones((5, 2)), -1)
    with pytest.raises(ValueError, match="1 <= m <= W"):
        krum(jnp.ones((5, 2)), f=0, m=9)
    with pytest.raises(ValueError):
        Krum(f=-1)
    with pytest.raises(ValueError):
        Krum(m=0)
    with pytest.raises(ValueError):     # strategy reduce, fleet too small
        Krum(f=1)._reduce(jnp.ones((4, 3)))


def test_geometric_median_validation():
    for kw in (dict(tol=0.0), dict(max_iter=0), dict(tol=-1.0)):
        with pytest.raises(ValueError):
            GeometricMedian(**kw)
        with pytest.raises(ValueError):
            geometric_median(jnp.ones((4, 2)), **kw)
        with pytest.raises(ValueError):
            adv.np_geometric_median(np.ones((4, 2)), **kw)


def test_get_strategy_byzantine_unknown_attack_lists_registry():
    tm = get_strategy("trimmed_mean", trim=1)
    with pytest.raises(ValueError) as ei:
        get_strategy("byzantine", inner=tm, attack="nope")
    for name in adv.list_attacks():
        assert name in str(ei.value)


def test_get_strategy_wires_new_aggregators():
    k = get_strategy("krum", f=1, m=2, microbatches=4)
    assert (k.name, k.f, k.m, k.microbatches) == ("krum", 1, 2, 4)
    g = get_strategy("geometric_median", tol=1e-5)
    assert g.name == "geometric_median" and g.tol == 1e-5
    byz = get_strategy("byzantine", inner=k, attack="little_is_enough")
    assert byz.microbatches == 4        # rides the inner accumulation
    assert byz.scale == 1.5             # the attack's own default


def test_byzantine_gradients_post_init_validation():
    tm = get_strategy("trimmed_mean", trim=1)
    # valid: fraction exactly at the (W-1)/2W cap
    ok = get_strategy("byzantine", inner=tm, workers=(0, 2), n_workers=5)
    assert ok.workers == (0, 2) and ok.scale == -10.0
    cases = [
        (dict(workers=()), "non-empty"),
        (dict(workers=(0, 0)), "distinct"),
        (dict(workers=(-1,)), "distinct non-negative"),
        (dict(workers=(0,), n_workers=0), "n_workers"),
        (dict(workers=(4,), n_workers=4), "out of range"),
        (dict(workers=(0, 1), n_workers=4), "majority"),
        (dict(workers=(0, 1, 2), n_workers=5), "majority"),
        (dict(attack="bogus"), "registered"),
        (dict(scale=float("inf")), "finite"),
        (dict(scale=float("nan")), "finite"),
    ]
    for kw, match in cases:
        with pytest.raises(ValueError, match=match):
            get_strategy("byzantine", inner=tm, **kw)


def test_sim_helpers_validation():
    with pytest.raises(ValueError, match="registered"):
        adv.sim_aggregator_max_f("nope", 8)
    with pytest.raises(ValueError, match="n_workers"):
        adv.byzantine_fractions(2)
    with pytest.raises(ValueError, match="n_workers"):
        AdversarialGrid(n_workers=2)
    with pytest.raises(ValueError, match="steps"):
        AdversarialGrid(steps=0)
    with pytest.raises(ValueError, match="lr"):
        AdversarialGrid(lr=0.0)
    with pytest.raises(ValueError, match="aggregatable range"):
        adversarial_sweep(AdversarialGrid(fractions=(0.0, 0.6)))
    with pytest.raises(ValueError, match="registered"):
        AdversarialGrid(aggregators=("trimmed-mean",))  # typo'd name
    with pytest.raises(ValueError, match="unknown attack"):
        adversarial_sweep(AdversarialGrid(
            attack_scales=(("bogus", 2.0),)))
    with pytest.raises(ValueError, match="no cells"):
        adversarial_curve([], "mean", "scale")


def test_arch_default_aggregator_validated_and_set():
    from repro.serverless import ArchSpec, get_arch
    for name in ("spirt", "hier_spirt", "spirt_s3"):
        assert get_arch(name).default_aggregator == "trimmed_mean"
    assert get_arch("allreduce").default_aggregator == "mean"
    assert get_arch("gpu").default_aggregator == "mean"
    with pytest.raises(ValueError, match="default_aggregator"):
        ArchSpec(name="x", round_terms=lambda **k: {},
                 default_aggregator="bogus")


# ---------------------------------------------------------------------------
# The fraction sweep: determinism + the degradation/floor contract
# ---------------------------------------------------------------------------
def _small_grid(**kw):
    base = dict(n_workers=8, steps=50)
    base.update(kw)
    return AdversarialGrid(**base)


def test_adversarial_sweep_bit_reproducible():
    grid = _small_grid()
    a = adversarial_sweep(grid, seed=11)
    b = adversarial_sweep(grid, seed=11)
    assert a == b                       # frozen cells, exact floats
    c = adversarial_sweep(grid, seed=12)
    assert a != c                       # the seed actually matters


def test_adversarial_sweep_reproducible_past_float_overflow():
    """A grid long enough to drive plain averaging clean through inf
    must still satisfy the same-seed equality contract (NaN floats
    would make identical sweeps compare unequal) and keep min_dist
    finite."""
    grid = _small_grid(steps=3000, attacks=("scale",),
                       aggregators=("mean",))
    a = adversarial_sweep(grid, seed=0)
    assert a == adversarial_sweep(grid, seed=0)
    assert any(c.final_dist == float("inf") and c.diverged for c in a)
    assert all(np.isfinite(c.min_dist) for c in a)


def test_adversarial_sweep_cells_invariant_to_grid_shape():
    """A cell is a pure function of its OWN (aggregator, attack,
    fraction) coordinates and the seed: shrinking the grid elsewhere —
    fewer attacks, fewer aggregators — must reproduce the surviving
    cells bit-identically (the attack noise stream is keyed by attack
    name, not grid position)."""
    full = adversarial_sweep(_small_grid(), seed=5)
    sub = adversarial_sweep(
        _small_grid(attacks=("gaussian_noise",),
                    aggregators=("mean", "krum")), seed=5)
    want = [c for c in full if c.attack == "gaussian_noise"
            and c.aggregator in ("mean", "krum")]
    assert sub == want


def test_adversarial_sweep_fraction_zero_is_attack_free():
    """With nobody byzantine every attack column is identical — the
    corruption machinery must be a no-op at fraction 0."""
    cells = adversarial_sweep(_small_grid(), seed=3)
    for agg in ("mean",) + ROBUST:
        per_attack = {c.attack: c.final_dist for c in cells
                      if c.aggregator == agg and c.n_byz == 0}
        assert len(set(per_attack.values())) == 1, (agg, per_attack)


def test_mean_degrades_monotonically_robust_holds_floor():
    """Tier-1 version of the benchmark's acceptance assertion."""
    grid = _small_grid()
    cells = adversarial_sweep(grid, seed=0)
    floor = 2 * grid.converge_tol
    for attack in adv.list_attacks():
        _, cs = adversarial_curve(cells, "mean", attack,
                                  "converged_step")
        cs = np.where(cs < 0, grid.steps + 1, cs)
        assert all(b >= a for a, b in zip(cs, cs[1:])), (attack, cs)
        for agg in ROBUST:
            cap = adv.sim_aggregator_max_f(agg, grid.n_workers)
            held = [c for c in cells
                    if c.aggregator == agg and c.attack == attack
                    and c.n_byz <= cap]
            assert held and all(c.final_dist <= floor
                                and not c.diverged for c in held), (
                agg, attack, [(c.fraction, c.final_dist) for c in held])
    # the strong attack's contrast: mean diverges, every robust holds
    _, mean_d = adversarial_curve(cells, "mean", "scale")
    assert mean_d[-1] > 10 * grid.init_dist
    for agg in ROBUST:
        _, rob_d = adversarial_curve(cells, agg, "scale")
        assert mean_d[-1] > 100 * rob_d[-1], (agg, rob_d)


def test_oracle_budget_is_capped_at_breakdown():
    cells = adversarial_sweep(_small_grid(), seed=0)
    for c in cells:
        cap = adv.sim_aggregator_max_f(c.aggregator, 8)
        assert c.f_used == min(c.n_byz, cap), c


def test_jax_gaussian_noise_is_fresh_per_step():
    """The JAX gaussian attack must redraw noise every sync step (the
    numpy twin does) — a step-independent key would freeze one draw
    into a constant-bias attack.  ByzantineGradients threads the step
    counter through its strategy state."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core.sharding import make_mesh
    spec = adv.get_attack("gaussian_noise")
    mesh = make_mesh((1,), ("data",))
    g = {"a": jnp.ones((1, 4), jnp.float32)}
    specs = jax.tree.map(lambda _: P("data"), g)

    def corrupt(step):
        f = jax.shard_map(
            lambda x: spec.jax_apply(x, jnp.asarray(True), "data", 5.0,
                                     7, jnp.asarray(step))["a"],
            mesh=mesh, in_specs=(specs,), out_specs=P("data"),
            check_vma=False)
        return np.asarray(f(g))

    s0, s0b, s1 = corrupt(0), corrupt(0), corrupt(1)
    np.testing.assert_array_equal(s0, s0b)      # same step: replayable
    assert not np.array_equal(s0, s1)           # new step: fresh noise
    assert not np.array_equal(s0, np.ones((1, 4)))  # actually corrupts
    # the wrapper's state carries (step counter, inner state)
    byz = get_strategy("byzantine", inner=get_strategy("allreduce"),
                       attack="gaussian_noise")
    step0, inner0 = byz.init_state(g)
    assert int(step0) == 0 and inner0 == ()


# ---------------------------------------------------------------------------
# Real-training regressions (subprocess: own XLA device count)
# ---------------------------------------------------------------------------
def _run_subprocess_code(code, timeout=560):
    import subprocess
    import sys

    from repro.launch._subprocess import child_env
    out = subprocess.run([sys.executable, "-c", code], env=child_env(4),
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout, out.stdout[-2000:]


def test_krum_and_geometric_median_sync_match_numpy_twins():
    """Under a real 4-device shard_map the flat-buffer sync must apply
    the SAME statistic the simulated sweep uses: reconstruct each
    worker's flattened gradient on the host, reduce with the numpy
    twin, and demand agreement.  (Unlike the coordinate-wise trimmed
    mean / median, Krum and the geometric median are JOINT rules over
    the whole gradient — per-leaf application is a different statistic,
    so sync_per_leaf is deliberately not the reference here.)"""
    import textwrap
    _run_subprocess_code(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.serverless.adversarial import (np_geometric_median,
                                                  np_krum)
        from repro.core.sharding import make_mesh
        from repro.serverless.recovery import GeometricMedian, Krum
        mesh = make_mesh((4,), ("data",))
        r = np.random.RandomState(0)
        grads = {"a": jnp.asarray(r.randn(4, 8, 3), jnp.float32),
                 "b": jnp.asarray(r.randn(4, 5), jnp.float32)}
        specs = jax.tree.map(lambda g: P("data"), grads)
        # each worker's whole flattened gradient, [W, N] on the host
        stack = np.stack([np.concatenate(
            [np.asarray(grads[k][w]).ravel() for k in grads])
            for w in range(4)])
        for strat, ref in (
                (Krum(f=0), lambda s: np_krum(s, 0)),
                (Krum(f=0, m=2), lambda s: np_krum(s, 0, m=2)),
                (GeometricMedian(tol=1e-7, max_iter=300),
                 lambda s: np_geometric_median(s, tol=1e-10,
                                               max_iter=600))):
            f = jax.shard_map(lambda g: strat.sync(g, (), "data")[0],
                              mesh=mesh, in_specs=(specs,),
                              out_specs=specs, check_vma=False)
            out = f(grads)
            want = ref(stack)
            got = np.concatenate([np.asarray(out[k][0]).ravel()
                                  for k in out])
            for k in grads:
                assert out[k].dtype == grads[k].dtype
                assert out[k].shape == grads[k].shape
                # every worker receives the same aggregate
                np.testing.assert_array_equal(np.asarray(out[k][0]),
                                              np.asarray(out[k][1]))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        print("OK")
    """))


def test_byzantine_train_seeded_determinism():
    """Same seed -> bit-identical loss trace across two in-process runs
    of the refactored harness (and a different seed diverges)."""
    import textwrap
    _run_subprocess_code(textwrap.dedent("""
        from repro.launch.byzantine_train import run
        kw = dict(attack="sign_flip", steps=6, batch=32, data_size=256,
                  eval_size=64, seed=3)
        a = run("trimmed_mean", **kw)
        b = run("trimmed_mean", **kw)
        assert a["losses"] == b["losses"], (a["losses"], b["losses"])
        assert a["acc"] == b["acc"]
        c = run("trimmed_mean", **dict(kw, seed=4))
        assert c["losses"] != a["losses"]
        print("OK")
    """))
