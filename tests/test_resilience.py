"""Resilience harness tests: fault schedules, the in-DB store,
survivor re-meshing, checkpoint regressions and the end-to-end chaos
runs (bit-exact restore / no-replay takeover) in 4-device subprocesses.
"""
import numpy as np
import pytest

from repro.resilience import FaultSchedule, InMemoryStore
from repro.serverless.faults import FaultPlan, WorkerCrash

# NOTE: the chaos subprocess tests use a (W, 1) mesh — the auto 'model'
# axis is width 1, so the partial-manual SPMD crash that gates
# test_multidevice's wide-model-axis tests does not apply (same reason
# test_adversarial's byzantine_train subprocesses run ungated).


# ---------------------------------------------------------------------------
# FaultSchedule
# ---------------------------------------------------------------------------
def test_schedule_sorts_and_queries():
    s = FaultSchedule(kills=((7, 2), (3, 0)))
    assert s.kills == ((3, 0), (7, 2))
    assert s.kill_at(3) == 0 and s.kill_at(7) == 2
    assert s.kill_at(5) is None
    assert s.n_kills == 2
    assert FaultSchedule.single(4, worker=1).kills == ((4, 1),)


def test_schedule_rejects_bad_entries():
    with pytest.raises(ValueError, match="step must be >= 1"):
        FaultSchedule(kills=((0, 1),))
    with pytest.raises(ValueError, match="worker must be >= 0"):
        FaultSchedule(kills=((2, -1),))
    with pytest.raises(ValueError, match="one kill per step"):
        FaultSchedule(kills=((2, 0), (2, 1)))


def test_schedule_from_fault_plan_maps_and_clamps():
    plan = FaultPlan(crashes=(
        WorkerCrash(0, 0.0),      # clamps up to step 1
        WorkerCrash(1, 50.0),     # -> round(50/100 * 10) = 5
        WorkerCrash(2, 999.0),    # clamps down to step 9
        WorkerCrash(3, 51.0),     # also -> 5: dropped (occupied)
    ))
    s = FaultSchedule.from_fault_plan(plan, total_steps=10,
                                      horizon_s=100.0)
    assert s.kills == ((1, 0), (5, 1), (9, 2))


def test_schedule_from_fault_plan_validates():
    with pytest.raises(ValueError, match="total_steps"):
        FaultSchedule.from_fault_plan(FaultPlan(), total_steps=1,
                                      horizon_s=10.0)
    with pytest.raises(ValueError, match="horizon_s"):
        FaultSchedule.from_fault_plan(FaultPlan(), total_steps=4,
                                      horizon_s=0.0)


def test_schedule_from_fault_plan_properties():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=50, deadline=None)
    @hyp.given(
        times=st.lists(st.floats(min_value=0.0, max_value=200.0,
                                 allow_nan=False), max_size=8),
        total_steps=st.integers(min_value=2, max_value=40),
        horizon=st.floats(min_value=1.0, max_value=150.0))
    def check(times, total_steps, horizon):
        plan = FaultPlan(crashes=tuple(
            WorkerCrash(i % 4, t) for i, t in enumerate(times)))
        s = FaultSchedule.from_fault_plan(plan, total_steps=total_steps,
                                          horizon_s=horizon)
        steps = [k for k, _ in s.kills]
        # every kill lands strictly inside the run, sorted and unique
        assert all(1 <= k <= total_steps - 1 for k in steps)
        assert steps == sorted(steps)
        assert len(set(steps)) == len(steps)
        assert s.n_kills <= len(times)
        # pure function of its inputs
        again = FaultSchedule.from_fault_plan(
            plan, total_steps=total_steps, horizon_s=horizon)
        assert again == s

    check()


# ---------------------------------------------------------------------------
# InMemoryStore
# ---------------------------------------------------------------------------
def test_store_accounting_and_missing_key():
    st = InMemoryStore()
    st.put("a", b"xyz")
    assert st.get("a") == b"xyz"
    assert (st.bytes_written, st.bytes_read) == (3, 3)
    assert (st.puts, st.gets) == (1, 1)
    assert "a" in st and "b" not in st
    with pytest.raises(KeyError, match="no key 'b'"):
        st.get("b")
    st.reset()
    assert st.keys() == [] and st.bytes_written == 0


def test_store_partition_roundtrip():
    st = InMemoryStore()
    blob = bytes(range(256)) * 5 + b"tail"   # not divisible by 4
    st.push_partitions(blob, 4)
    assert len(st.keys()) == 4
    rebuilt, dead_bytes = st.fetch_state(4, dead=2)
    assert rebuilt == blob
    assert dead_bytes == len(st.get("shard/2"))
    with pytest.raises(ValueError, match="out of range"):
        st.fetch_state(4, dead=4)
    with pytest.raises(ValueError, match="n_workers"):
        st.push_partitions(blob, 0)


# ---------------------------------------------------------------------------
# survivor_mesh (validation paths run on the default 1-device backend)
# ---------------------------------------------------------------------------
def test_survivor_mesh_validation():
    from repro.core.sharding import make_mesh, survivor_mesh
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="no axis 'pod'"):
        survivor_mesh(mesh, 0, data_axis="pod")
    with pytest.raises(ValueError, match="out of range"):
        survivor_mesh(mesh, 3)
    with pytest.raises(ValueError, match="no survivors"):
        survivor_mesh(mesh, 0)


# ---------------------------------------------------------------------------
# checkpoint regressions (PR 7 satellites)
# ---------------------------------------------------------------------------
def test_checkpoint_treedef_mismatch_names_both(tmp_path):
    from repro import checkpoint
    p = str(tmp_path / "s.msgpack")
    checkpoint.save(p, {"a": np.zeros(2), "b": np.ones(3)})
    # same leaf count/shapes, different structure -> treedef error
    # must name both structures so the mismatch is debuggable
    with pytest.raises(ValueError) as ei:
        checkpoint.restore(p, like=[np.zeros(2), np.ones(3)])
    msg = str(ei.value)
    assert "stored" in msg and "like" in msg


def test_checkpoint_restored_leaves_are_writable(tmp_path):
    """np.frombuffer regression: restored numpy leaves must own
    writable memory (in-place optimizer updates, donation)."""
    from repro import checkpoint
    p = str(tmp_path / "s.msgpack")
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.zeros(4, dtype=np.int32)}
    checkpoint.save(p, tree)
    out = checkpoint.restore(
        p, like={"w": np.zeros((2, 3), np.float32),
                 "b": np.zeros(4, np.int32)})
    for leaf in (out["w"], out["b"]):
        assert isinstance(leaf, np.ndarray)
        assert leaf.flags.writeable
        leaf += 1                      # must not raise
    np.testing.assert_array_equal(out["w"], tree["w"] + 1)


def test_checkpoint_restore_to_jax_template_is_donatable(tmp_path):
    from repro import checkpoint
    import jax
    import jax.numpy as jnp
    p = str(tmp_path / "s.msgpack")
    checkpoint.save(p, {"w": np.full((4,), 2.0, np.float32)})
    out = checkpoint.restore(p, like={"w": jnp.zeros(4)})
    assert isinstance(out["w"], jax.Array)

    @jax.jit
    def bump(x):
        return x + 1

    donated = jax.jit(lambda x: x * 2, donate_argnums=0)(out["w"])
    np.testing.assert_array_equal(np.asarray(donated), 4.0)
    # the original buffer was donated -> restored arrays are owned,
    # not views of the serialized payload
    assert out["w"].is_deleted()
    del bump


# ---------------------------------------------------------------------------
# launch._subprocess helpers
# ---------------------------------------------------------------------------
def test_subprocess_env_and_result_parsing():
    from repro.launch import _subprocess
    env = _subprocess.child_env(6)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].endswith("device_count=6")
    assert env["PYTHONPATH"].startswith(_subprocess.src_root())
    with pytest.raises(ValueError, match="devices"):
        _subprocess.child_env(0)

    parsed = _subprocess.parse_result_line(
        "noise\nRESULT,inner=krum,acc=0.5,loss=1.25\n",
        numeric_except=("inner",))
    assert parsed == {"inner": "krum", "acc": 0.5, "loss": 1.25}
    with pytest.raises(RuntimeError, match="no RESULT line"):
        _subprocess.parse_result_line("it crashed\n")


# ---------------------------------------------------------------------------
# end-to-end chaos runs (4-device subprocesses)
# ---------------------------------------------------------------------------
_SMALL = dict(steps=5, kill_step=3, checkpoint_every=2, seq=8,
              n_workers=4, global_batch=12)


def _check_chaos_scenario(seed: int) -> None:
    """One killed-at-step-k scenario: restore must replay the
    uninterrupted same-seed loss trace bit-exactly; takeover must
    resume without replay on the survivor fleet within tolerance."""
    from repro.launch.resilient_train import run_in_subprocess
    out = run_in_subprocess(seed=seed, **_SMALL)
    runs = out["runs"]
    rest, take = runs["restore"], runs["takeover"]
    # restore: bit-exact vs the uninterrupted baseline, and the
    # replayed steps reproduced their pre-kill losses exactly
    assert rest["bitexact_vs_baseline"]
    assert rest["replay_exact"]
    assert rest["recoveries"][0]["replayed_steps"] == 1
    assert rest["n_workers_end"] == 4
    # takeover: no replay, shrunk fleet, converges within tolerance
    trec = take["recoveries"][0]
    assert trec["replayed_steps"] == 0
    assert trec["n_workers_after"] == 3
    assert take["n_workers_end"] == 3
    assert take["final_loss_gap"] < 0.5
    # takeover moves only the dead peer's partition (~1/W of the
    # full checkpoint the restore path reads back)
    assert trec["bytes_moved"] < rest["recoveries"][0]["bytes_moved"]


def test_killed_then_restored_replays_bitexact():
    """Acceptance: the canonical seed, always run (no hypothesis
    dependency — this is the criterion the PR stands on)."""
    _check_chaos_scenario(seed=0)


@pytest.mark.slow
def test_killed_then_restored_replays_bitexact_seeded():
    """Hypothesis-drawn seeds: bit-exactness is a property of the
    harness, not of one lucky seed.  (slow: one ~1min subprocess per
    example.)"""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=2, deadline=None)
    @hyp.given(seed=st.integers(min_value=1, max_value=7))
    def check(seed):
        _check_chaos_scenario(seed)

    check()


@pytest.mark.slow
def test_restore_onto_shrunk_survivor_mesh():
    """restore_reinvoke=False: the checkpoint written from the W-way
    mesh restores onto the (W-1)-way survivor mesh and training
    continues (sharded restore onto a different mesh)."""
    from repro.launch.resilient_train import run_in_subprocess
    out = run_in_subprocess(restore_reinvoke=False,
                            modes="baseline,restore", **_SMALL)
    runs = out["runs"]
    rest, base = runs["restore"], runs["baseline"]
    rec = rest["recoveries"][0]
    assert rec["n_workers_after"] == 3
    assert rest["n_workers_end"] == 3
    assert rec["replayed_steps"] == 1
    # pre-checkpoint prefix is untouched history; post-rollback losses
    # come from 3-way arithmetic, so no bit-claim -- but the run must
    # converge to the neighbourhood of the unfaulted baseline
    k = rec["ckpt_step"]
    assert rest["losses"][:k] == base["losses"][:k]
    assert abs(rest["final_loss"] - base["final_loss"]) < 0.5
