"""The split of the train step's device time by the program's name scopes:
the scopes reach every phase of the tiny LM step as lowered on the CPU,
change nothing but metadata, and on a small trace recorded on a TPU v5e
chip (the tiny LM cell's traced slice and its compiled step, as
``harness.run`` wrote them) the phases add up to the device's busy time."""
import contextlib
import gzip
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import hlo, scopes, spec, trace  # noqa: E402

def _lower_tiny_step() -> str:
    """The optimized HLO of the tiny LM cell's train step (allreduce,
    AdamW, remat over the layer scan), compiled for the CPU."""
    import jax
    from chipbench import system, traffic
    cell = spec.load_cell("tiny-lm-1", DATA)
    used = jax.devices()[:1]
    sysm = system.build(cell, used)
    assert sysm.model.remat
    shapes = system.param_shapes(sysm.model)
    params = system.make_params(shapes, cell.config, 1, used[0])
    state = sysm.ts.init_state(jax.random.PRNGKey(0), dtype_params=params)
    batch = jax.device_put(traffic.make_pool(cell.traffic, cell.config, 1)[0],
                           sysm.feed_shardings)
    return sysm.ts.step_fn.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def lowered():
    text = _lower_tiny_step()
    return text, scopes.op_names(text)


def test_each_phase_of_the_step_is_named(lowered):
    _, names = lowered
    found = {scopes.phase(n) for n in names.values()}
    assert {"forward", "recompute", "backward", "optimizer"} <= found
    assert scopes.is_scoped(names)


def test_attention_is_named_in_every_pass(lowered):
    _, names = lowered
    found = {scopes.phase(n) for n in names.values() if scopes.in_attention(n)}
    assert {"forward", "recompute", "backward"} <= found


def test_scopes_change_nothing_but_metadata(lowered, monkeypatch):
    import jax
    text, _ = lowered
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_tiny_step()
    assert not scopes.is_scoped(scopes.op_names(bare))
    assert scopes.code_only(bare) == scopes.code_only(text)


def test_fresh_compile_is_the_program_the_harness_compiles(lowered):
    """With the compile cache off, the step built from shapes alone is
    the step ``harness.setup`` compiles from arrays, with the scopes."""
    text, _ = lowered
    fresh = scopes.fresh_step_text(spec.load_cell("tiny-lm-1", DATA))
    assert scopes.code_only(fresh) == scopes.code_only(text)
    assert scopes.is_scoped(scopes.op_names(fresh))


def test_phase_of_a_name():
    assert scopes.scope_names("jit(f)/transpose(jvp(forward))/mul") == \
        ["f", "forward", "mul"]
    cases = {
        "jit(step_fn)/jvp(forward)/while/body/attention/dot_general":
            "forward",
        "jit(step_fn)/transpose(jvp(forward))/while/body/closed_call/"
        "checkpoint/rematted_computation/attention/exp": "recompute",
        "jit(step_fn)/transpose(jvp(forward))/checkpoint/attention/"
        "bqgks,bqkgh->bskh/dot_general": "backward",
        "jit(step_fn)/sync/psum": "sync",
        "jit(step_fn)/optimizer/fused_adamw/pallas_call": "optimizer",
        "jit(step_fn)/sin": "unscoped",
        "jit(step_fn)/jvp(forward_pass)/mul": "unscoped",
    }
    for name, want in cases.items():
        assert scopes.phase(name) == want, name
    assert scopes.in_attention("jit(f)/jvp(forward)/attention/exp")
    assert not scopes.in_attention("jit(f)/swa_attention/pallas_call")
    text = ('%body (p: f32[8]) -> f32[8] {\n'
            '  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)\n'
            '}\n\n'
            'ENTRY %main (a: f32[8]) -> f32[8] {\n'
            '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
            'metadata={op_name="jit(f)/optimizer/mul;jit(f)/sync/add" '
            'source_file="x.py" source_line=3}\n'
            '  %while.2 = f32[8]{0} while(%a), condition=%cond, body=%body, '
            'metadata={op_name="jit(f)/transpose(jvp(forward))/while"}\n'
            '  ROOT %copy.4 = f32[8]{0} copy(%while.2)\n'
            '}\n')
    assert scopes.op_names(text) == {
        "fusion.3": "jit(f)/optimizer/mul",
        "while.2": "jit(f)/transpose(jvp(forward))/while",
        "copy-done.1": "jit(f)/transpose(jvp(forward))/while"}


def test_split_of_a_made_up_window():
    """One device: a while loop whose body runs a forward op, a backward
    op and an op with no scope, the loop alone for the rest; then an
    optimizer op that the window cuts in half."""
    table = {"w": hlo.Instr("control"), "f": hlo.Instr("matmul", 1e9),
             "b": hlo.Instr("other"), "u": hlo.Instr("other"),
             "o": hlo.Instr("other")}
    names = {"w": "jit(s)/jvp(forward)/while",
             "f": "jit(s)/jvp(forward)/attention/dot_general",
             "b": "jit(s)/transpose(jvp(forward))/mul", "u": "jit(s)/add",
             "o": "jit(s)/optimizer/mul"}
    ops = {0: [("w", 0.0, 6.0), ("f", 0.0, 2.0), ("b", 2.0, 3.0),
               ("u", 3.5, 4.0), ("o", 7.0, 9.0)]}
    spans = [("window", 0.0, 8.0)]
    s = scopes.split(ops, spans, table, names, steps=2)
    assert s.busy_s == pytest.approx(7.0 / 2)
    assert s.phase_s == pytest.approx({
        "forward": 1.0, "recompute": 0.0, "backward": 0.5, "sync": 0.0,
        "optimizer": 0.5, "unscoped": (0.5 + 2.5) / 2})
    assert s.attention_s == pytest.approx(1.0)
    assert s.unscoped_pct == pytest.approx(100 * 1.5 / 3.5)


def _unpack(tmp_path, stem):
    d = tmp_path / stem
    d.mkdir()
    (d / f"{stem}.xplane.pb").write_bytes(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    (d / "step.hlo.txt").write_bytes(gzip.decompress(
        (DATA / f"{stem}.hlo.txt.gz").read_bytes()))
    return d


def _run(cell, traced=True, steps=5):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell),
        trace=types.SimpleNamespace(steps=steps) if traced else None)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = _unpack(tmp_path_factory.mktemp("scoped"), "tiny-lm-scoped-1")
    text = (d / "step.hlo.txt").read_text()
    ops, async_ops, spans = trace.read(trace.find_xplane(str(d)))
    return d, ops, async_ops, spans, hlo.parse(text), scopes.op_names(text)


def test_recorded_phases_add_up_to_the_busy_time(recorded):
    _, ops, async_ops, spans, table, names = recorded
    steps = sum(1 for n, s, e in spans if n == "dispatch"
                and s >= next(a for m, a, _ in spans if m == "window"))
    s = scopes.split(ops, spans, table, names, steps)
    summary = trace.summarize(ops, async_ops, spans, table, steps)
    assert s.busy_s == pytest.approx(summary.busy_s / steps, rel=1e-12)
    assert sum(s.phase_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    for p in ("forward", "recompute", "backward", "optimizer"):
        assert s.phase_s[p] > 0, p
    assert s.phase_s["sync"] >= 0
    assert 0 < s.attention_s < s.busy_s
    assert 0 <= s.unscoped_pct < 100


def test_read_caches_and_reads_nothing_where_there_is_nothing(
        recorded, tmp_path, monkeypatch):
    d = recorded[0]
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(d))
    assert scopes.read(_run("tiny", traced=False)) is None
    first = scopes.read(_run("tiny"))
    assert first is not None and scopes.read(_run("tiny")) is first
    # the program before the scopes: a trace with none reads nothing
    old = _unpack(tmp_path, "tiny-lm-1")
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(old))
    monkeypatch.setattr(scopes, "fresh_step_text",
                        lambda cell: (old / "step.hlo.txt").read_text())
    assert scopes.read(_run("tiny")) is None
    for name in ("step.forward_ms", "step.recompute_ms", "step.backward_ms",
                 "step.sync_ms", "step.optimizer_ms", "model.attention_ms",
                 "step.unscoped_pct"):
        assert spec.load_reader(name).read(_run("tiny")) is None


def test_names_a_stale_cache_left_are_taken_from_a_fresh_compile(
        recorded, tmp_path, monkeypatch):
    """The step's text as a cache hit would give it, with the metadata of
    a program without the scopes: the names come from a fresh compile of
    the same program, and from nowhere where it is another program or
    cannot be compiled."""
    d, ops, _, spans, table, names = recorded
    text = (d / "step.hlo.txt").read_text()
    stale = tmp_path / "stale"
    stale.mkdir()
    (stale / "t.xplane.pb").write_bytes(
        Path(trace.find_xplane(str(d))).read_bytes())
    (stale / "step.hlo.txt").write_text(
        text.replace("forward", "loss").replace("optimizer", "update"))
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(stale))
    monkeypatch.setattr(scopes, "fresh_step_text", lambda cell: text)
    got = scopes.read(_run("tiny", steps=6))
    assert got == scopes.split(ops, spans, table, names, 6)
    monkeypatch.setattr(scopes, "fresh_step_text",
                        lambda cell: text.replace("f32[", "f16["))
    assert scopes.read(_run("tiny", steps=7)) is None

    def fails(cell):
        raise RuntimeError("no compiler")
    monkeypatch.setattr(scopes, "fresh_step_text", fails)
    assert scopes.read(_run("tiny", steps=8)) is None
