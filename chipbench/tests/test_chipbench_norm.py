"""The CNN's ``norm`` scope and the readers of CNN cells: GroupNorm is named
in the forward and the backward of the tiny CNN step lowered on the CPU
through ``build_train_step``, the scope changes nothing but metadata, and
on a small trace recorded on a TPU v5e chip (the tiny CNN cell's traced
slice and its compiled step, as ``harness.run`` wrote them) the norm's
time and the convolutions' roofline share read sensible numbers."""
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

NORM = spec.load_reader("model.norm_ms.cnn")
ROOFLINE = spec.load_reader("matmul_roofline.cnn")
STEM = "tiny-cnn-scoped-1"


def _lower_tiny_cnn_step() -> str:
    """The optimized HLO of the tiny CNN cell's train step (allreduce, SGD
    momentum), compiled for the CPU."""
    import jax
    from chipbench import system, traffic
    cell = spec.load_cell("tiny-cnn-1", DATA)
    used = jax.devices()[:1]
    sysm = system.build(cell, used)
    shapes = system.param_shapes(sysm.model)
    params = system.make_params(shapes, cell.config, 1, used[0])
    state = sysm.ts.init_state(jax.random.PRNGKey(0), dtype_params=params)
    batch = jax.device_put(traffic.make_pool(cell.traffic, cell.config, 1)[0],
                           sysm.feed_shardings)
    return sysm.ts.step_fn.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def lowered():
    text = _lower_tiny_cnn_step()
    return text, scopes.op_names(text)


def test_norm_is_named_in_the_forward_and_the_backward(lowered):
    _, names = lowered
    found = {scopes.phase(n) for n in names.values() if NORM.in_norm(n)}
    assert {"forward", "backward"} <= found
    assert not any(NORM.in_norm(n) for n in names.values()
                   if scopes.phase(n) == "optimizer")


def test_norm_scope_changes_nothing_but_metadata(lowered, monkeypatch):
    import jax
    text, _ = lowered
    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name == "norm"
        else scope(name))
    bare = _lower_tiny_cnn_step()
    names = scopes.op_names(bare)
    assert scopes.is_scoped(names)
    assert not any(NORM.in_norm(n) for n in names.values())
    assert scopes.code_only(bare) == scopes.code_only(text)


def _run(cell="tiny-cnn-1", kind="images", traced=True, steps=5, **trace_):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell, traffic={"kind": kind}),
        peak=spec.peaks("TPU v5 lite"),
        trace=types.SimpleNamespace(steps=steps, **trace_) if traced
        else None)


@pytest.mark.parametrize("reader", [NORM, ROOFLINE],
                         ids=["model.norm_ms.cnn", "matmul_roofline.cnn"])
@pytest.mark.parametrize("case", ["lm", "untraced"])
def test_cnn_readers_read_nothing_on_an_lm_or_untraced_run(reader, case):
    """Neither touches a trace where it has nothing to read: an LM cell's
    traced run (with a roofline that the LM's reader would read) and an
    untraced CNN run."""
    if case == "lm":
        run = _run("tiny-lm-1", kind="lm", matmul_flops=1e9, matmul_s=1e-3)
    else:
        run = _run(traced=False)
    assert reader.read(run) is None


def test_norm_seconds_of_a_made_up_window():
    """Two devices; norm ops overlap on one, a control op and an op outside
    the window are left out."""
    table = {"w": hlo.Instr("control"), "n1": hlo.Instr("other"),
             "n2": hlo.Instr("other"), "c": hlo.Instr("matmul", 1e9)}
    names = {"w": "jit(s)/jvp(forward)/norm/while",
             "n1": "jit(s)/jvp(forward)/norm/reduce_sum",
             "n2": "jit(s)/transpose(jvp(forward))/norm/mul",
             "c": "jit(s)/jvp(forward)/conv_general_dilated"}
    ops = {0: [("w", 0.0, 8.0), ("n1", 1.0, 3.0), ("n2", 2.0, 4.0),
               ("c", 4.0, 6.0), ("n1", 9.0, 10.0)],
           1: [("n2", 0.5, 1.5)]}
    spans = [("window", 0.0, 8.0)]
    got = NORM.norm_seconds(ops, spans, table, names, steps=2)
    assert got == pytest.approx((3.0 + 1.0) / (2 * 2))


def _unpack(tmp_path, stem, edit=None):
    d = tmp_path / stem
    d.mkdir()
    (d / f"{stem}.xplane.pb").write_bytes(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    text = gzip.decompress((DATA / f"{stem}.hlo.txt.gz").read_bytes()).decode()
    (d / "step.hlo.txt").write_text(edit(text) if edit else text)
    return d


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = _unpack(tmp_path_factory.mktemp("cnn"), STEM)
    text = (d / "step.hlo.txt").read_text()
    ops, async_ops, spans = trace.read(trace.find_xplane(str(d)))
    window = next(s for n, s, _ in spans if n == "window")
    steps = sum(1 for n, s, _ in spans if n == "dispatch" and s >= window)
    summary = trace.summarize(ops, async_ops, spans, hlo.parse(text), steps)
    return d, summary


def test_recorded_norm_time_is_positive_and_below_the_busy_time(
        recorded, monkeypatch):
    d, summary = recorded
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(d))
    ms = NORM.read(_run(steps=summary.steps))
    assert 0 < ms < 1e3 * summary.busy_s / summary.steps


def test_recorded_convolution_roofline_is_a_share(recorded):
    _, summary = recorded
    run = _run(steps=summary.steps, matmul_flops=summary.matmul_flops,
               matmul_s=summary.matmul_s)
    assert summary.matmul_flops > 0
    assert 0 < ROOFLINE.read(run) < 100


def test_norm_reads_nothing_for_a_program_without_the_scope(
        recorded, tmp_path, monkeypatch):
    """The recorded step with the scope taken out of its names, as the
    program before it was opened compiles: no op carries it, the step
    compiled afresh is the same program and carries none either, so the
    reader reports nothing and not 0; a fresh compile that carries the
    scope, as a stale compile cache would make necessary, gives it back."""
    d, summary = recorded
    with_norm = (d / "step.hlo.txt").read_text()
    bare = _unpack(tmp_path, STEM, lambda t: t.replace("/norm/", "/"))
    bare_text = (bare / "step.hlo.txt").read_text()
    assert "/norm/" in with_norm and "/norm/" not in bare_text
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(bare))
    monkeypatch.setattr(scopes, "fresh_step_text", lambda cell: bare_text)
    assert NORM.read(_run(steps=summary.steps)) is None
    monkeypatch.setattr(scopes, "fresh_step_text", lambda cell: with_norm)
    assert NORM.read(_run(steps=summary.steps)) > 0
