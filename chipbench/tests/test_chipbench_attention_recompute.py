"""``model.attention_recompute_ms``: the attention's device time in remat's
recompute, on made-up windows and on a small trace recorded on a TPU v5e
chip (the tiny LM cell's traced slice and its compiled step, whose remat
reruns the attention's XLA forward)."""
import gzip
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import hlo, scopes, spec  # noqa: E402

READER = spec.load_reader("model.attention_recompute_ms")

NAMES = {
    "w": "jit(s)/transpose(jvp(forward))/rematted_computation/attention/"
         "while",
    "r": "jit(s)/transpose(jvp(forward))/rematted_computation/attention/"
         "flash_attention_fwd/pallas_call",
    "t": "jit(s)/transpose(jvp(forward))/rematted_computation/attention/"
         "transpose",
    "p": "jit(s)/transpose(jvp(forward))/rematted_computation/dot_general",
    "f": "jit(s)/jvp(forward)/attention/flash_attention_fwd/pallas_call",
    "b": "jit(s)/transpose(jvp(forward))/attention/flash_attention_bwd/"
         "pallas_call"}
TABLE = {"w": hlo.Instr("control"), "r": hlo.Instr("other"),
         "t": hlo.Instr("other"), "p": hlo.Instr("matmul", 1e9),
         "f": hlo.Instr("other"), "b": hlo.Instr("other")}


def _run(cell="tiny", kind="lm", traced=True, steps=5):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell, traffic={"kind": kind}),
        trace=types.SimpleNamespace(steps=steps) if traced else None)


@pytest.mark.parametrize("steps,want", [(1, 3.0), (2, 1.5)])
def test_recompute_of_a_made_up_window(steps, want):
    """One device: the recomputed kernel and a layout op that overlaps it,
    a control op around them, a projection in the recompute, the forward
    and the backward kernels, and a recomputed kernel the window cuts."""
    ops = {0: [("w", 0.0, 9.0), ("f", 0.0, 1.0), ("r", 1.0, 3.0),
               ("t", 2.5, 3.5), ("p", 3.5, 5.0), ("b", 5.0, 7.0),
               ("r", 7.5, 9.0)]}
    got = READER.recompute_seconds(ops, [("window", 0.0, 8.0)], TABLE,
                                   NAMES, steps)
    assert got == pytest.approx(want)


def test_mean_over_devices():
    ops = {0: [("r", 0.0, 2.0)], 1: [("t", 0.0, 1.0), ("b", 1.0, 3.0)]}
    got = READER.recompute_seconds(ops, [("window", 0.0, 4.0)], TABLE,
                                   NAMES, 1)
    assert got == pytest.approx(1.5)


def test_nothing_recomputed_reads_zero():
    """Where remat keeps what the attention's backward needs, the
    recompute holds no attention op."""
    ops = {0: [("f", 0.0, 1.0), ("p", 1.0, 2.0), ("b", 2.0, 3.0)]}
    assert READER.recompute_seconds(ops, [("window", 0.0, 3.0)], TABLE,
                                    NAMES, 1) == 0.0


def _unpack(tmp_path, stem):
    d = tmp_path / stem
    d.mkdir()
    (d / f"{stem}.xplane.pb").write_bytes(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    (d / "step.hlo.txt").write_bytes(gzip.decompress(
        (DATA / f"{stem}.hlo.txt.gz").read_bytes()))
    return d


def test_recorded_remat_reruns_the_attention(tmp_path, monkeypatch):
    """The recorded step reruns the XLA attention's forward in its
    recompute: that reads above 0 and below the attention's time over
    forward, recompute and backward."""
    d = _unpack(tmp_path, "tiny-lm-scoped-1")
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(d))
    got = READER.read(_run())
    attention_ms = spec.load_reader("model.attention_ms").read(_run())
    assert 0 < got < attention_ms


@pytest.mark.parametrize("case", ["untraced", "images", "unscoped"])
def test_reads_nothing_where_there_is_nothing(case, tmp_path, monkeypatch):
    """An untraced run, a CNN cell and a program from before the scopes
    (the step compiled afresh carries none either) read nothing."""
    d = _unpack(tmp_path, "tiny-lm-1")
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(d))
    monkeypatch.setattr(scopes, "fresh_step_text",
                        lambda cell: (d / "step.hlo.txt").read_text())
    run = {"untraced": _run(traced=False), "images": _run(kind="images"),
           "unscoped": _run()}[case]
    assert READER.read(run) is None
