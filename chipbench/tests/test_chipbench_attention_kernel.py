"""``model.attention_kernel_pct``: the share of the attention's device time
that Mosaic kernels take, on made-up windows and on a small trace recorded
on a TPU v5e chip (the tiny LM cell's traced slice and its compiled step,
whose attention runs as XLA fusions, so it reads 0)."""
import gzip
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import hlo, scopes, spec, trace  # noqa: E402

READER = spec.load_reader("model.attention_kernel_pct")

TEXT = (
    'ENTRY %main (a: bf16[8]) -> bf16[8] {\n'
    '  %flash_attention_fwd.3 = (bf16[8]{0}, f32[8]{0}) custom-call(%a), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[8]{0}}, frontend_attributes={kernel_metadata="{}"}, metadata='
    '{op_name="jit(s)/jvp(forward)/attention/flash_attention_fwd/'
    'pallas_call"}\n'
    '  %cc.4 = bf16[8]{0} custom-call(%a), custom_call_target="Sharding"\n'
    '  ROOT %fusion.5 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f\n'
    '}\n')


def _run(cell="tiny", kind="lm", traced=True, steps=5):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell, traffic={"kind": kind}),
        trace=types.SimpleNamespace(steps=steps) if traced else None)


def test_mosaic_calls_are_the_tpu_custom_calls():
    assert READER.mosaic_calls(TEXT) == {"flash_attention_fwd.3"}


@pytest.mark.parametrize("kernels,want", [
    ((), 0.0), (("k",), 100 * 3.0 / 4.0), (("k", "f"), 100.0)])
def test_share_of_a_made_up_window(kernels, want):
    """One device: a kernel and a fusion under the attention scope, an
    op outside it, a control op, and a kernel call the window cuts."""
    table = {"w": hlo.Instr("control"), "k": hlo.Instr("other"),
             "f": hlo.Instr("other"), "m": hlo.Instr("matmul", 1e9)}
    names = {"w": "jit(s)/jvp(forward)/attention/while",
             "k": "jit(s)/jvp(forward)/attention/flash_attention_fwd/"
                  "pallas_call",
             "f": "jit(s)/transpose(jvp(forward))/attention/transpose",
             "m": "jit(s)/jvp(forward)/dot_general"}
    ops = {0: [("w", 0.0, 9.0), ("k", 0.0, 2.0), ("f", 2.0, 3.0),
               ("m", 3.0, 5.0), ("k", 7.0, 9.0)]}
    spans = [("window", 0.0, 8.0)]
    got = READER.kernel_share(ops, spans, table, names, set(kernels))
    assert got == pytest.approx(want)


def test_no_attention_op_reads_nothing():
    ops = {0: [("m", 0.0, 1.0)]}
    assert READER.kernel_share(ops, [("window", 0.0, 2.0)],
                               {"m": hlo.Instr("matmul", 1.0)},
                               {"m": "jit(s)/jvp(forward)/dot_general"},
                               set()) is None


def _unpack(tmp_path, stem):
    d = tmp_path / stem
    d.mkdir()
    (d / f"{stem}.xplane.pb").write_bytes(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    (d / "step.hlo.txt").write_bytes(gzip.decompress(
        (DATA / f"{stem}.hlo.txt.gz").read_bytes()))
    return d


def test_recorded_xla_attention_reads_zero(tmp_path, monkeypatch):
    d = _unpack(tmp_path, "tiny-lm-scoped-1")
    monkeypatch.setattr(scopes, "trace_dir", lambda cell: str(d))
    assert READER.mosaic_calls((d / "step.hlo.txt").read_text()) == set()
    assert READER.read(_run()) == 0.0


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
