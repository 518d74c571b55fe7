"""The reduction from trace to metrics, on a small trace recorded on a TPU
v5e chip: the tiny LM cell's traced slice and its compiled step's HLO,
as ``harness.run`` wrote them."""
import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import hlo, trace  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    xplane = d / "tiny-lm-1.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (DATA / "tiny-lm-1.xplane.pb.gz").read_bytes()))
    table = hlo.parse(gzip.decompress(
        (DATA / "tiny-lm-1.hlo.txt.gz").read_bytes()).decode())
    ops, async_ops, spans = trace.read(str(xplane))
    return ops, async_ops, spans, table


def test_trace_names_every_device_op_by_its_hlo_instruction(recorded):
    ops, _, spans, table = recorded
    assert sorted(ops) == [0]
    names = {name for name, _, _ in ops[0]}
    assert names and names <= set(table)
    assert {n for n, _, _ in spans} == {"window", "input", "dispatch", "wait"}
    assert any(table[n].cls == "matmul" for n in names)


def test_summary_of_the_recorded_window(recorded):
    ops, async_ops, spans, table = recorded
    s = trace.summarize(ops, async_ops, spans, table, steps=5)
    (lo, hi), = [(a, b) for n, a, b in spans if n == "window"]
    assert s.window_s == pytest.approx(hi - lo)
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_pct < 100
    assert s.devices == 1 and s.collective_s == 0 == s.collective_exposed_s
    assert 0 < s.matmul_s < s.busy_s and s.matmul_flops > 0
    # a share of the bf16 peak can never pass 100%
    assert s.matmul_flops / 197e12 / s.matmul_s <= 1.0
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
    assert all(g[0] in ("input", "dispatch", "wait", "other")
               for g in s.idle_gaps)
    assert sum(g[1] for g in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 5)]) == \
        [(0, 1), (2, 4), (5, 10)]
    assert trace.measure(trace.subtract([(0, 2), (3, 4)], [(1, 3.5)])) == \
        pytest.approx(1.5)
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == \
        "fusion.12"


def test_collectives_and_their_exposed_part():
    """Two devices: an all-reduce half hidden under a fusion on one, and
    alone on the other, where it runs asynchronously."""
    table = {"ar": hlo.Instr("collective"), "f": hlo.Instr("matmul", 1e9),
             "w": hlo.Instr("control")}
    ops = {0: [("w", 0.0, 4.0), ("f", 0.0, 2.0), ("ar", 1.0, 3.0)],
           1: [("f", 0.0, 1.0), ("ar", 1.0, 1.5), ("ar", 2.5, 3.0)]}
    async_ops = {1: [("ar", 1.0, 3.0)]}
    spans = [("window", 0.0, 4.0), ("dispatch", 0.0, 0.5),
             ("wait", 0.5, 4.0)]
    s = trace.summarize(ops, async_ops, spans, table, steps=2)
    assert s.collective_s == pytest.approx(2.0)
    assert s.collective_exposed_s == pytest.approx((1.0 + 2.0) / 2)
    assert s.busy_s == pytest.approx((4.0 + 2.0) / 2)
    assert s.matmul_flops == pytest.approx(1e9)
    assert s.idle_gaps == []     # device 0's while loop covers the window
