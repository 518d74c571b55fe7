"""The benchmark's files: BENCHMARK.json keeps its contract, every cell,
configuration and metric it names loads by name, and a new one is found
as a file with no edit anywhere."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, cells // 2)


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name_and_matches_benchmark(cell):
    c = spec.load_cell(cell)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.chips == w["chips"] and c.why == w["why"]
    assert c.config["name"] == w["config"]
    assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").exists()
    entry = next(x for x in BENCH["configs"] if x["name"] == w["config"])
    assert json.loads((ROOT / entry["file"]).read_text()) == c.config
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    assert set(c.limits) == {"loss_gap_1", "loss_gap_2", "loss_gap_3",
                             "grad_norm_gap", "change_norm_gap",
                             "grad_diff_gap"}
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    e2e = {m["name"] for m in spec.metrics_for(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(BENCH, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_loads_by_name(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    reader = spec.load_reader(metric)
    assert reader.UNIT == m["unit"]
    if "layer" in m:
        assert reader.LAYER == m["layer"]
    assert callable(reader.read)


def test_peaks_table_keyed_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_new_cell_config_traffic_and_metric_are_found_as_files(tmp_path):
    """A later PR adds files; nothing that exists changes."""
    base = tmp_path / "bench"
    shutil.copytree(ROOT / "chipbench" / "tests" / "data", base)
    (base / "metrics").mkdir()
    w = json.loads((base / "workloads" / "tiny-lm-1.json").read_text())
    w["name"] = "tiny-lm-new"
    (base / "workloads" / "tiny-lm-new.json").write_text(json.dumps(w))
    (base / "metrics" / "steps.count.py").write_text(
        'UNIT = "steps"\nLAYER = "harness loop"\n\n\n'
        'def read(run):\n    return len(run.step_s)\n')
    cell = spec.load_cell("tiny-lm-new", base)
    assert cell.traffic["kind"] == "lm" and cell.chips == 1
    reader = spec.load_reader("steps.count", base)
    assert reader.UNIT == "steps"
    bench = {"end_to_end": [{"name": "tokens_per_s"}],
             "per_layer": [{"name": "steps.count", "moves": "tokens_per_s"}]}
    found = spec.metrics_for(bench, "tiny-lm-new", "per_layer")
    assert [m["name"] for m in found] == ["steps.count"]
    with pytest.raises(ValueError):
        w["name"] = "other"
        (base / "workloads" / "misnamed.json").write_text(json.dumps(w))
        spec.load_cell("misnamed", base)
