"""Every file BENCHMARK.json names exists, loads, and keeps the contract's
shape: the harness finds configurations, traffic mixes, drivers and
per-layer metric readers by name alone."""
import json
import re

import pytest

from conftest import CHIP

ROOT = CHIP.parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert data["max_retained_receipts"] >= 1
    for key in cfg["reduced"]:
        assert key in data and NAME.match(key)
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(run, cell):
    c = run.load_cell(cell)
    assert c.chips in (1, 4)
    driver = run.load_module(CHIP / "traffic" / f"{c.traffic['driver']}.py",
                             f"chipbench_driver_{c.traffic['driver']}")
    for fn in ("prepare", "window", "check", "control"):
        assert callable(getattr(driver, fn))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert c.traffic["limits"]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader(run, metric):
    reader = run.load_module(CHIP / "metrics" / f"{metric['name']}.py",
                             f"chipbench_metric_{metric['name']}")
    assert callable(reader.read)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    for cell in metric["workloads"]:
        assert cell in CELLS
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        assert cell in moved.get("workloads", CELLS)


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
