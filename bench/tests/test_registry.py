"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic, limits and metric readers by name."""

import json
import os
import re

import pytest

from bench import harness
from bench import reference

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(workload):
    cell = harness.load_cell(workload, BENCH)
    assert cell.chips in (1, 4)
    assert {"kind", "batch", "seq"} <= set(cell.mix)
    assert set(cell.limits) >= {"emb_gap", "fold_gap", "count_gap",
                                "head_berr"}
    assert harness.driver(cell).run
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_configs_hold_their_own_files():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(harness.CHECKOUT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "reference", cfg["family"] + ".py"))
        assert callable(reference.of(cfg).forward_ops)


def test_unknown_workload_and_device_kind_are_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell", BENCH)
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_metric_names_with_dots_load():
    read = harness.metric_reader("idle_share.local")
    assert callable(read)


UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_entries_keep_to_their_shape():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_shares_of_a_peak_are_named_for_it():
    names = {m["name"]: m for m in BENCH["per_layer"]}
    for name, m in names.items():
        if name.endswith("_roofline") or "mfu" in name:
            assert m["unit"] == "%"
