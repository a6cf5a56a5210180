"""The benchmark finds every piece of a cell by name, and BENCHMARK.json
keeps to the shape the harness reads."""

from __future__ import annotations

import importlib
import json
import re
import shutil

import pytest

from bench import compare, run
from bench.reference import Model
from bench.tests.tiny import ROOT, workloads

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", workloads())
def test_cell_files_load_by_name(workload):
    spec = run.load_spec(ROOT, workload)
    entry = importlib.import_module(f"bench.entries.{spec['traffic']['entry']}")
    # the configuration file states the sizes the program runs
    m = Model.from_config(spec["config"])
    entry._program_config(spec["config"]["program_arch"], m)
    assert set(spec["limits"]) == {"grad_gap", "estimator_gap", "change_gap", "bits_gap"}
    assert set(spec["config"]["reduced"]) == set(spec["config"]["published"])


@pytest.mark.parametrize("workload", workloads())
def test_cell_metrics_and_readers(bench, workload):
    e2e = {m["name"] for m in run.cell_metrics(bench, workload, trace=False)}
    assert {"setup_s", "tokens_per_s"} <= e2e
    per_layer = run.cell_metrics(bench, workload, trace=True)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(isinstance(x, str) and "\n" not in x for x in layers)
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == configs
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 2)


def test_peaks_are_keyed_by_device_kind():
    assert run.peaks_for(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for(ROOT, "TPU v9 imaginary")


def test_a_new_cell_is_new_files_and_entries(tmp_path, bench):
    """A later cell adds a traffic file, a limits file and a workloads entry;
    the harness finds them by name and no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = bench["workloads"][0]
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{base['traffic']}.json").read_text())
    traffic["n_workers"] = 4
    (tmp_path / "bench" / "traffic" / "later.n4.json").write_text(json.dumps(traffic))
    limits = (ROOT / "bench" / "limits" / f"{base['name']}.json").read_text()
    (tmp_path / "bench" / "limits" / "later-cell.json").write_text(limits)
    new = dict(bench, workloads=bench["workloads"] + [
        dict(base, name="later-cell", traffic="later.n4")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    spec = run.load_spec(tmp_path, "later-cell")
    assert spec["traffic"]["n_workers"] == 4
    assert [m["name"] for m in run.cell_metrics(new, "later-cell", True)] == [
        m["name"] for m in run.cell_metrics(new, base["name"], True)
        if "workloads" not in m]


def test_judge_fails_a_number_over_its_limit_or_not_finite():
    limits = {"a": 1e-3, "b": 0.0}
    assert compare.judge({"a": 5e-4, "b": 0.0}, limits)[0]
    assert not compare.judge({"a": 2e-3, "b": 0.0}, limits)[0]
    assert not compare.judge({"a": float("nan"), "b": 0.0}, limits)[0]
    assert not compare.judge({"a": 0.0, "b": 1e-9}, limits)[0]


def test_memory_peak_counts_the_region_reserved_for_temporaries():
    # memory_stats() of a TPU v5e after the qwen cell's first rounds: the
    # round's temporaries sit in the reserved region, not in bytes_in_use
    stats = {"bytes_in_use": 5200224768, "peak_bytes_in_use": 5200225280,
             "bytes_reserved": 7498252288, "peak_bytes_reserved": 7498252288}
    assert run.peak_bytes(stats) == 5200225280 + 7498252288
    assert run.peak_bytes({"peak_bytes_in_use": 7}) == 7
