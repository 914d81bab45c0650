"""`BENCHMARK.json` against the files it names and the rules it keeps."""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402
from workloads import RUNNERS  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert (ROOT / bench["command"][1]).is_file()
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_and_units(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_config_resolves(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = spec.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in ("dataset", "n_trees", "n_comparators", "n_leaves",
                    "n_test", "n_genes", "n_classes", "n_features",
                    "max_depth"):
            assert key in cfg, (c["name"], key)
        assert (ROOT / cfg["reference"]).is_file()


def test_every_cell_resolves_and_reports(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic = spec.traffic(w["traffic"])
        runner = RUNNERS[traffic["kind"]]
        reported = {m["name"] for m in spec.metrics_for(bench, "end_to_end",
                                                        w["name"])}
        assert reported == {"setup_s", runner.rate_metric}
        layer = spec.metrics_for(bench, "per_layer", w["name"])
        assert layer
        assert {m["moves"] for m in layer} <= reported
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    assert e2e >= {"setup_s", "evals_per_s", "faults_per_s"}


def test_every_metric_has_a_reader(bench):
    cells = {w["name"] for w in bench["workloads"]}
    layers = {}
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
        assert set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert set(layers) <= {"search driver", "search step", "fitness kernel",
                           "fault simulator", "device"}


def test_every_compared_number_has_a_limit():
    limits = spec.limits()
    names = {"acc_gap_samples", "area_gap_rel", "rank_mismatches",
             "front_mismatches", "generation_gap", "shape_mismatches",
             "rows_carried_share", "front_regressions", "lane_mismatches",
             "circuit_mismatches"}
    assert set(limits) == names
    assert all(v >= 0 for v in limits.values())


def test_off_tpu_run_refuses_without_a_result(bench):
    w = bench["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", w, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_without_the_program(tmp_path, bench):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
