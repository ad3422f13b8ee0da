"""A cell, a configuration, a traffic mix, a driver and a metric dropped
into a benchmark root are found by name alone: no harness file changes."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import run as run_lib
from chipbench.bench import Bench

REPO = Path(__file__).resolve().parents[2]

TOY_DRIVER = '''
def run(ctx, devices):
    import time
    ctx.start_window()
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < ctx.seconds:
        n += ctx.cell.traffic["per_tick"]
    ctx.end_window(time.perf_counter())
    return {"memory_peak_bytes": 0, "attempted": 1, "failed": 0, "work": n,
            "checks": [{"name": "toy", "value": 0, "limit": 0, "ok": True}]}
'''

TOY_METRIC = '''
NAME = "toy_work_per_s"

def read(run, trace, *, cell, peaks):
    return run["work"] / run["window_s"]
'''


@pytest.fixture()
def root(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "https://example.org/toy",
                            "file": "chipbench/configs/toy.json", "reduced": [],
                            "why": "registry test"})
    spec["workloads"].append({"name": "toy.spin", "config": "toy", "traffic": "spin",
                              "chips": 1, "why": "registry test"})
    spec["end_to_end"].append({"name": "toy_work_per_s", "unit": "ops/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["toy.spin"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = root / "chipbench"
    (c / "configs" / "toy.json").write_text(json.dumps({"name": "toy"}))
    (c / "traffic" / "spin.json").write_text(json.dumps({"kind": "spin", "per_tick": 3}))
    (c / "workloads" / "toy.spin.json").write_text(json.dumps({"driver": "toy"}))
    (c / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (c / "metrics" / "toy_work_per_s.py").write_text(TOY_METRIC)
    return root


def test_cell_found_by_name(root):
    cell = Bench(root).cell("toy.spin")
    assert cell.spec["driver"] == "toy" and cell.traffic["per_tick"] == 3
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "toy_work_per_s"]
    assert cell.per_layer == []


def test_new_cell_runs_end_to_end(root):
    res = run_lib.run_cell("toy.spin", 5, 0.2, False, root=root, require_chip=False)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "toy_work_per_s"}
    assert res["metrics"]["toy_work_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_metric_name_must_match_its_file(root):
    (root / "chipbench" / "metrics" / "toy_work_per_s.py").write_text('NAME = "other"\n')
    with pytest.raises(ValueError):
        Bench(root).metric("toy_work_per_s")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        Bench(REPO).peaks("TPU v99")
    assert Bench(REPO).peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_exits_nonzero(capsys, monkeypatch):
    # On the CPU the benchmark refuses to run and prints no result line.
    monkeypatch.setattr("sys.argv", ["run.py"])
    rc = run_lib.main(["--workload", "rbpf.paper", "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
