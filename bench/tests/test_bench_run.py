"""The harness: its refusals, where it keeps state, and how it finds a
cell's pieces by name."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def test_a_cpu_device_is_refused_with_kind_and_count():
    with pytest.raises(run.NoChip,
                       match=r"platform 'cpu', device_kind 'cpu', \d+ device"):
        run.check_device(1)


def test_main_exits_nonzero_and_prints_no_result_without_a_chip(capsys):
    rc = run.main(["--workload", "resnet50_bulk", "--seed", "1",
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no TPU" in err


def test_a_device_kind_missing_from_the_peaks_is_an_error():
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.load_peaks("TPU v9 imaginary")


def test_plan_caches_stay_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    run.setup_env()
    cache = Path(run.os.environ["REPRO_CACHE_DIR"])
    assert cache.is_relative_to(BENCH)
    assert ".state/" in (BENCH / ".gitignore").read_text()


def test_no_bench_source_asks_the_planner_to_tune():
    for path in BENCH.rglob("*.py"):
        if "tests" not in path.parts:
            assert "tune=" not in path.read_text(), path


def test_only_the_benchmark_files_are_not_enough(tmp_path):
    """A directory with BENCHMARK.json and bench/ alone has no program:
    the run fails and prints no result line."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50_bulk",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")


def test_every_cell_finds_its_pieces_by_name():
    bench = run.load_benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        _, config = run.find_cell(bench, cell["name"])
        assert (ROOT / config["file"]).is_file()
        assert (BENCH / "configs" / f"{cell['config']}.py").is_file()
        assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
        reported = {n for n, m in e2e.items()
                    if cell["name"] in m.get("workloads", [cell["name"]])}
        assert "setup_s" in reported and len(reported) >= 2
        for m in bench["per_layer"]:
            if cell["name"] in m["workloads"]:
                assert m["moves"] in reported, (cell["name"], m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_metric_is_added_as_a_file_and_an_entry(tmp_path):
    (tmp_path / "throwaway.bulk.py").write_text(
        "def read(win):\n    return 2.0 * win.seconds\n")
    (tmp_path / "silent.bulk.py").write_text(
        "def read(win):\n    return None\n")
    entries = [
        {"name": "throwaway.bulk", "unit": "s", "workloads": ["c1"]},
        {"name": "silent.bulk", "unit": "s", "workloads": ["c1"]},
        {"name": "other.bulk", "unit": "s", "workloads": ["c2"]},
    ]
    win = run.Window(3.0, 0.0, 1.0, [], [], [], None, {}, {})
    got = run.read_metrics(entries, "c1", win, metrics_dir=tmp_path)
    assert got == {"throwaway.bulk": {"value": 6.0, "unit": "s"}}


def test_benchmark_json_has_exactly_the_contract_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1] == "bench/run.py"
    assert all(c["chips"] == 1 for c in bench["workloads"])


def test_a_mix_is_added_as_data_alone():
    """Bursts and mixed resolutions are parameters of the one generator:
    a mix file with them runs whole and comes out correct."""
    from . import _tiny
    cell, cfg, mix = _tiny.cell("resnet50_interactive")
    mix = dict(mix, rate_cycle=[[0.1, 4], [0.4, 1]],
               image_sizes=[[24, 1], [32, 2], [40, 1]])
    res = run.run_cell(cell, cfg, mix, 2**31 + 99, 0.3, False,
                       run.load_peaks("TPU v5 lite"), _tiny.CPU,
                       log=lambda *a, **k: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 60 and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
