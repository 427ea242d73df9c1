"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import catalog
from bench.meter import CompileMeter

ROOT = catalog.ROOT


def _toy_checkout(tmp_path):
    """A copy of bench/ with a configuration, a mix, a kind and a metric of
    new names dropped in, and a BENCHMARK.json naming them."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(catalog.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench_dir / "configs" / "toy-config.json").write_text(json.dumps(
        {"name": "toy-config", "dtype": "float64", "size": 3,
         "limits": {"sum_gap": 0.0}}))
    (bench_dir / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "toy_kind", "steps": 4}))
    (bench_dir / "kinds" / "toy_kind.py").write_text(
        "def setup(cfg, mix, seed, ctx):\n"
        "    return {'n': cfg['size'] * mix['steps']}\n"
        "def window(st, seconds, spans, meter):\n"
        "    return {'window_s': 1.0, 'attempted': st['n'], 'failed': 0,\n"
        "            'end_to_end': {'toy_rate': float(st['n'])},\n"
        "            'counters': {'toy_count': st['n']}}\n"
        "def check(st):\n"
        "    return {'sum_gap': 0.0}\n")
    (bench_dir / "metrics" / "toy_metric.toy.py").write_text(
        "def read(run):\n    return run.get('toy_count')\n")
    # a quantity's reader, shared by every cell-suffixed metric of it
    (bench_dir / "metrics" / "toy_share.py").write_text(
        "def read(run):\n    return 2 * run.get('toy_count')\n")
    bench = {
        "configs": [{"name": "toy-config",
                     "file": "bench/configs/toy-config.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy-config",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "toy_rate", "unit": "items/s",
                        "workloads": ["toy.cell"]},
                       {"name": "other_rate", "unit": "items/s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "toy_metric.toy", "unit": "items",
                       "workloads": ["toy.cell"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path), str(bench_dir)


def test_new_files_are_found_by_name(tmp_path):
    root, bench_dir = _toy_checkout(tmp_path)
    bench = catalog.load_benchmark(root)
    cell = catalog.cell(bench, "toy.cell")
    cfg = catalog.config(bench, cell["config"], root)
    mix = catalog.traffic(cell["traffic"], bench_dir)
    kind = catalog.kind(mix["kind"], bench_dir)
    read = catalog.metric_reader("toy_metric.toy", bench_dir)
    e2e = catalog.metrics_of(bench, "toy.cell", "end_to_end")
    assert [m["name"] for m in e2e] == ["setup_s", "toy_rate"]
    assert read({"toy_count": 7}) == 7
    for name in ("toy_share.toy", "toy_share.other"):
        assert catalog.metric_reader(name, bench_dir)({"toy_count": 7}) == 14
    with pytest.raises(KeyError):
        catalog.metric_reader("toy_missing.toy", bench_dir)

    from bench import run

    res = run.run_cell(cfg, mix, kind, e2e, [], 1, 1.0, False, CompileMeter())
    assert res["correct"] is True
    assert res["attempted"] == 12
    assert res["metrics"]["toy_rate"] == {"value": 12.0, "unit": "items/s"}
    assert set(res["metrics"]) == {"setup_s", "toy_rate"}
    assert list(res)[-1] == "checks"
    assert res["checks"] == {"sum_gap": {"value": 0.0, "limit": 0.0}}


def test_every_named_piece_of_the_benchmark_exists():
    bench = catalog.load_benchmark()
    for cell in bench["workloads"]:
        cfg = catalog.config(bench, cell["config"])
        mix = catalog.traffic(cell["traffic"])
        kind = catalog.kind(mix["kind"])
        for fn in ("setup", "window", "check", "control"):
            assert callable(getattr(kind, fn))
        assert cfg["limits"]
    for m in bench["per_layer"]:
        assert callable(catalog.metric_reader(m["name"]))


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "j1j2-cyl4.sweep", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_directory_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(catalog.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "j1j2-cyl4.sweep",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
