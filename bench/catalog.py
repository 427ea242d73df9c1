"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- a configuration: the file its ``configs`` entry names;
- a traffic mix: ``bench/traffic/<name>.json``, whose ``kind`` names the
  generator, ``bench/kinds/<kind>.py``;
- a per-layer metric: ``bench/metrics/<name>.py``, or else the reader of
  its quantity, ``bench/metrics/<base>.py``, where ``<base>`` is the name up
  to its first dot (``device_idle.sweep`` -> ``device_idle``): a reader
  with ``read(run) -> float | None``, shared by the cells that split one
  quantity by the end-to-end metric it moves.

A later change adds a configuration, a mix or a metric by adding a file and
an entry, never by editing the harness.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _named(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, modname: str):
    if not os.path.isfile(path):
        raise KeyError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(name: str, bench_dir: str = BENCH_DIR):
    """The generator module that drives a traffic kind."""
    return _module(os.path.join(bench_dir, "kinds", f"{name}.py"),
                   f"bench_kind_{name}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(run)`` of the per-layer metric ``name``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.isfile(path):
            modname = "bench_metric_" + stem.replace(".", "_").replace("-", "_")
            return _module(path, modname).read
    raise KeyError(f"no reader for the metric {name!r} under {bench_dir}")


def metrics_of(bench: dict, workload: str, section: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that a cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]
