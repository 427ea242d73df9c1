"""Run one benchmark cell and print its result line.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout that holds ``src/`` and ``BENCHMARK.json``.  The
cell, its configuration, its traffic mix and its per-layer metrics are
found by name (``bench/catalog.py``).  Set-up builds and warms everything
the window will use; the window runs for ``--seconds``; then the outputs of
the window are compared with the dense reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, ``breakdown`` when traced, and ``checks``, each number compared
with its limit.  Standard error ends with the same checks, one per line.
No TPU, or fewer chips than the cell asks for: exit code 3 and no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NO_DEVICE = 3


def log(**rec):
    print("bench: " + json.dumps(rec, default=float), file=sys.stderr,
          flush=True)


class Context:
    """What a traffic kind's set-up gets: the compile meter and phase
    logging."""

    def __init__(self, meter):
        self.meter = meter

    log = staticmethod(log)

    @contextlib.contextmanager
    def phase(self, name):
        snap, t0, built = self.meter.snapshot(), time.perf_counter(), {}
        yield lambda: built
        built.update(self.meter.since(snap))
        log(phase=name, seconds=time.perf_counter() - t0, **built)


def device_record():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cfg, mix, kind, e2e, per_layer, seed, seconds, trace, meter):
    """Set up, run the window, check; returns the result object.

    The device check is the caller's: tests drive this on the CPU."""
    from bench.meter import Spans
    from bench import trace as trace_mod

    st = kind.setup(cfg, mix, seed, Context(meter))
    setup_s = time.perf_counter() - T0
    log(setup_s=setup_s, **meter.since((0, 0.0, 0)))

    spans = Spans(annotate=trace)
    session = trace_mod.start() if trace else None
    try:
        win = kind.window(st, seconds, spans, meter)
    finally:
        if trace:
            t_stop = time.perf_counter()
            events = trace_mod.stop(session)
    device = dict(device_record(), memory_peak_bytes=peak_bytes())
    log(window_s=win["window_s"], attempted=win["attempted"],
        failed=win["failed"], **{k: v for k, v in win["counters"].items()
                                 if not isinstance(v, list)})
    reduced = None
    if trace:
        t_read = time.perf_counter()
        reduced = trace_mod.reduce_events(events)
        del events
        log(trace_stop_s=t_read - t_stop,
            trace_reduce_s=time.perf_counter() - t_read)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])

    numbers = kind.check(st)
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = win["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    if trace:
        run = {"window_s": win["window_s"], "trace": reduced, **win["counters"]}
        metrics = {}
        for m in per_layer:
            value = m["read"](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def prepare(workload):
    """Everything a process of this checkout does before it touches a
    device: the program and the harness on the path, the cell's pieces
    found by name, and the environment the run needs.  Returns the parsed
    ``BENCHMARK.json``, the cell, its configuration, mix and kind."""
    # one host thread for the small host LAPACK calls: load from one
    # process with few threads keeps the runs of a cell steady
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
        os.environ[var] = "1"
    # the package, not the script's directory, goes on the path
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import catalog

    bench = catalog.load_benchmark(ROOT)
    cell = catalog.cell(bench, workload)
    cfg = catalog.config(bench, cell["config"], ROOT)
    mix = catalog.traffic(cell["traffic"])
    kind = catalog.kind(mix["kind"])
    os.environ["JAX_ENABLE_X64"] = "1" if cfg["dtype"] == "float64" else "0"
    # the compile cache lives at the fixed checkout path, whatever the
    # environment says, so two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    return bench, cell, cfg, mix, kind


def on_tpu(cell):
    """The device record, or None (and why, on standard error) when JAX
    finds no TPU or fewer chips than the cell asks for.  Turns on the
    persistent compile cache when the device is right."""
    dev = device_record()
    log(device=dev)
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), found "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return None
    from repro.dist import configure_compilation_cache

    log(compile_cache=configure_compilation_cache())
    return dev


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix, kind = prepare(args.workload)
    from bench import catalog

    per_layer = [dict(m, read=catalog.metric_reader(m["name"]))
                 for m in catalog.metrics_of(bench, cell["name"], "per_layer")]
    e2e = catalog.metrics_of(bench, cell["name"], "end_to_end")
    if on_tpu(cell) is None:
        return NO_DEVICE

    from bench.meter import CompileMeter

    result = run_cell(cfg, mix, kind, e2e,
                      per_layer if args.trace else [],
                      args.seed, args.seconds, bool(args.trace), CompileMeter())
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
