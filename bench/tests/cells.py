"""The benchmark's cells cut to a size a CPU test can hold."""
from bench import catalog

SMALL = {
    "j1j2-cyl4.sweep": ({"lx": 2, "ly": 3, "max_bond": 8}, {}),
}


def small_cell(workload):
    bench = catalog.load_benchmark()
    cell = catalog.cell(bench, workload)
    cfg = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    cfg_over, mix_over = SMALL[workload]
    cfg.update(cfg_over)
    mix.update(mix_over)
    e2e = catalog.metrics_of(bench, workload, "end_to_end")
    return cfg, mix, catalog.kind(mix["kind"]), e2e


def run_small(workload, seed=2**31 + 12345, seconds=2.0, broken=None):
    """Drive a whole run of the cell at the small size, device check
    skipped; returns the result object.  ``broken()``, when given, runs as
    the window starts: it breaks the timed path, not the set-up."""
    from bench import run
    from bench.meter import CompileMeter

    cfg, mix, kind, e2e = small_cell(workload)
    if broken is not None:
        window = kind.window

        def broken_window(*a, **kw):
            broken()
            return window(*a, **kw)

        kind.window = broken_window
    return run.run_cell(cfg, mix, kind, e2e, [], seed, seconds, False,
                        CompileMeter())
