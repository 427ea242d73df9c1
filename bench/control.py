"""Readings that set the limits of ``correct``: the program and the control.

    python bench/control.py --workload NAME --seeds 1,2,3 --seconds S

For each seed, in one process: the cell's set-up, a window of ``S``
seconds, then the numbers the benchmark compares, once for the program's
outputs (``check``) and once with the float32 reference put in the
program's place (``control``).  One JSON line per seed.  Needs a TPU, like
``run.py``; the benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

# the package, not the script's directory, goes on the path
HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = os.path.dirname(HERE)
from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, mix, kind = run.prepare(args.workload)
    dev = run.on_tpu(cell)
    if dev is None:
        return run.NO_DEVICE
    from bench.meter import CompileMeter, Spans

    meter = CompileMeter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        st = kind.setup(cfg, mix, seed, run.Context(meter))
        win = kind.window(st, args.seconds, Spans(), meter)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "device": dev,
            "attempted": win["attempted"], "failed": win["failed"],
            "check": kind.check(st), "control": kind.control(st),
            "limits": cfg["limits"], "seconds": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
