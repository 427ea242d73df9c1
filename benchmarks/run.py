# One function per paper table. Prints ``name,us_per_call,derived`` CSV and
# (for the dist suite) writes benchmarks/bench_dist.json as a perf record.
#
# Each suite runs in its own process, one after another, and this parent
# never imports jax: a process that has touched jax holds the accelerator,
# and suites that start their own children (bench_dist) need it free.
# Exits non-zero when any suite failed.
import importlib
import os
import subprocess
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

SUITES = [
    ("Fig5/10/13: contraction algorithms", "bench_contraction"),
    ("Fig2: block structure", "bench_blocks"),
    ("TableII: cost model + weak scaling", "bench_scaling"),
    ("Alg1: Davidson", "bench_davidson"),
    ("Fig6: sweep uniformity", "bench_sweep"),
    ("Dist: plan cache + mesh sharding", "bench_dist"),
    ("Serve: batched multi-problem throughput", "bench_serve"),
    ("LM cells (beyond paper)", "bench_lm"),
]


def run_suite(module: str) -> None:
    """Child mode: run one suite in this process and print its CSV rows."""
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    fn = importlib.import_module(f"benchmarks.{module}").run
    for name, us, derived in fn():
        print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> int:
    print("name,us_per_call,derived", flush=True)
    failed = []
    for title, module in SUITES:
        print(f"# {title}", flush=True)
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--suite", module]
        ).returncode
        if rc != 0:
            print(f"{title}_FAILED,0,exit {rc}", flush=True)
            failed.append(module)
    if failed:
        print(f"failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    if "--suite" in sys.argv:
        run_suite(sys.argv[sys.argv.index("--suite") + 1])
    else:
        sys.exit(main())
