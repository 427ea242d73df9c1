"""CLI front end: ``python -m repro.serve`` — batched parameter sweeps.

Expands ``--sweep NAME=a:b:n`` ranges into a cartesian grid of problems,
submits them all through a ``DMRGService`` queue, and prints one row per
problem plus the service stats.  ``--check`` re-solves every problem
individually and asserts the batched energies match to 1e-10 AND that the
warmed pipeline served the whole sweep with zero retraces.

Example (the README quickstart)::

    PYTHONPATH=src python -m repro.serve --model heisenberg --n-sites 8 \
        --max-bond 16 --sweep J=0.8:1.2:4 --batch 4 --check

``--warmup MODEL[,m=BOND][,n=SITES]`` (repeatable, requires
``--plan-store``) switches to warmup-only mode: prime the persistent plan
+ executable store for each named target and exit, so a later worker on
the same store starts its first sweep near steady-state speed (README
"Cold start", DESIGN.md Sec. 3.9)::

    PYTHONPATH=src python -m repro.serve --warmup heisenberg,m=8,n=6 \
        --batch 2 --plan-store /tmp/dmrg_store
"""
from __future__ import annotations

import os

# ``python -m repro.serve`` imports the package __init__ (and through it jax)
# BEFORE this module runs, so an env setdefault here is too late for jax's
# import-time config read — flip the flag through the config API instead.
os.environ.setdefault("JAX_ENABLE_X64", "1")
if os.environ["JAX_ENABLE_X64"] not in ("0", "false", "False"):
    import jax

    jax.config.update("jax_enable_x64", True)

import argparse
import itertools
import json
import sys
import time
from typing import Dict, List, Tuple

import numpy as np


def parse_sweep(arg: str) -> Tuple[str, np.ndarray]:
    """``NAME=a:b:n`` -> (name, linspace(a, b, n)); ``NAME=v`` -> single value."""
    try:
        name, rng = arg.split("=", 1)
        parts = rng.split(":")
        if len(parts) == 1:
            return name, np.array([float(parts[0])])
        lo, hi, n = float(parts[0]), float(parts[1]), int(parts[2])
        if n < 1:
            raise ValueError
        return name, np.linspace(lo, hi, n)
    except ValueError:
        raise SystemExit(
            f"bad --sweep {arg!r}: expected NAME=a:b:n or NAME=value"
        )


def build_grid(sweeps: List[Tuple[str, np.ndarray]]) -> List[Dict[str, float]]:
    """Cartesian product of the swept axes as per-problem parameter dicts."""
    if not sweeps:
        return [{}]
    names = [s[0] for s in sweeps]
    return [
        {n: float(v) for n, v in zip(names, combo)}
        for combo in itertools.product(*(s[1] for s in sweeps))
    ]


def parse_warmup(arg: str, default_m: int, default_n: int):
    """``MODEL[,m=BOND][,n=SITES]`` -> (model, max_bond, n_sites)."""
    parts = arg.split(",")
    model, m, n = parts[0], default_m, default_n
    try:
        for p in parts[1:]:
            k, v = p.split("=", 1)
            if k == "m":
                m = int(v)
            elif k == "n":
                n = int(v)
            else:
                raise ValueError
        if not model:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"bad --warmup {arg!r}: expected MODEL[,m=BOND][,n=SITES]"
        )
    return model, m, n


def run_warmup(args) -> int:
    """Warmup-only mode: prime the plan store for each --warmup target.

    For every ``MODEL,m=...`` target this runs the service warmup — one full
    solve per power-of-two slot size, covering every bond-schedule structure
    — against the activated ``--plan-store``, then the blocking export
    compile pass.  A fresh worker on the same store afterwards starts its
    first sweep within ~2x of steady state (benchmarks/bench_dist.py
    ``cold_start`` leg) instead of ~20x.
    """
    from repro.dist import store_stats
    from repro.serve import DMRGService, ProblemSpec

    if not args.plan_store:
        print("--warmup requires --plan-store (nowhere to persist) ",
              file=sys.stderr)
        return 2
    svc = DMRGService(max_batch=args.batch, start=False,
                      plan_store=args.plan_store)
    sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= args.batch]
    try:
        for target in args.warmup:
            model, m, n = parse_warmup(target, args.max_bond, args.n_sites)
            spec = ProblemSpec.make(
                model, n, max_bond=m,
                sweeps_per_bond=args.sweeps_per_bond,
                davidson_iters=args.davidson_iters,
            )
            t0 = time.perf_counter()
            svc.warmup(spec, sizes=sizes)
            print(f"warmed {model} (m={m}, n={n}) x sizes {sizes} in "
                  f"{time.perf_counter() - t0:.1f}s")
        st = store_stats()
        print(f"plan store {st['root']}: {st['saves']} plan saves, "
              f"{st['export_saves']} export saves, "
              f"{st['export_prefetched']} artifacts compiled")
        return 0
    finally:
        svc.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="Batched DMRG parameter sweeps through the serving queue.",
    )
    ap.add_argument("--model", default="heisenberg",
                    help="registered model name (see repro.serve.MODEL_BUILDERS)")
    ap.add_argument("--n-sites", type=int, default=8)
    ap.add_argument("--max-bond", type=int, default=16)
    ap.add_argument("--sweeps-per-bond", type=int, default=2)
    ap.add_argument("--davidson-iters", type=int, default=6)
    ap.add_argument("--sweep", action="append", default=[], metavar="NAME=a:b:n",
                    help="parameter range (repeat for a cartesian grid)")
    ap.add_argument("--batch", type=int, default=8,
                    help="max batch slot size (padded to powers of two)")
    ap.add_argument("--queue", type=int, default=64,
                    help="admission bound (backpressure threshold)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip precompilation (first batches will retrace)")
    ap.add_argument("--plan-store", metavar="DIR",
                    help="persistent plan + executable store (DESIGN.md 3.9); "
                         "activated for the whole process, primed by warmup")
    ap.add_argument("--warmup", action="append", default=[],
                    metavar="MODEL[,m=BOND][,n=SITES]",
                    help="warmup-only mode: precompile the named model's full "
                         "bond-schedule structure x slot-size set into "
                         "--plan-store, then exit (repeatable)")
    ap.add_argument("--stats-json", metavar="PATH",
                    help="write service + plan-cache stats as JSON ('-' = stdout)")
    ap.add_argument("--checkpoint-dir", metavar="DIR",
                    help="journal undelivered requests here; a restarted "
                         "service with the same dir re-enqueues them")
    ap.add_argument("--check", action="store_true",
                    help="verify vs per-problem solves, zero retraces, and "
                         "a zero recovery ledger (no retries/bisections)")
    args = ap.parse_args(argv)

    from repro.dist import configure_compilation_cache

    configure_compilation_cache()
    if args.warmup:
        return run_warmup(args)

    from repro.core import run_dmrg
    from repro.serve import DEVICE_LOCK, DMRGService, ProblemSpec, group_key
    from repro.serve.problems import build_problem

    grid = build_grid([parse_sweep(s) for s in args.sweep])
    specs = [
        ProblemSpec.make(
            args.model,
            args.n_sites,
            max_bond=args.max_bond,
            sweeps_per_bond=args.sweeps_per_bond,
            davidson_iters=args.davidson_iters,
            **params,
        )
        for params in grid
    ]

    svc = DMRGService(max_batch=args.batch, max_queue=args.queue,
                      checkpoint_dir=args.checkpoint_dir,
                      plan_store=args.plan_store)
    try:
        if not args.no_warmup:
            sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= args.batch]
            t0 = time.perf_counter()
            # warm one spec per distinct group (structure-changing parameters
            # like h=0 vs h!=0 land in different groups)
            seen = set()
            for spec in specs:
                key = group_key(spec, build_problem(spec)[1])
                if key in seen:
                    continue
                seen.add(key)
                svc.warmup(spec, sizes=sizes)
            print(f"warmup: {len(seen)} group(s) x sizes {sizes} in "
                  f"{time.perf_counter() - t0:.1f}s "
                  f"({svc.ops.retraces} traces)")

        rids = [svc.submit(spec, timeout=60.0) for spec in specs]
        print(f"submitted {len(rids)} problems "
              f"(batch<={args.batch}, queue<={args.queue})")

        results = []
        for rid, spec in zip(rids, specs):
            rec = svc.result(rid, timeout=3600.0)
            results.append(rec)
            label = " ".join(f"{k}={v:g}" for k, v in spec.params)
            print(f"  [{rid:3d}] {label:30s} E = {rec['energy']:+.12f}  "
                  f"(bond {rec['max_bond']}, batch {rec['batch_size']})")

        stats = svc.stats()
        print(
            f"served {stats['completed']} problems in "
            f"{stats['solve_seconds']:.2f}s solve time: "
            f"{stats['problems_per_sec']:.2f} problems/sec, "
            f"fill {stats['batch_fill_ratio']:.2f}, "
            f"retraces {stats['retraces']}"
        )
        if args.stats_json:
            payload = json.dumps(stats, indent=2, default=str)
            if args.stats_json == "-":
                print(payload)
            else:
                with open(args.stats_json, "w") as fh:
                    fh.write(payload + "\n")
                print(f"stats written to {args.stats_json}")

        if args.check:
            worst = 0.0
            for spec, rec in zip(specs, results):
                space, mpo = build_problem(spec)
                with DEVICE_LOCK:  # never compile concurrently with the worker
                    ref = run_dmrg(
                        space,
                        None,
                        spec.n_sites,
                        bond_schedule=spec.bond_schedule,
                        sweeps_per_bond=spec.sweeps_per_bond,
                        davidson_iters=spec.davidson_iters,
                        cutoff=spec.cutoff,
                        mpo=mpo,
                        algo="batched",
                        jit_matvec=True,
                    )
                worst = max(worst, abs(rec["energy"] - ref.energy))
            print(f"check: max |E_batched - E_single| = {worst:.3e}")
            if worst >= 1e-10:
                print("CHECK FAILED: batched energies diverge", file=sys.stderr)
                return 1
            if not args.no_warmup and stats["retraces"] != 0:
                print(
                    f"CHECK FAILED: {stats['retraces']} steady-state retraces",
                    file=sys.stderr,
                )
                return 1
            # with no faults armed, a clean sweep must never touch the
            # recovery machinery
            if not stats["faults"]["armed"]:
                ledger = {k: stats[k] for k in
                          ("retries", "bisections", "worker_restarts")}
                if any(ledger.values()):
                    print(f"CHECK FAILED: nonzero recovery ledger {ledger}",
                          file=sys.stderr)
                    return 1
            print("CHECK OK")
        return 0
    finally:
        svc.shutdown()


if __name__ == "__main__":
    sys.exit(main())
