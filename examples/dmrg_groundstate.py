"""End-to-end driver for the paper's own workload: DMRG ground-state search
on the two benchmark systems (spins: 2D J1-J2 Heisenberg; electrons:
triangular Hubbard), with a growing bond-dimension schedule, per-sweep
energy/truncation logging, and a choice of the three contraction algorithms.

    PYTHONPATH=src python examples/dmrg_groundstate.py --system spins \
        --lx 4 --ly 2 --max-bond 32 --algo list
"""
import argparse
import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--system", choices=["spins", "electrons"], default="spins")
    ap.add_argument("--lx", type=int, default=4)
    ap.add_argument("--ly", type=int, default=2)
    ap.add_argument("--max-bond", type=int, default=32)
    ap.add_argument("--sweeps-per-bond", type=int, default=2)
    ap.add_argument("--algo",
                    choices=["list", "dense", "csr", "csr_ref", "batched",
                             "auto", "list_unplanned"],
                    default="list")
    ap.add_argument("--jit-matvec", action="store_true",
                    help="jit the planned two-site matvec")
    ap.add_argument("--no-jit-env", action="store_true",
                    help="disable the fused jitted env updates (engine "
                         "algos default to them; bare algos always use the "
                         "seed extend path)")
    ap.add_argument("--svd-method",
                    choices=["svd", "randomized", "auto", "unplanned"],
                    default=None,
                    help="decomposition stage: planned batched SVD (default "
                         "for engine algos), randomized sketch, cost-model "
                         "auto, or the seed per-sector loop")
    ap.add_argument("--shard", action="store_true",
                    help="mesh-shard blocks over all visible devices "
                         "(pair with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 on CPU)")
    ap.add_argument("--spmd", action="store_true",
                    help="true SPMD execution (docs/distributed.md): "
                         "device-resident envs + shard_map collective "
                         "bucket GEMMs over the (row, col) mesh; implies "
                         "the batched engine path")
    ap.add_argument("--j2", type=float, default=0.5)
    ap.add_argument("--u", type=float, default=8.5)
    ap.add_argument("--check-ed", action="store_true",
                    help="compare against exact diagonalization (small only)")
    ap.add_argument("--stats-json", metavar="PATH",
                    help="write run stats + global plan-cache counters as "
                         "JSON ('-' = stdout)")
    ap.add_argument("--checkpoint-dir", metavar="DIR",
                    help="persist sweep checkpoints here and resume from "
                         "the newest one on restart (README Robustness)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="site updates between mid-sweep checkpoints "
                         "(sweep boundaries always checkpoint)")
    ap.add_argument("--plan-store", metavar="DIR",
                    help="persistent plan + executable store (README Cold "
                         "start): a primed store takes the first sweep from "
                         "~20x steady-state cost to ~2x; a cold run primes it")
    args = ap.parse_args(argv)
    if args.algo.endswith("_unplanned") and (
        args.shard or args.spmd or args.jit_matvec
    ):
        ap.error("--shard/--spmd/--jit-matvec require an engine algo, "
                 "not " + args.algo)
    if args.shard and args.spmd:
        ap.error("--shard (storage mode) and --spmd are mutually exclusive")
    if args.algo.endswith("_unplanned") and args.svd_method not in (
        None, "unplanned",
    ):
        ap.error("--svd-method " + args.svd_method
                 + " requires an engine algo, not " + args.algo)

    from repro.core import run_dmrg
    from repro.core.models import electron_system, spin_system
    from repro.dist import configure_compilation_cache

    configure_compilation_cache()

    if args.system == "spins":
        space, terms = spin_system(args.lx, args.ly, j2=args.j2)
    else:
        space, terms = electron_system(args.lx, args.ly, u=args.u)
    n = args.lx * args.ly

    shard_policy = None
    if args.shard or args.spmd:
        from repro.dist import BlockShardPolicy, make_block_mesh
        shard_policy = BlockShardPolicy(
            make_block_mesh(), mode="spmd" if args.spmd else "auto"
        )

    schedule = [min(8, args.max_bond)]
    while schedule[-1] < args.max_bond:
        schedule.append(min(2 * schedule[-1], args.max_bond))
    print(f"{args.system}: {args.lx}x{args.ly} cylinder, {n} sites, "
          f"algo={'spmd' if args.spmd else args.algo}, schedule={schedule}"
          + (f", mesh={dict(shard_policy.mesh.shape)}" if shard_policy else ""))
    res = run_dmrg(space, terms, n, bond_schedule=schedule,
                   sweeps_per_bond=args.sweeps_per_bond,
                   davidson_iters=4, algo=args.algo, verbose=True,
                   jit_matvec=args.jit_matvec or args.spmd,
                   shard_policy=shard_policy, spmd=args.spmd,
                   svd_method=args.svd_method,
                   jit_env=False if args.no_jit_env
                   or args.algo.endswith("_unplanned") else None,
                   checkpoint_dir=args.checkpoint_dir,
                   checkpoint_every=args.checkpoint_every,
                   plan_store=args.plan_store)
    print(f"\nground-state energy estimate: {res.energy:.10f}")
    print(f"energy per site:              {res.energy / n:.10f}")

    if args.check_ed and n <= 12:
        from repro.core.ed import ground_energy
        from repro.core.mps import neel_states, total_charge
        q = total_charge(space, neel_states(space, n))
        e0 = ground_energy(space, terms, n, charge=q)
        print(f"ED reference:                 {e0:.10f} "
              f"(|err|={abs(res.energy - e0):.2e})")

    if args.stats_json:
        import json

        from repro.dist import cache_stats

        payload = {
            "energy": float(res.energy),
            "energy_per_site": float(res.energy) / n,
            "n_sites": n,
            "algo": args.algo,
            "schedule": schedule,
            "caches": cache_stats(),
        }
        if args.spmd:
            from repro.dist import spmd_stats

            payload["spmd"] = spmd_stats()
        text = json.dumps(payload, indent=2, default=str)
        if args.stats_json == "-":
            print(text)
        else:
            with open(args.stats_json, "w") as fh:
                fh.write(text + "\n")
            print(f"stats written to {args.stats_json}")


if __name__ == "__main__":
    main()
