"""Smoke run of the DMRG solver on a TPU, end to end.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # SPMD on a 2x2 mesh vs one chip

Run it from a checkout: it refuses to start when ``src/repro`` is not next
to it.  One process drives the chip.  Each phase prints one JSON line with
its numbers before the next phase starts (``run_dmrg``'s own per-sweep
progress lines go in between).  One-chip phases, in order:

1. ``device``: what JAX sees; anything but a TPU exits non-zero.
2. ``ed_check``: J1-J2 (J2=0.5) on a 3x2 strip at m=8 (exact at 6 sites)
   against exact diagonalization (``core/ed.py``).
3. ``service``: ``DMRGService`` with a plan store, ``warmup``, then four
   ``j1j2_ladder`` requests (n=6, m=8, J2 from 0.40 to 0.55) in one batch-4
   slot, against independent ``run_dmrg`` solves, with zero retraces after
   warmup.
4. ``deployment``: J1-J2 (J2=0.5) on the paper's 8x6 cylinder (48 sites,
   compressed MPO bond 20), bond doubling from 64 to 1024, one sweep per
   bond, until ``DEADLINE_S`` after start: a sweep still running then is
   cut after its current pair update, and the phase is skipped when fewer
   than ``SETUP_RESERVE_S`` remain for it.  One ``deployment_sweep`` line
   per finished sweep (energy, bond, truncation, seconds, the host-LAPACK
   share, programs compiled, peak device bytes); the energies must be
   finite and non-increasing.
5. ``recovery_ledger``: every retry/degradation counter of every engine
   that ran must be zero — a run that degraded did not run the chip path.

The production single-chip path throughout: ``algo="batched"``,
``jit_matvec=True``, fused jitted env updates, the planned batched SVD, in
the program's float64.  The sizes are set by the TPU compiler, not by the
chip's memory or compute: every padded block structure is its own program,
and in emulated float64 one matvec program of the 8x6 cylinder at m=64
takes ~26 s to compile for a v5e, so a cold compilation cache leaves the
deployment little or no time; a warm one (``JAX_COMPILATION_CACHE_DIR``)
lets it reach further.  ``--four-chips`` runs only the SPMD phase:
``run_dmrg``'s SPMD path on a 2x2 mesh against the one-chip batched path in
the same process, the 8x6 cylinder doubling from 64 to 256 under the same
deadline.

The last line of standard output is ``{"ok": true, "device": {...}}``; any
failure exits non-zero without it.
"""
import argparse
import json
import math
import os
import sys
import tempfile
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, SRC)

ED_TOL = 1e-8          # |E - E_ED| of the ED check
MONOTONE_RTOL = 1e-8   # sweep energies non-increasing within this
EQUAL_TOL = 1e-10      # service vs singles, SPMD vs one chip
SOLVER = dict(algo="batched", jit_matvec=True)
DAVIDSON_ITERS = 3     # run_dmrg's default
# seconds after start: no pair update of the deployment starts after this.
# A run gets 1200 s, and one cold pair update of the 8x6 at m <= 64
# compiles for up to ~80 s (env core ~41 s, matvec core ~26 s on a v5e)
DEADLINE_S = 1020.0
# the deployment starts only with this much left before the deadline: its
# set-up (MPO compression, first right environments) builds ~340 programs
# and cannot be cut, so it has to end well inside the run's limit
SETUP_RESERVE_S = 560.0


def emit(phase, **rec):
    print(json.dumps({"phase": phase, **rec}, default=float), flush=True)


def elapsed():
    return time.monotonic() - T0


class CompileMeter:
    """Counts XLA programs built, their seconds and compile-cache hits.

    JAX reports a backend-compile duration for every program it builds,
    cache hits included (then the seconds are the load), so the programs
    actually compiled are ``programs - cache hits``.
    """

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.BACKEND_COMPILE:
                self.programs += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == self.CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, snap):
        p, s, h = snap
        programs, hits = self.programs - p, self.cache_hits - h
        return {
            "programs": programs,
            "compiled_programs": programs - hits,
            "compile_cache_hits": hits,
            "compile_s": self.seconds - s,
        }


def peak_bytes(devices=None):
    import jax

    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def ledger_of(engine_stats, pair_retries=0):
    """The non-zero recovery counters of one engine ledger (empty = clean)."""
    bad = {}
    for k, v in engine_stats["retries"].items():
        if v:
            bad[f"retries.{k}"] = v
    for k, v in engine_stats["degradations"].items():
        if v:
            bad[f"degradations.{k}"] = v
    d = engine_stats["decomp"]
    if d["retries"]:
        bad["decomp.retries"] = d["retries"]
    for k, v in d["degradations"].items():
        if v:
            bad[f"decomp.degradations.{k}"] = v
    if pair_retries:
        bad["pair_retries"] = pair_retries
    return bad


def sweep_record(s, host_svd_s=None):
    rec = {
        "energy": s.energy,
        "max_bond": s.max_bond,
        "trunc_err": s.trunc_err,
        "seconds": s.seconds,
        "svd_seconds": s.svd_seconds,
        "env_seconds": s.env_seconds,
        "svd_share": s.svd_seconds / s.seconds if s.seconds else None,
    }
    if host_svd_s is not None:
        rec["host_lapack_seconds"] = host_svd_s
        rec["host_lapack_share"] = host_svd_s / s.seconds if s.seconds else None
    return rec


def doubling(lo, hi):
    out, m = [], lo
    while m < hi:
        out.append(m)
        m *= 2
    return out + [hi]


def check_monotone(energies):
    if not all(math.isfinite(e) for e in energies):
        raise RuntimeError(f"non-finite sweep energy: {energies}")
    for a, b in zip(energies, energies[1:]):
        if b > a + MONOTONE_RTOL * abs(a):
            raise RuntimeError(f"sweep energy rose: {a!r} -> {b!r}")


class OutOfTime(Exception):
    """Raised between pair updates once the run's deadline has passed."""


def cylinder_engine(lx, ly, **engine_kw):
    """A ``DMRGEngine`` on the J1-J2 cylinder, set up as ``run_dmrg`` does
    (compressed MPO, Neel product state)."""
    from repro.core.models import spin_system
    from repro.core.mpo import build_mpo, compress_mpo
    from repro.core.mps import neel_states, product_state_mps
    from repro.core.sweep import DMRGEngine

    n = lx * ly
    space, terms = spin_system(lx, ly, j2=0.5)
    mpo = compress_mpo(build_mpo(space, terms, n), cutoff=1e-13)
    mps = product_state_mps(space, neel_states(space, n))
    return DMRGEngine(mps, mpo, davidson_iters=DAVIDSON_ITERS, **engine_kw)


def sweep_until(engine, schedule, deadline, on_sweep=None):
    """One sweep per bond of ``schedule`` on ``engine`` while time remains.

    Returns ``(stats, cut)``: the finished sweeps' ``SweepStats`` and why
    the schedule stopped early (None when it ran to its end).  A sweep still
    running at ``deadline`` (seconds since start) stops after its current
    pair update and is dropped.
    """
    from repro.dist.decomp import host_svd_stats

    def on_site(_state):
        if elapsed() > deadline:
            raise OutOfTime

    stats = []
    for m in schedule:
        if elapsed() > deadline:
            return stats, f"deadline {deadline:.0f} s passed before the m={m} sweep"
        host0 = host_svd_stats()["seconds"]
        try:
            s = engine.sweep(max_bond=m, on_site=on_site)
        except OutOfTime:
            return stats, (
                f"deadline {deadline:.0f} s passed inside the m={m} sweep"
            )
        stats.append(s)
        if on_sweep is not None:
            on_sweep(m, s, host_svd_stats()["seconds"] - host0)
    return stats, None


# ------------------------------------------------------------------- phases
def device_phase():
    import jax

    devs = jax.devices()
    rec = {
        "devices": [str(d) for d in devs],
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64),
    }
    emit("device", **rec)
    return rec


def ed_phase(meter, lx=3, ly=2, schedule=(8,), sweeps_per_bond=3):
    from repro.core import run_dmrg
    from repro.core.ed import ground_energy
    from repro.core.models import spin_system
    from repro.core.mps import neel_states, total_charge

    n = lx * ly
    space, terms = spin_system(lx, ly, j2=0.5)
    snap, t0 = meter.snapshot(), time.perf_counter()
    res = run_dmrg(space, terms, n, bond_schedule=schedule,
                   sweeps_per_bond=sweeps_per_bond, verbose=True, **SOLVER)
    solve_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    e_ed = ground_energy(space, terms, n,
                         charge=total_charge(space, neel_states(space, n)))
    err = abs(res.energy - e_ed)
    rec = dict(lattice=f"{lx}x{ly}", n_sites=n, schedule=list(schedule),
               energy=res.energy, e_ed=e_ed, abs_err=err, solve_s=solve_s,
               ed_s=time.perf_counter() - t0,
               sweeps=[sweep_record(s) for s in res.sweep_stats],
               peak_bytes_in_use=peak_bytes(), **meter.since(snap))
    emit("ed_check", **rec)
    if not err <= ED_TOL:
        raise RuntimeError(f"ED check failed: |E - E_ED| = {err:.3e} > {ED_TOL}")
    return res


def deployment_phase(meter, lx=8, ly=6, schedule=None, deadline=DEADLINE_S):
    """The 8x6 cylinder under ``deadline``; returns a ``DMRGResult`` for the
    recovery ledger (None when the phase was skipped)."""
    from repro.core.dmrg import DMRGResult

    schedule = list(schedule or doubling(64, 1024))
    base = dict(lattice=f"{lx}x{ly}", n_sites=lx * ly, schedule=schedule,
                deadline_s=deadline)
    if deadline - elapsed() < SETUP_RESERVE_S:
        emit("deployment", **base, completed_sweeps=0, reached_bond=None,
             cut=f"skipped: {elapsed():.1f} s gone, fewer than "
                 f"{SETUP_RESERVE_S:.0f} s left before the deadline")
        return None
    snap, t0 = meter.snapshot(), time.perf_counter()
    eng = cylinder_engine(lx, ly, **SOLVER)
    setup_s = time.perf_counter() - t0
    emit("deployment_setup", mpo_bond=max(w.indices[0].dim for w in eng.mpo),
         setup_s=setup_s, **meter.since(snap))

    def on_sweep(m, s, host_s):
        emit("deployment_sweep", bond_schedule=m, **sweep_record(s, host_s),
             peak_bytes_in_use=peak_bytes(), **meter.since(sweep_snap[0]))
        sweep_snap[0] = meter.snapshot()

    sweep_snap = [meter.snapshot()]
    stats, cut = sweep_until(eng, schedule, deadline, on_sweep)
    energies = [s.energy for s in stats]
    emit("deployment", **base, completed_sweeps=len(stats),
         reached_bond=stats[-1].max_bond if stats else None, cut=cut,
         energies=energies, setup_s=setup_s,
         total_s=time.perf_counter() - t0, peak_bytes_in_use=peak_bytes(),
         **meter.since(snap))
    check_monotone(energies)
    return DMRGResult(energy=energies[-1] if stats else float("nan"),
                      mps=eng.mps, sweep_stats=stats,
                      engine_stats=eng.contract_fn.stats())


def service_phase(meter, n=6, max_bond=8, j2s=(0.40, 0.45, 0.50, 0.55)):
    from repro.core import run_dmrg
    from repro.dist import persist
    from repro.serve import DEVICE_LOCK, DMRGService, ProblemSpec
    from repro.serve.problems import build_problem

    specs = [ProblemSpec.make("j1j2_ladder", n, J1=1.0, J2=j2,
                              max_bond=max_bond) for j2 in j2s]
    snap, t0 = meter.snapshot(), time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as store:
        # a long batch wait: the slot is cut the moment all four requests
        # are queued, never as a ragged partial slot
        svc = DMRGService(max_batch=len(specs), batch_wait_s=600.0,
                          plan_store=store)
        try:
            svc.warmup(specs[0], sizes=(len(specs),))
            warm_s = time.perf_counter() - t0
            t1 = time.perf_counter()
            rids = [svc.submit(s) for s in specs]
            recs = [svc.result(r, timeout=900.0) for r in rids]
            serve_s = time.perf_counter() - t1
            st = svc.stats()
        finally:
            svc.shutdown()
            persist.deactivate_store()
    singles, diffs = [], []
    for spec, r in zip(specs, recs):
        if r["status"] != "done":
            raise RuntimeError(f"service request failed: {r}")
        space, mpo = build_problem(spec)
        with DEVICE_LOCK:
            ref = run_dmrg(space, None, spec.n_sites,
                           bond_schedule=spec.bond_schedule,
                           sweeps_per_bond=spec.sweeps_per_bond,
                           davidson_iters=spec.davidson_iters,
                           cutoff=spec.cutoff, mpo=mpo, **SOLVER)
        singles.append(ref)
        diffs.append(abs(r["energy"] - ref.energy))
    rec = dict(model="j1j2_ladder", n_sites=n, max_bond=max_bond,
               batch=len(specs), energies=[r["energy"] for r in recs],
               single_energies=[s.energy for s in singles],
               max_abs_diff=max(diffs), retraces_after_warmup=st["retraces"],
               slots=st["slots"], batch_fill_ratio=st["batch_fill_ratio"],
               warmup_s=warm_s, serve_s=serve_s,
               peak_bytes_in_use=peak_bytes(), **meter.since(snap))
    emit("service", **rec)
    if not max(diffs) <= EQUAL_TOL:
        raise RuntimeError(f"service vs singles differ by {max(diffs):.3e}")
    if st["retraces"] != 0:
        raise RuntimeError(f"{st['retraces']} retraces after warmup")
    return st, singles


def recovery_phase(solves, service_stats):
    """``solves``: (name, DMRGResult) pairs; ``service_stats``: svc.stats()."""
    bad = {}
    for name, res in solves:
        pair = sum(s.pair_retries for s in res.sweep_stats)
        for k, v in ledger_of(res.engine_stats, pair).items():
            bad[f"{name}.{k}"] = v
    if service_stats is not None:
        for k in ("failed", "retries", "bisections", "worker_restarts"):
            if service_stats[k]:
                bad[f"service.{k}"] = service_stats[k]
        for eng in service_stats["plan_caches"].get("engines", []):
            for k, v in ledger_of(eng).items():
                bad[f"service.{k}"] = v
    names = [n for n, _ in solves] + (["service"] if service_stats else [])
    emit("recovery_ledger", engines=names, nonzero=bad)
    if bad:
        raise RuntimeError(f"recovery ledger not clean: {bad}")


def four_chip_phase(meter, lx=8, ly=6, schedule=None, deadline=DEADLINE_S):
    """SPMD on a 2x2 mesh until half the time left to ``deadline``, then
    the one-chip batched path over the same finished sweeps."""
    import jax

    from repro.core.dmrg import DMRGResult
    from repro.dist import BlockShardPolicy, make_block_mesh, spmd_stats

    devs = jax.devices()
    if len(devs) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    schedule = list(schedule or doubling(64, 256))
    policy = BlockShardPolicy(make_block_mesh(), mode="spmd")
    snap, t0 = meter.snapshot(), time.perf_counter()
    # run_dmrg(spmd=True) builds exactly this engine: the SPMD policy and
    # the jitted padded matvec it implies
    spmd = cylinder_engine(lx, ly, algo="batched", jit_matvec=True,
                           shard_policy=policy)
    spmd_deadline = elapsed() + (deadline - elapsed()) / 2
    spmd_stats_, cut = sweep_until(spmd, schedule, spmd_deadline)
    spmd_s = time.perf_counter() - t0
    if not spmd_stats_:
        raise RuntimeError(f"no SPMD sweep finished: {cut}")
    done = schedule[:len(spmd_stats_)]
    t0 = time.perf_counter()
    one = cylinder_engine(lx, ly, **SOLVER)
    one_stats, _ = sweep_until(one, done, float("inf"))
    one_s = time.perf_counter() - t0
    one_e = [s.energy for s in one_stats]
    spmd_e = [s.energy for s in spmd_stats_]
    diffs = [abs(a - b) for a, b in zip(one_e, spmd_e)]
    # where the SPMD run's stored tensors live: every env and MPS block
    # must span the whole mesh, not sit on device 0
    env_devices = sorted({len(b.sharding.device_set)
                          for env in spmd.left_envs + spmd.right_envs
                          if env is not None for b in env.blocks.values()})
    mps_devices = sorted({len(b.sharding.device_set)
                          for t in spmd.mps.tensors for b in t.blocks.values()})
    peaks = peak_bytes(devs)
    rec = dict(lattice=f"{lx}x{ly}", schedule=schedule, finished=done,
               cut=cut, mesh=dict(policy.mesh.shape),
               one_chip_energies=one_e, spmd_energies=spmd_e,
               max_abs_diff=max(diffs), one_chip_s=one_s, spmd_s=spmd_s,
               spmd_sweeps=[sweep_record(s) for s in spmd_stats_],
               spmd_stats=spmd_stats(), env_device_set_sizes=env_devices,
               mps_device_set_sizes=mps_devices, peak_bytes_in_use=peaks,
               **meter.since(snap))
    emit("four_chips", **rec)
    if not max(diffs) <= EQUAL_TOL:
        raise RuntimeError(f"SPMD vs one chip differ by {max(diffs):.3e}")
    if env_devices != [4] or mps_devices != [4]:
        raise RuntimeError(
            f"stored tensors not on all 4 devices: env {env_devices}, "
            f"mps {mps_devices}")
    if any(p == 0 for p in peaks):
        raise RuntimeError(f"a device held nothing: peak bytes {peaks}")
    return [
        (name, DMRGResult(energy=st[-1].energy, mps=eng.mps, sweep_stats=st,
                          engine_stats=eng.contract_fn.stats()))
        for name, eng, st in (("one_chip", one, one_stats),
                              ("spmd", spmd, spmd_stats_))
    ]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD phase on a 2x2 mesh")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no checkout here ({SRC}/repro is missing)",
              file=sys.stderr)
        return 2

    import jax

    from repro.dist import configure_compilation_cache

    dev = device_phase()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    emit("compile_cache", dir=configure_compilation_cache())
    meter = CompileMeter()
    if args.four_chips:
        solves = four_chip_phase(meter)
        recovery_phase(solves, None)
    else:
        solves = [("ed_check", ed_phase(meter))]
        svc_stats, singles = service_phase(meter)
        solves += [(f"single{i}", r) for i, r in enumerate(singles)]
        dep = deployment_phase(meter)
        if dep is not None:
            solves.append(("deployment", dep))
        recovery_phase(solves, svc_stats)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
