"""Distributed contraction engine: plan-cache, batching and jit benchmarks.

Weak-scaling style run on a 16-site m=32 Heisenberg chain comparing

- seed per-call contraction (``list_unplanned``) vs the plan-cached engine
  (``list``) vs the shape-bucketed batched backend and the compile-once
  (bucket-padded) jitted matvec, plus "auto" and an 8-fake-device
  mesh-sharded sweep (energy must match single-device).

Every configuration is swept to structural steady state (block structures
drift while the wavefunction converges, retracing jitted code and churning
plans) and reports **compile/warmup and steady-state separately**:
``*_first_sweep_s`` is the cold first sweep, ``*_sweep_s`` the mean of the
last ``TIMED`` sweeps, and jitted configs also record how many matvec
retraces happened inside the timed window (0 == compile-once achieved).

The run also splits each steady-state sweep into its three pipeline stages —
contraction+Davidson vs decomposition (``*_decomp_stage_s``, the summed
``svd_split`` wall time per sweep) vs environment updates
(``*_env_stage_s``, the summed left/right env-update wall time per sweep) —
and runs two dedicated stage microbenches: decomposition at m=64 (seed
per-sector loop vs planned batched engine, ``decomp_stage`` in the JSON)
and the environment stage at m=32 (``env_stage``): full left+right env
rebuild passes over the converged state through the eager three-call
``extend_left``/``extend_right`` path vs the fused jitted environment
engine (``dist/envcore.py``), asserting block-for-block agreement to
<1e-10 and zero retraces inside the timed window, and recording the stage
speedup.

Emits CSV rows (via benchmarks/run.py) and a JSON record at
``benchmarks/bench_dist.json`` so future PRs have a perf trajectory.  Must
run in its own process with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set *before* jax imports; ``main()`` below re-execs itself accordingly and
run.py invokes it as a subprocess.

``--quick`` (used by CI) runs only the acceptance-critical configurations
on the same workload — eager planned vs batched+jit vs list+jit — so its
``planned_sweep_s`` is directly comparable with the checked-in record;
``--check PATH`` exits nonzero if ``planned_sweep_s`` regressed more than
2x vs the record at PATH.

``--coldstart`` runs only the **cold-start leg** (also part of the full
run, ``cold_start`` in the JSON): two fresh subprocesses sharing one plan
store (``dist/persist.py``).  Process A sweeps against the empty store
(priming it) and finishes with the blocking export-compile pass — the
warmup contract from README "Cold start".  Process B activates the primed
store and must reach its first sweep with **zero plan builds** and within
a small multiple of steady state, vs the ~20x cost process A paid.  The
leg asserts builds==0 and primed/cold energy equality <1e-10 outright;
``--check`` additionally gates ``primed_first_s`` at 2x the checked-in
record.  The record is written to ``benchmarks/bench_coldstart.json``
(untracked; uploaded as a CI artifact by the ``coldstart`` job).

``--spmd`` runs only the **weak-scaling leg** (also part of the full run,
``weak_scaling`` in the JSON): one fresh subprocess per fake-device count
in {1, 2, 4, 8}, each sweeping the cold-start workload in true SPMD mode
(``run_dmrg(spmd=True)`` semantics: device-resident replicated block
storage + per-bucket shard_map collective GEMMs, docs/distributed.md)
against the single-program list reference.  Every count asserts energy
equality <1e-10 and zero compiled-SPMD-program growth inside the timed
window; the 4-device leg additionally times the gather-to-host baseline
(same batched algorithm, storage-mode policy) and asserts the SPMD sweep
is >=5x faster.  The record is written to ``benchmarks/bench_spmd.json``
(untracked; uploaded as a CI artifact by the ``spmd`` job); ``--check``
gates the 4-device ``spmd_steady_s`` at 2x the checked-in record.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_XLA_FLAG = "--xla_force_host_platform_device_count=8"

WARM = 4   # sweeps to reach structural steady state
TIMED = 2  # sweeps averaged for the steady-state number


def _bench_decomp_stage(fresh_engine, n, m64=64, warm_sweeps=3, reps=3):
    """Decomposition-stage microbench at m=64: seed loop vs planned engine.

    Converges a run at bond 64, rebuilds every pair tensor theta_j =
    T_j · T_{j+1}, and times the full set of splits through the seed
    per-sector loop (``svd_split_unplanned``) vs the planned batched engine
    (jit-warmed), blocking on every output block so jax's async dispatch
    cannot hide device work.  Asserts the two paths' absorbed products agree
    block-for-block to <1e-10 first (the gauge-invariant equality check).
    """
    import numpy as np

    from repro.dist.decomp import DecompositionEngine
    from repro.dist.plan import DecompPlanCache
    from repro.tensor.blocksparse import contract, svd_split_unplanned

    eng = fresh_engine(algo="list")
    for _ in range(warm_sweeps):
        eng.sweep(max_bond=m64)
    T = eng.mps.tensors
    thetas = [eng.contract_fn(T[j], T[j + 1], ((2,), (0,))) for j in range(n - 1)]

    deng = DecompositionEngine(cache=DecompPlanCache())

    def run_all(split):
        outs = [split(th, 2, m64)[:2] for th in thetas]
        for U, V in outs:
            for b in U.blocks.values():
                b.block_until_ready()
            for b in V.blocks.values():
                b.block_until_ready()
        return outs

    ref = run_all(svd_split_unplanned)  # warm numpy/lazy caches
    got = run_all(deng.svd_split)       # build plans + compile cores
    max_diff = 0.0
    for (Ur, Vr), (Up, Vp) in zip(ref, got):
        pr = np.asarray(contract(Ur, Vr, ((2,), (0,))).to_dense())
        pp = np.asarray(contract(Up, Vp, ((2,), (0,))).to_dense())
        max_diff = max(max_diff, float(np.max(np.abs(pr - pp))))
    assert max_diff < 1e-10, f"planned/seed split products diverge: {max_diff}"

    t0 = time.perf_counter()
    for _ in range(reps):
        run_all(svd_split_unplanned)
    seed_s = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_all(deng.svd_split)
    planned_s = (time.perf_counter() - t0) / reps
    return {
        "max_bond": m64,
        "n_thetas": len(thetas),
        "reps": reps,
        "seed_per_sector_s": seed_s,
        "planned_batched_s": planned_s,
        "speedup": seed_s / max(planned_s, 1e-12),
        "max_product_diff": max_diff,
        "decomp_stats": deng.stats(),
    }


def _bench_env_stage(fresh_engine, n, m=32, warm_sweeps=4, reps=5):
    """Environment-stage microbench at m=32: eager three-call vs fused jit.

    Converges a run at bond m, then times full environment rebuild passes —
    a left-to-right pass of ``extend_left`` plus a right-to-left pass of
    ``extend_right`` over every site — through (a) the seed-shaped eager
    path (three chained plan-cached engine calls per site) and (b) the
    fused jitted ``EnvironmentEngine`` on padded operands (one compiled
    call per site).  Asserts the two paths agree block-for-block to <1e-10
    and that the fused path triggers zero retraces inside the timed window,
    blocking on every env block so async dispatch cannot hide device work.
    """
    import numpy as np

    from repro.core.env import extend_left, extend_right, left_edge, right_edge
    from repro.dist.envcore import EnvironmentEngine
    from repro.dist.plan import EnvPlanCache

    eng = fresh_engine(algo="list", jit_env=False)
    for _ in range(warm_sweeps):
        eng.sweep(max_bond=m)
    T, W = eng.mps.tensors, eng.mpo
    ceng = eng.contract_fn  # the warm plan-cached eager engine
    fused = EnvironmentEngine(cache=EnvPlanCache())

    def block(envs):
        for t in envs:
            for b in t.blocks.values():
                b.block_until_ready()
        return envs

    def eager_pass():
        envs = []
        A = left_edge(T[0], W[0])
        for j in range(n - 1):
            A = extend_left(A, T[j], W[j], ceng)
            envs.append(A)
        B = right_edge(T[n - 1], W[n - 1])
        for j in range(n - 1, 0, -1):
            B = extend_right(B, T[j], W[j], ceng)
            envs.append(B)
        return block(envs)

    def fused_pass():
        envs = []
        A = left_edge(T[0], W[0])
        for j in range(n - 1):
            A = fused.update_left(A, T[j], W[j])
            envs.append(A)
        B = right_edge(T[n - 1], W[n - 1])
        for j in range(n - 1, 0, -1):
            B = fused.update_right(B, T[j], W[j])
            envs.append(B)
        return block(envs)

    ref = eager_pass()           # warm eager plans
    got = fused_pass()           # build env plans + compile fused cores
    max_diff = 0.0
    for tr, tf in zip(ref, got):
        assert set(tr.blocks) == set(tf.blocks)
        for k in tr.blocks:
            max_diff = max(max_diff, float(np.max(np.abs(
                np.asarray(tr.blocks[k]) - np.asarray(tf.blocks[k])
            ))))
    assert max_diff < 1e-10, f"fused/eager env updates diverge: {max_diff}"

    t0 = time.perf_counter()
    for _ in range(reps):
        eager_pass()
    eager_s = (time.perf_counter() - t0) / reps
    rt0 = fused.jit_retraces
    t0 = time.perf_counter()
    for _ in range(reps):
        fused_pass()
    fused_s = (time.perf_counter() - t0) / reps
    timed_retraces = fused.jit_retraces - rt0
    assert timed_retraces == 0, f"env core retraced in timed window: {timed_retraces}"
    return {
        "max_bond": m,
        "n_updates": 2 * (n - 1),
        "reps": reps,
        "eager_three_call_s": eager_s,
        "fused_jit_s": fused_s,
        "speedup": eager_s / max(fused_s, 1e-12),
        "timed_retraces": timed_retraces,
        "max_block_diff": max_diff,
        "env_stats": fused.stats(),
    }


def _bench(n=16, m=32, quick=False):
    import jax

    from repro.core.models import heisenberg_j1j2_terms
    from repro.core.mpo import build_mpo, compress_mpo
    from repro.core.mps import neel_states, product_state_mps
    from repro.core.siteops import spin_half_space
    from repro.core.sweep import DMRGEngine
    from repro.dist import BlockShardPolicy, make_block_mesh
    from repro.dist.engine import ContractionEngine
    from repro.dist.plan import PlanCache

    sp = spin_half_space()
    terms = heisenberg_j1j2_terms(n // 2, 2, 1.0, 0.5, cylinder=False)
    mpo = compress_mpo(build_mpo(sp, terms, n), cutoff=1e-13)

    def fresh_engine(**kw):
        mps = product_state_mps(sp, neel_states(sp, n))
        return DMRGEngine(mps, mpo, davidson_iters=2, **kw)

    def timed_sweeps(eng, warm=WARM, timed=TIMED, bond=m):
        """(first_sweep_s, steady_sweep_s, energy, timed-window retraces,
        steady decomposition-stage seconds per sweep, steady env-stage
        seconds per sweep)."""
        t0 = time.perf_counter()
        eng.sweep(max_bond=bond)
        first = time.perf_counter() - t0
        for _ in range(warm - 1):
            eng.sweep(max_bond=bond)
        rt0 = getattr(eng.contract_fn, "jit_retraces", 0)
        t0 = time.perf_counter()
        svd_s = 0.0
        env_s = 0.0
        for _ in range(timed):
            s = eng.sweep(max_bond=bond)
            svd_s += s.svd_seconds
            env_s += s.env_seconds
        steady = (time.perf_counter() - t0) / timed
        rt1 = getattr(eng.contract_fn, "jit_retraces", 0)
        return first, steady, float(s.energy), rt1 - rt0, svd_s / timed, env_s / timed

    rec = {
        "n_sites": n,
        "max_bond": m,
        "devices": jax.device_count(),
        "warm_sweeps": WARM,
        "timed_sweeps": TIMED,
        "quick": quick,
    }

    # eager reference config: plan-cached engine, no jit anywhere — its env
    # stage is the seed-shaped three-call extend path, the A/B baseline for
    # the fused env numbers below
    cache = PlanCache()
    eng = fresh_engine(
        engine=ContractionEngine(backend="list", cache=cache), jit_env=False
    )
    t1_plan, t_plan, e_plan, _, d_plan, v_plan = timed_sweeps(eng)
    rec["planned_first_sweep_s"] = t1_plan
    rec["planned_sweep_s"] = t_plan
    # stage split: decomposition (svd_split wall clock) + environment
    # (env-update wall clock) vs everything else (contraction + Davidson)
    rec["planned_decomp_stage_s"] = d_plan
    rec["planned_env_stage_s"] = v_plan
    rec["planned_contract_stage_s"] = t_plan - d_plan - v_plan
    rec["planned_decomp_stats"] = eng.contract_fn.stats()["decomp"]
    rec["plan_cache"] = cache.stats()
    rec["energy"] = e_plan

    # tentpole config: shape-bucketed batched backend + compile-once
    # (bucket-padded) jitted matvec
    eng = fresh_engine(algo="batched", jit_matvec=True)
    t1_b, t_b, e_b, rt_b, d_b, v_b = timed_sweeps(eng)
    rec["batched_first_sweep_s"] = t1_b
    rec["batched_sweep_s"] = t_b
    rec["batched_decomp_stage_s"] = d_b
    rec["batched_env_stage_s"] = v_b
    rec["batched_contract_stage_s"] = t_b - d_b - v_b
    rec["batched_timed_retraces"] = rt_b
    rec["batched_total_retraces"] = eng.contract_fn.jit_retraces
    rec["batched_svd_retraces"] = eng.contract_fn.decomp.jit_retraces
    rec["batched_env_retraces"] = eng.contract_fn.env.jit_retraces
    rec["batched_env_stats"] = eng.contract_fn.stats()["env"]
    # robustness ledger: no faults are armed here, so the degradation
    # ladder must stay untouched — any nonzero counter means a backend
    # silently failed and fell back, which would skew every timing above
    st_b = eng.contract_fn.stats()
    rec["recovery_ledger"] = {
        "engine_retries": dict(st_b["retries"]),
        "engine_degradations": dict(st_b["degradations"]),
        "decomp_retries": st_b["decomp"]["retries"],
        "decomp_degradations": dict(st_b["decomp"]["degradations"]),
    }
    assert not any(st_b["retries"].values()), rec["recovery_ledger"]
    assert not any(st_b["degradations"].values()), rec["recovery_ledger"]
    assert st_b["decomp"]["retries"] == 0, rec["recovery_ledger"]
    assert not any(st_b["decomp"]["degradations"].values()), rec["recovery_ledger"]
    rec["batched_speedup"] = t_plan / max(t_b, 1e-12)
    rec["batched_energy_diff"] = abs(e_b - e_plan)
    # fused-vs-eager env stage inside full sweeps (the microbench below
    # isolates the same comparison on identical tensors)
    rec["env_stage_sweep_speedup"] = v_plan / max(v_b, 1e-12)

    eng = fresh_engine(algo="list", jit_matvec=True)
    t1_jit, t_jit, e_jit, rt_jit, _, _ = timed_sweeps(eng)
    rec["planned_jit_first_sweep_s"] = t1_jit
    rec["planned_jit_sweep_s"] = t_jit
    rec["planned_jit_timed_retraces"] = rt_jit
    rec["planned_jit_total_retraces"] = eng.contract_fn.jit_retraces
    rec["jit_speedup"] = t_plan / max(t_jit, 1e-12)

    assert abs(e_b - e_plan) < 1e-10, (e_b, e_plan)
    assert abs(e_jit - e_plan) < 1e-10, (e_jit, e_plan)

    rec["decomp_stage"] = _bench_decomp_stage(fresh_engine, n)
    rec["env_stage"] = _bench_env_stage(fresh_engine, n, m)

    if not quick:
        # the seed per-call algorithm is ~20x the planned engine, so it is
        # sampled at sweep 2 (warm=1, timed=1) rather than swept to steady
        # state — the ratio is labeled with its protocol
        t1_seed, t_seed, e_seed, _, _, _ = timed_sweeps(
            fresh_engine(algo="list_unplanned"), warm=1, timed=1
        )
        rec["seed_unplanned_sweep_s"] = t_seed
        rec["seed_unplanned_protocol"] = {"warm": 1, "timed": 1}
        # like-for-like ratio: planned engine sampled at the same sweep 2
        _, t_plan2, e_plan2, _, _, _ = timed_sweeps(
            fresh_engine(algo="list", jit_env=False), warm=1, timed=1
        )
        rec["planned_sweep2_s"] = t_plan2
        rec["plan_speedup_sweep2"] = t_seed / max(t_plan2, 1e-12)

        eng = fresh_engine(algo="batched")
        _, t_be, e_be, _, _, _ = timed_sweeps(eng)
        rec["batched_eager_sweep_s"] = t_be

        _, t_auto, e_auto, _, _, _ = timed_sweeps(fresh_engine(algo="auto"))
        rec["auto_sweep_s"] = t_auto

        # sharded smoke on a reduced workload: on fake CPU devices the
        # storage-mode gathers dominate (~30x), so this records energy
        # equality plus a small timing sample, not a steady-state number
        ns, ms = 8, 16
        mps = product_state_mps(sp, neel_states(sp, ns))
        terms_s = heisenberg_j1j2_terms(ns // 2, 2, 1.0, 0.5, cylinder=False)
        mpo_s = compress_mpo(build_mpo(sp, terms_s, ns), cutoff=1e-13)
        single = DMRGEngine(mps, mpo_s, davidson_iters=2, algo="list")
        for _ in range(2):
            s_single = single.sweep(max_bond=ms)
        policy = BlockShardPolicy(make_block_mesh())
        sharded = DMRGEngine(
            product_state_mps(sp, neel_states(sp, ns)),
            mpo_s,
            davidson_iters=2,
            algo="list",
            shard_policy=policy,
        )
        sharded.sweep(max_bond=ms)
        t0 = time.perf_counter()
        s_shard = sharded.sweep(max_bond=ms)
        rec["sharded_smoke"] = {
            "n_sites": ns,
            "max_bond": ms,
            "sweep_s": time.perf_counter() - t0,
            "energy_diff": abs(float(s_shard.energy) - float(s_single.energy)),
        }
        assert rec["sharded_smoke"]["energy_diff"] < 1e-10, rec["sharded_smoke"]
        # seed and planned follow the same trajectory sweep-for-sweep
        assert abs(e_seed - e_plan2) < 1e-10, (e_seed, e_plan2)
        assert abs(e_be - e_plan) < 1e-10, (e_be, e_plan)
        assert abs(e_auto - e_plan) < 1e-8, (e_auto, e_plan)
    return rec


def _child_main():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    rec = _bench(quick="--quick" in sys.argv)
    print("BENCH_DIST_JSON " + json.dumps(rec))


# ----------------------------------------------------------- cold-start leg

COLD_N = 8    # cold-start workload: small enough that the priming run and
COLD_M = 16   # its export-compile pass stay in CI budget, large enough that
              # plan building + compilation dominate a cold first sweep


def _bench_coldstart(store_dir, phase):
    """One cold-start subprocess: sweep the workload against ``store_dir``.

    ``phase="cold"``: the store is empty — this run primes it (plans +
    export artifacts saved as they are built) and finishes with the
    blocking ``prefetch_exports(compile=True)`` pass, which precompiles
    the deserialized-artifact wrappers into the persistent XLA cache (the
    second half of the warmup contract; without it a later process pays
    fresh XLA compiles for the wrapped modules).

    ``phase="primed"``: a fresh process against the primed store — the
    blocking compile prefetch runs first (worker-startup cost, reported
    separately), then the first sweep must find every plan and executable
    ready: zero plan builds, small first/steady ratio.
    """
    from repro.core.models import heisenberg_j1j2_terms
    from repro.core.mpo import build_mpo, compress_mpo
    from repro.core.mps import neel_states, product_state_mps
    from repro.core.siteops import spin_half_space
    import jax

    from repro.core.sweep import DMRGEngine
    from repro.dist import cache_stats, persist

    cache_hits = [0]

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    n, m = COLD_N, COLD_M
    sp = spin_half_space()
    terms = heisenberg_j1j2_terms(n // 2, 2, 1.0, 0.5, cylinder=False)
    # activate BEFORE building the MPO: compression itself runs plan-cached
    # contractions, and those plans must round-trip too (run_dmrg orders the
    # activation the same way)
    store = persist.activate_store(store_dir, prefetch=False)
    mpo = compress_mpo(build_mpo(sp, terms, n), cutoff=1e-13)

    prefetch_s = 0.0
    if phase == "primed":
        t0 = time.perf_counter()
        store.prefetch_exports(compile=True, block=True)
        prefetch_s = time.perf_counter() - t0

    mps = product_state_mps(sp, neel_states(sp, n))
    eng = DMRGEngine(mps, mpo, davidson_iters=2, algo="batched",
                     jit_matvec=True)
    t0 = time.perf_counter()
    s = eng.sweep(max_bond=m)
    first = time.perf_counter() - t0
    for _ in range(WARM - 1):
        eng.sweep(max_bond=m)
    t0 = time.perf_counter()
    for _ in range(TIMED):
        s = eng.sweep(max_bond=m)
    steady = (time.perf_counter() - t0) / TIMED

    if phase == "cold":
        # the warmup contract's second half: compile every artifact this
        # run just exported, so the primed process's wrappers hit the
        # persistent XLA cache instead of recompiling
        t0 = time.perf_counter()
        store.prefetch_exports(compile=True, block=True)
        prefetch_s = time.perf_counter() - t0

    st = cache_stats()
    return {
        "phase": phase,
        "first_s": first,
        "steady_s": steady,
        "prefetch_compile_s": prefetch_s,
        "energy": float(s.energy),
        "plan_builds": sum(
            st[k]["builds"]
            for k in ("plan_cache", "decomp_plan_cache", "env_plan_cache")
        ),
        "store": st["plan_store"],
        # persistent compilation-cache hits in this process (a cold process
        # must see none: its cache directory starts empty)
        "compile_cache_hits": cache_hits[0],
    }


def _coldstart_child_main():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    i = sys.argv.index("--child-coldstart")
    rec = _bench_coldstart(sys.argv[i + 1], sys.argv[i + 2])
    print("BENCH_COLDSTART_JSON " + json.dumps(rec))


def _coldstart_subprocess(store_dir, phase, env):
    cmd = [sys.executable, os.path.abspath(__file__), "--child-coldstart",
           store_dir, phase]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=3600
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart child ({phase}) failed:\n{proc.stderr[-2000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_COLDSTART_JSON "):
            return json.loads(line[len("BENCH_COLDSTART_JSON "):])
    raise AssertionError(proc.stdout)


def _run_coldstart():
    """The cold-start leg: prime in process A, measure process B.

    Returns the ``cold_start`` record and asserts the leg's two hard
    invariants (independent of machine speed): the primed process built
    zero plans, and its energy trajectory is identical to the cold run's
    to <1e-10.
    """
    import tempfile

    env = dict(os.environ)
    env.setdefault("JAX_ENABLE_X64", "1")
    with tempfile.TemporaryDirectory(prefix="bench_coldstart_") as store_dir:
        # the children's compilation cache lives in the store directory, so
        # the cold child starts with an empty one
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(store_dir, "xla")
        cold = _coldstart_subprocess(store_dir, "cold", env)
        primed = _coldstart_subprocess(store_dir, "primed", env)
    steady = primed["steady_s"]
    rec = {
        "n_sites": COLD_N,
        "max_bond": COLD_M,
        "warm_sweeps": WARM,
        "timed_sweeps": TIMED,
        "cold_first_s": cold["first_s"],
        "cold_steady_s": cold["steady_s"],
        "warmup_compile_s": cold["prefetch_compile_s"],
        "primed_prefetch_s": primed["prefetch_compile_s"],
        "primed_first_s": primed["first_s"],
        "steady_s": steady,
        "cold_ratio": cold["first_s"] / max(steady, 1e-12),
        "primed_ratio": primed["first_s"] / max(steady, 1e-12),
        "primed_speedup": cold["first_s"] / max(primed["first_s"], 1e-12),
        "cold_plan_builds": cold["plan_builds"],
        "primed_plan_builds": primed["plan_builds"],
        "energy_diff": abs(cold["energy"] - primed["energy"]),
        "primed_compile_cache_hits": primed["compile_cache_hits"],
        "store_saves": cold["store"]["saves"],
        "store_export_saves": cold["store"]["export_saves"],
    }
    assert rec["primed_plan_builds"] == 0, rec
    assert rec["energy_diff"] < 1e-10, rec
    return rec


# ---------------------------------------------------------- weak-scaling leg

SPMD_N = 8    # weak-scaling workload: the cold-start J1-J2 ladder — small
SPMD_M = 16   # enough that four device counts fit in CI budget, block-rich
              # enough that every bucket shape class crosses the collectives
SPMD_DEVICES = (1, 2, 4, 8)
SPMD_GATE_DEVICES = 4    # device count carrying the gather-vs-spmd gate
SPMD_GATE_SPEEDUP = 5.0  # spmd must beat the gather-to-host path by this
SPMD_TIMED = 3           # timed sweeps per leg; steady state = min of these


def _bench_spmd(ndev):
    """One weak-scaling subprocess: list vs SPMD sweeps at ``ndev`` devices.

    Runs the SPMD (``mode="spmd"``, device-resident replicated storage +
    per-bucket shard_map collectives) sweep against the single-program list
    reference, reporting first/steady sweep seconds, the decomposition/env
    stage split, energy equality, and the SPMD collective ledger
    (``dist.spmd.stats()``) — with the hard compile-once check that the set
    of compiled SPMD programs stopped growing inside the timed window.

    At ``SPMD_GATE_DEVICES`` it also times the gather-to-host baseline the
    SPMD mode replaces: the *same* bucketed batched algorithm under a
    storage-mode policy, where every engine operation re-gathers the
    sharded blocks to replicated form on host before stacking buckets.
    That pair of numbers carries the acceptance gate (``SPMD_GATE_SPEEDUP``).

    Protocol: steady state is the MIN over ``SPMD_TIMED`` sweeps (robust
    to load spikes on shared CI runners, unlike the mean), and the SPMD
    leg warms two sweeps longer than the others — its first compile ramp
    (per-bucket shard_map programs inlined into the fused cores) has the
    longest tail.
    """
    import jax

    from repro.core.models import heisenberg_j1j2_terms
    from repro.core.mpo import build_mpo, compress_mpo
    from repro.core.mps import neel_states, product_state_mps
    from repro.core.siteops import spin_half_space
    from repro.core.sweep import DMRGEngine
    from repro.dist import BlockShardPolicy, make_block_mesh, spmd_stats

    assert jax.device_count() == ndev, (jax.device_count(), ndev)
    n, m = SPMD_N, SPMD_M
    sp = spin_half_space()
    terms = heisenberg_j1j2_terms(n // 2, 2, 1.0, 0.5, cylinder=False)
    mpo = compress_mpo(build_mpo(sp, terms, n), cutoff=1e-13)

    def fresh(**kw):
        mps = product_state_mps(sp, neel_states(sp, n))
        return DMRGEngine(mps, mpo, davidson_iters=2, **kw)

    def timed(eng, warm=WARM):
        t0 = time.perf_counter()
        eng.sweep(max_bond=m)
        first = time.perf_counter() - t0
        for _ in range(warm - 1):
            eng.sweep(max_bond=m)
        sweeps = []
        svd_s = env_s = 0.0
        for _ in range(SPMD_TIMED):
            t0 = time.perf_counter()
            s = eng.sweep(max_bond=m)
            sweeps.append(time.perf_counter() - t0)
            svd_s += s.svd_seconds
            env_s += s.env_seconds
        steady = min(sweeps)
        return first, steady, float(s.energy), svd_s / SPMD_TIMED, env_s / SPMD_TIMED

    _, t_list, e_list, _, _ = timed(fresh(algo="list"))

    mesh = make_block_mesh()
    policy = BlockShardPolicy(mesh, mode="spmd")
    eng = fresh(algo="batched", jit_matvec=True, shard_policy=policy)
    t0 = time.perf_counter()
    eng.sweep(max_bond=m)
    first = time.perf_counter() - t0
    for _ in range(WARM + 1):
        eng.sweep(max_bond=m)
    progs0 = spmd_stats()["unique_programs"]
    sweeps = []
    svd_s = env_s = 0.0
    for _ in range(SPMD_TIMED):
        t0 = time.perf_counter()
        s = eng.sweep(max_bond=m)
        sweeps.append(time.perf_counter() - t0)
        svd_s += s.svd_seconds
        env_s += s.env_seconds
    steady = min(sweeps)
    prog_growth = spmd_stats()["unique_programs"] - progs0

    rec = {
        "devices": ndev,
        "mesh": [int(mesh.shape["row"]), int(mesh.shape["col"])],
        "list_steady_s": t_list,
        "spmd_first_s": first,
        "spmd_steady_s": steady,
        "spmd_decomp_stage_s": svd_s / SPMD_TIMED,
        "spmd_env_stage_s": env_s / SPMD_TIMED,
        "spmd_vs_list_ratio": steady / max(t_list, 1e-12),
        "energy_diff": abs(float(s.energy) - e_list),
        "timed_program_growth": prog_growth,
        "spmd_stats": spmd_stats(),
    }
    if ndev == SPMD_GATE_DEVICES:
        # the gather-to-host baseline: same algorithm, storage-mode policy
        gpol = BlockShardPolicy(make_block_mesh())  # auto -> storage on CPU
        assert gpol.storage_only
        _, t_gather, e_gather, _, _ = timed(
            fresh(algo="batched", shard_policy=gpol)
        )
        rec["gather_steady_s"] = t_gather
        rec["gather_energy_diff"] = abs(e_gather - e_list)
        rec["spmd_vs_gather_speedup"] = t_gather / max(steady, 1e-12)
    return rec


def _spmd_child_main():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    ndev = int(sys.argv[sys.argv.index("--child-spmd") + 1])
    rec = _bench_spmd(ndev)
    print("BENCH_SPMD_JSON " + json.dumps(rec))


def _spmd_subprocess(ndev):
    env = dict(os.environ)
    # replace any inherited device-count flag with this leg's count
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={ndev}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("JAX_ENABLE_X64", "1")
    cmd = [sys.executable, os.path.abspath(__file__), "--child-spmd", str(ndev)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=3600
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"spmd child ({ndev} devices) failed:\n{proc.stderr[-2000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_SPMD_JSON "):
            return json.loads(line[len("BENCH_SPMD_JSON "):])
    raise AssertionError(proc.stdout)


def _run_weak_scaling():
    """The weak-scaling leg: one subprocess per fake-device count.

    Each count gets its own process because the device count is fixed by
    ``XLA_FLAGS`` before jax imports.  Asserts, at every count: SPMD energy
    equals the list reference to <1e-10 and the compiled-program set
    stopped growing inside the timed window (compile-once).  At
    ``SPMD_GATE_DEVICES`` it additionally asserts the acceptance gate:
    SPMD steady sweep >= ``SPMD_GATE_SPEEDUP``x faster than the
    gather-to-host (storage-mode, same algorithm) baseline.
    """
    legs = {}
    for ndev in SPMD_DEVICES:
        leg = _spmd_subprocess(ndev)
        assert leg["energy_diff"] < 1e-10, leg
        assert leg["timed_program_growth"] == 0, leg
        legs[str(ndev)] = leg
    gate_leg = legs[str(SPMD_GATE_DEVICES)]
    assert gate_leg["gather_energy_diff"] < 1e-10, gate_leg
    speedup = gate_leg["spmd_vs_gather_speedup"]
    assert speedup >= SPMD_GATE_SPEEDUP, (
        f"spmd vs gather-to-host speedup {speedup:.2f}x at "
        f"{SPMD_GATE_DEVICES} devices is below the "
        f"{SPMD_GATE_SPEEDUP:.0f}x acceptance gate: {gate_leg}"
    )
    return {
        "n_sites": SPMD_N,
        "max_bond": SPMD_M,
        "warm_sweeps": WARM,
        "spmd_warm_sweeps": WARM + 2,
        "timed_sweeps": SPMD_TIMED,
        "steady_estimator": "min",
        "device_counts": list(SPMD_DEVICES),
        "legs": legs,
        "gate": {
            "devices": SPMD_GATE_DEVICES,
            "required_speedup": SPMD_GATE_SPEEDUP,
            "spmd_vs_gather_speedup": speedup,
        },
    }


def spmd_rows(ws):
    """CSV rows for a weak-scaling record (shared by full and --spmd)."""
    rows = [
        (
            f"dist_spmd_sweep_{ndev}dev",
            ws["legs"][str(ndev)]["spmd_steady_s"] * 1e6,
            f"vs_list={ws['legs'][str(ndev)]['spmd_vs_list_ratio']:.2f}x;"
            f"ediff={ws['legs'][str(ndev)]['energy_diff']:.1e};"
            f"programs={ws['legs'][str(ndev)]['spmd_stats']['unique_programs']}",
        )
        for ndev in ws["device_counts"]
    ]
    g = ws["gate"]
    rows.append((
        "dist_spmd_vs_gather",
        ws["legs"][str(g["devices"])]["gather_steady_s"] * 1e6,
        f"speedup={g['spmd_vs_gather_speedup']:.2f}x;"
        f"required={g['required_speedup']:.0f}x;devices={g['devices']}",
    ))
    return rows


def check_regression(rec, ref, factor=2.0):
    """Fail (return nonzero) if a gated timing regressed > factor vs ref.

    Gates ``planned_sweep_s`` when present, ``cold_start.primed_first_s``
    when both records carry a cold-start leg (the coldstart-only record from
    ``--coldstart`` has no ``planned_sweep_s``; a pre-cold-start reference
    has no ``cold_start``), and the gate-device-count SPMD steady sweep when
    both records carry a weak-scaling leg.
    """
    rc = 0
    if "planned_sweep_s" in rec:
        got, want = rec["planned_sweep_s"], ref["planned_sweep_s"]
        if got > factor * want:
            print(
                f"REGRESSION: planned_sweep_s {got:.3f}s > {factor:.1f}x "
                f"checked-in {want:.3f}s"
            )
            rc = 1
        else:
            print(f"planned_sweep_s {got:.3f}s vs checked-in {want:.3f}s: ok")
    if "cold_start" in rec and "cold_start" in ref:
        got = rec["cold_start"]["primed_first_s"]
        want = ref["cold_start"]["primed_first_s"]
        if got > factor * want:
            print(
                f"REGRESSION: cold_start.primed_first_s {got:.3f}s > "
                f"{factor:.1f}x checked-in {want:.3f}s"
            )
            rc = 1
        else:
            print(
                f"cold_start.primed_first_s {got:.3f}s vs checked-in "
                f"{want:.3f}s: ok"
            )
    if "weak_scaling" in rec and "weak_scaling" in ref:
        key = str(SPMD_GATE_DEVICES)
        got = rec["weak_scaling"]["legs"][key]["spmd_steady_s"]
        want = ref["weak_scaling"]["legs"][key]["spmd_steady_s"]
        if got > factor * want:
            print(
                f"REGRESSION: weak_scaling spmd_steady_s ({key} devices) "
                f"{got:.3f}s > {factor:.1f}x checked-in {want:.3f}s"
            )
            rc = 1
        else:
            print(
                f"weak_scaling spmd_steady_s ({key} devices) {got:.3f}s vs "
                f"checked-in {want:.3f}s: ok"
            )
    return rc


def run(quick=False, write_json=True):
    """run.py entry (CSV rows only); see ``_run`` for the JSON record."""
    return _run(quick=quick, write_json=write_json)[0]


def _run(quick=False, write_json=True):
    """Execute in a subprocess (XLA flag must precede jax)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _XLA_FLAG).strip()
    env.setdefault("JAX_ENABLE_X64", "1")
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=3600
    )
    if proc.returncode != 0:
        raise RuntimeError(f"bench_dist child failed:\n{proc.stderr[-2000:]}")
    rec = None
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_DIST_JSON "):
            rec = json.loads(line[len("BENCH_DIST_JSON "):])
    assert rec is not None, proc.stdout
    if not quick:
        # the cold-start leg spawns its own pair of subprocesses (the whole
        # point is crossing a process boundary), so it runs from the parent;
        # the weak-scaling leg likewise needs one process per device count
        rec["cold_start"] = _run_coldstart()
        rec["weak_scaling"] = _run_weak_scaling()
    if write_json:
        out_path = os.path.join(os.path.dirname(__file__), "bench_dist.json")
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
    rows = [
        (
            "dist_planned_sweep",
            rec["planned_sweep_s"] * 1e6,
            f"first={rec['planned_first_sweep_s']:.2f}s;"
            f"cache_hits={rec['plan_cache']['hits']};"
            f"cache_misses={rec['plan_cache']['misses']}",
        ),
        (
            "dist_batched_jit_sweep",
            rec["batched_sweep_s"] * 1e6,
            f"speedup={rec['batched_speedup']:.2f}x;"
            f"timed_retraces={rec['batched_timed_retraces']};"
            f"decomp_stage_s={rec['batched_decomp_stage_s']:.3f}",
        ),
        (
            "dist_decomp_stage_m64",
            rec["decomp_stage"]["planned_batched_s"] * 1e6,
            f"speedup_vs_seed={rec['decomp_stage']['speedup']:.2f}x;"
            f"seed_s={rec['decomp_stage']['seed_per_sector_s']:.3f};"
            f"product_diff={rec['decomp_stage']['max_product_diff']:.1e}",
        ),
        (
            "dist_env_stage_m32",
            rec["env_stage"]["fused_jit_s"] * 1e6,
            f"speedup_vs_eager={rec['env_stage']['speedup']:.2f}x;"
            f"eager_s={rec['env_stage']['eager_three_call_s']:.3f};"
            f"timed_retraces={rec['env_stage']['timed_retraces']};"
            f"block_diff={rec['env_stage']['max_block_diff']:.1e}",
        ),
        (
            "dist_planned_jit_sweep",
            rec["planned_jit_sweep_s"] * 1e6,
            f"speedup={rec['jit_speedup']:.2f}x;"
            f"timed_retraces={rec['planned_jit_timed_retraces']}",
        ),
    ]
    if not quick:
        sm = rec["sharded_smoke"]
        rows = [
            (
                "dist_seed_unplanned_sweep2",
                rec["seed_unplanned_sweep_s"] * 1e6,
                f"vs_planned_sweep2={rec['plan_speedup_sweep2']:.2f}x",
            ),
        ] + rows + [
            ("dist_batched_eager_sweep", rec["batched_eager_sweep_s"] * 1e6, ""),
            ("dist_auto_sweep", rec["auto_sweep_s"] * 1e6, ""),
            (
                "dist_sharded_smoke_sweep",
                sm["sweep_s"] * 1e6,
                f"devices={rec['devices']};n={sm['n_sites']};"
                f"ediff={sm['energy_diff']:.1e}",
            ),
        ] + coldstart_rows(rec["cold_start"]) + spmd_rows(rec["weak_scaling"])
    return rows, rec


def coldstart_rows(cs):
    """CSV rows for a cold-start record (shared by full and --coldstart)."""
    return [
        (
            "dist_coldstart_primed_first_sweep",
            cs["primed_first_s"] * 1e6,
            f"ratio_vs_steady={cs['primed_ratio']:.2f}x;"
            f"speedup_vs_cold={cs['primed_speedup']:.2f}x;"
            f"plan_builds={cs['primed_plan_builds']}",
        ),
        (
            "dist_coldstart_cold_first_sweep",
            cs["cold_first_s"] * 1e6,
            f"ratio_vs_steady={cs['cold_ratio']:.2f}x;"
            f"warmup_compile_s={cs['warmup_compile_s']:.1f};"
            f"ediff={cs['energy_diff']:.1e}",
        ),
    ]


if __name__ == "__main__":
    if "--child-coldstart" in sys.argv:
        _coldstart_child_main()
        sys.exit(0)
    if "--child-spmd" in sys.argv:
        _spmd_child_main()
        sys.exit(0)
    if "--child" in sys.argv:
        _child_main()
    else:
        quick = "--quick" in sys.argv
        ref = None
        if "--check" in sys.argv:
            # load the reference BEFORE running: a full (non-quick) run
            # rewrites bench_dist.json, and the gate must not compare the
            # fresh record against itself
            try:
                ref_path = sys.argv[sys.argv.index("--check") + 1]
            except IndexError:
                sys.exit("--check requires a path to a reference JSON")
            with open(ref_path) as f:
                ref = json.load(f)
        if "--spmd" in sys.argv:
            # weak-scaling-only mode (the CI spmd job): skip the in-process
            # bench and run just the per-device-count SPMD leg
            rec = {"quick": True, "weak_scaling": _run_weak_scaling()}
            for name, us, derived in spmd_rows(rec["weak_scaling"]):
                print(f"{name},{us:.1f},{derived}")
            out = os.path.join(os.path.dirname(__file__), "bench_spmd.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=2, sort_keys=True)
            print(f"wrote {out}")
            sys.exit(check_regression(rec, ref) if ref is not None else 0)
        if "--coldstart" in sys.argv:
            # coldstart-only mode (the CI coldstart job): skip the in-process
            # bench entirely and run just the two-subprocess leg
            rec = {"quick": True, "cold_start": _run_coldstart()}
            for name, us, derived in coldstart_rows(rec["cold_start"]):
                print(f"{name},{us:.1f},{derived}")
            out = os.path.join(
                os.path.dirname(__file__), "bench_coldstart.json"
            )
            with open(out, "w") as f:
                json.dump(rec, f, indent=2, sort_keys=True)
            print(f"wrote {out}")
            sys.exit(check_regression(rec, ref) if ref is not None else 0)
        rows, rec = _run(quick=quick, write_json=not quick)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        if quick:
            out = os.path.join(os.path.dirname(__file__), "bench_dist_quick.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=2, sort_keys=True)
            print(f"wrote {out}")
        if ref is not None:
            sys.exit(check_regression(rec, ref))
