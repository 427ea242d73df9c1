"""Top-level DMRG driver: bond-dimension schedule + sweeps (paper Sec. II-C).

"In doing DMRG, we gradually increase bond dimension of the MPS, sweeping
over all sites multiple times for each successive bond dimension choice."
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp

from ..dist import persist
from ..dist.engine import ContractionEngine
from ..dist.shard import BlockShardPolicy, make_block_mesh
from .checkpoint import (
    CheckpointManager,
    pack_run_state,
    tensor_restore,
    unpack_envs,
)
from .mpo import build_mpo, compress_mpo
from .mps import MPS, neel_states, product_state_mps
from .siteops import LocalSpace
from .sweep import DMRGEngine, SweepStats


@dataclasses.dataclass
class DMRGResult:
    energy: float
    mps: MPS
    sweep_stats: List[SweepStats]
    # the run's ContractionEngine.stats() ledger (plan caches, backend and
    # stage counters, retries/degradations); None for bare contractors
    engine_stats: Optional[Dict] = None

    @property
    def energies(self) -> List[float]:
        return [s.energy for s in self.sweep_stats]


def run_dmrg(
    space: LocalSpace,
    terms,
    n_sites: int,
    bond_schedule: Sequence[int] = (8, 16, 32),
    sweeps_per_bond: int = 2,
    cutoff: float = 1e-12,
    algo: str = "list",
    davidson_iters: int = 3,
    mpo_cutoff: float = 1e-13,
    initial_states: Optional[Sequence[int]] = None,
    dtype=jnp.float64,
    verbose: bool = False,
    jit_matvec: bool = False,
    pad_matvec: Optional[bool] = None,
    shard_policy: Optional[BlockShardPolicy] = None,
    spmd: bool = False,
    svd_method: Optional[str] = None,
    jit_env: Optional[bool] = None,
    mpo=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 2,
    plan_store=None,
) -> DMRGResult:
    """Ground-state DMRG over a bond-dimension schedule.

    With ``checkpoint_dir`` set, the full sweep state (MPS, both env lists,
    schedule position, partial in-sweep accumulators, Davidson seed) is
    pickled atomically every ``checkpoint_every`` site updates plus at every
    sweep boundary, and a rerun with the same arguments resumes from the
    newest checkpoint — mid-sweep if that is where it died — with energies
    identical to the uninterrupted run (core/checkpoint.py).

    ``plan_store`` (a ``repro.dist.PlanStore`` or a path) activates the
    persistent plan + executable store for the duration of the run
    (``dist/persist.py``, DESIGN.md Sec. 3.9): plans, exported cores and
    compiled executables are loaded from — and written back to — the store,
    so a primed store takes the first sweep from ~20x steady-state cost to
    ~2x.  Physics is unchanged: primed and cold runs produce energies equal
    to <1e-10 (tests/test_persist.py).  A store already activated
    process-wide (``repro.dist.activate_store``) is used without passing it
    here; this argument scopes one to a single run.

    ``spmd=True`` turns on true SPMD execution (DESIGN.md 3.10,
    docs/distributed.md): MPS/MPO/environment tensors are pinned
    device-resident on the 2-D ("row", "col") mesh and every bucketed GEMM
    of the matvec and env stages runs as a shard_map collective program
    (``dist/spmd.py``).  It implies ``jit_matvec=True`` (the compile-once
    padded pipeline is what makes the collectives pay) and requires an
    engine-backed ``algo``.  Pass ``shard_policy`` to control the mesh (its
    mode must be "spmd"); omitted, a policy over all devices is built.
    Energies equal the single-device run to <1e-10 at any device count
    (tests/test_spmd.py).
    """
    if spmd:
        if shard_policy is None:
            shard_policy = BlockShardPolicy(make_block_mesh(), mode="spmd")
        elif shard_policy.mode != "spmd":
            raise ValueError(
                f"spmd=True needs a shard_policy with mode='spmd', got "
                f"mode={shard_policy.mode!r} (storage-mode policies keep the "
                f"gather-to-host path; pass spmd=False for that)"
            )
        jit_matvec = True
    with contextlib.ExitStack() as stack:
        if plan_store is not None:
            stack.enter_context(persist.using_store(plan_store))
        return _run_dmrg_body(
            space, terms, n_sites, bond_schedule, sweeps_per_bond, cutoff,
            algo, davidson_iters, mpo_cutoff, initial_states, dtype, verbose,
            jit_matvec, pad_matvec, shard_policy, svd_method, jit_env, mpo,
            checkpoint_dir, checkpoint_every, checkpoint_keep,
        )


def _run_dmrg_body(
    space, terms, n_sites, bond_schedule, sweeps_per_bond, cutoff, algo,
    davidson_iters, mpo_cutoff, initial_states, dtype, verbose, jit_matvec,
    pad_matvec, shard_policy, svd_method, jit_env, mpo, checkpoint_dir,
    checkpoint_every, checkpoint_keep,
) -> DMRGResult:
    # A pre-built MPO bypasses build/compress so callers comparing against a
    # batched multi-problem run (repro/serve) optimize the EXACT same
    # operator, not a re-compressed cousin with reordered degenerate blocks.
    if mpo is None:
        mpo = build_mpo(space, terms, n_sites, dtype=dtype)
        if mpo_cutoff is not None:
            mpo = compress_mpo(mpo, cutoff=mpo_cutoff)
    states = list(initial_states) if initial_states is not None else neel_states(space, n_sites)
    mps = product_state_mps(space, states, dtype=dtype)

    ckpt = (
        CheckpointManager(
            checkpoint_dir, every=checkpoint_every, keep=checkpoint_keep
        )
        if checkpoint_dir is not None
        else None
    )
    state = ckpt.load_latest() if ckpt is not None else None
    restored_envs = None
    stats: List[SweepStats] = []
    step = 0
    start_bi = start_si = 0
    sweep_resume = None
    if state is not None:
        mps.tensors = [tensor_restore(s) for s in state["mps"]]
        restored_envs = unpack_envs(state)
        stats = [SweepStats(**d) for d in state["stats"]]
        step = int(state["step"])
        start_bi, start_si = int(state["bond_idx"]), int(state["sweep_idx"])
        sweep_resume = state["sweep_resume"]

    engine = DMRGEngine(
        mps,
        mpo,
        algo=algo,
        davidson_iters=davidson_iters,
        jit_matvec=jit_matvec,
        pad_matvec=pad_matvec,
        shard_policy=shard_policy,
        svd_method=svd_method,
        jit_env=jit_env,
        restored_envs=restored_envs,
    )
    if state is not None:
        engine.seed = int(state["seed"])

    def _snapshot(bi: int, si: int, resume_state):
        return pack_run_state(
            step=step,
            bond_idx=bi,
            sweep_idx=si,
            sweep_resume=resume_state,
            mps_tensors=engine.mps.tensors,
            left_envs=engine.left_envs,
            right_envs=engine.right_envs,
            stats=stats,
            seed=engine.seed,
        )

    for bi, m in enumerate(bond_schedule):
        if bi < start_bi:
            continue
        for si in range(sweeps_per_bond):
            if bi == start_bi and si < start_si:
                continue
            resume = (
                sweep_resume if (bi, si) == (start_bi, start_si) else None
            )
            on_site = None
            if ckpt is not None:

                def on_site(rs, _bi=bi, _si=si):
                    nonlocal step
                    step += 1
                    if rs is not None:  # sweep boundary saved below instead
                        ckpt.maybe_save(_snapshot(_bi, _si, rs))

            s = engine.sweep(
                max_bond=m, cutoff=cutoff, resume=resume, on_site=on_site
            )
            stats.append(s)
            if ckpt is not None:
                # boundary checkpoint points at the NEXT schedule slot, so a
                # crash between sweeps resumes cleanly at the next sweep
                nbi, nsi = (
                    (bi, si + 1) if si + 1 < sweeps_per_bond else (bi + 1, 0)
                )
                ckpt.save(_snapshot(nbi, nsi, None))
            if verbose:
                print(
                    f"m={m:6d} E={s.energy:+.10f} maxbond={s.max_bond} "
                    f"trunc={s.trunc_err:.2e} t={s.seconds:.2f}s"
                )
    engine_stats = (
        engine.contract_fn.stats()
        if isinstance(engine.contract_fn, ContractionEngine)
        else None
    )
    return DMRGResult(
        energy=stats[-1].energy, mps=engine.mps, sweep_stats=stats,
        engine_stats=engine_stats,
    )
