"""Two-site DMRG sweeps (paper Sec. II-C, Fig. 1c-e).

Maintains left/right environments incrementally, optimizes each neighboring
pair with Davidson, splits with a blockwise truncated SVD absorbing the
singular values along the sweep direction, and supports all contraction
backends ("list", "dense", "csr", "batched", "auto") through the
plan-cached ``dist.ContractionEngine``.  Optional extras when the backend
is an engine (the default): a jitted planned matvec (``jit_matvec=True``)
with bucket-padded operands so it compiles once per quantized structure
(``pad_matvec``, defaulting to the jit flag), and a ``BlockShardPolicy``
that keeps MPS/MPO/environment blocks mesh-sharded, mirroring the paper's
distribute-every-block-over-all-processors layout.  A policy in "spmd"
mode (``run_dmrg(spmd=True)``) instead pins every stored tensor
device-resident on the mesh — uploaded once in ``__init__``/``_init_envs``
— and the engine executes all bucketed GEMMs as shard_map collective
programs (``dist/spmd.py``, DESIGN.md 3.10); "storage" mode keeps the
gather-before-compute fallback.

The decomposition stage goes through the engine too (``svd_method``): the
planned batched SVD (``dist/decomp.py``) by default, the seed per-sector
loop with ``svd_method="unplanned"``, or the randomized path
("randomized"/"auto") — so ``_optimize_pair`` stays in device-land from the
matvec through the split, with one host sync per split for truncation.
``SweepStats.svd_seconds`` reports the stage's wall-clock per sweep.

The environment stage is the fourth and final pipeline stage under the
engine (``jit_env``, defaulting on for engines): each left/right env update
runs as ONE fused jitted call (``dist/envcore.py``) on power-of-two-padded
operands instead of three chained eager contractions, and ``_init_envs``
rebuilds the right environments as one planned right-to-left pass.
``jit_env=False`` (or a bare contractor) falls back to the seed
``extend_left`` / ``extend_right``; ``SweepStats.env_seconds`` carries the
stage's wall-clock per sweep.

Under a JAX profiler session each pair update is a ``sweep.pair`` span of
``repro.obs`` with its stages nested inside: ``sweep.theta``, ``sweep.pad``,
``sweep.operator``, ``davidson.solve``, ``sweep.unpad``, ``split``,
``sweep.place`` and ``env.update``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from .. import obs
from ..dist import faults
from ..dist.batch import pad_block_sparse, unpad_block_sparse
from ..dist.engine import ContractionEngine
from ..dist.faults import FaultInjected, NumericalHealthError
from ..dist.shard import BlockShardPolicy
from ..tensor.blocksparse import (
    BlockSparseTensor,
    contract,
    flip_flow,
    svd_split_unplanned,
)
from .davidson import davidson
from .env import (
    extend_left,
    extend_right,
    get_contractor,
    left_edge,
    matvec_two_site,
    right_edge,
)
from .mps import MPS


@dataclasses.dataclass
class SweepStats:
    energy: float
    max_bond: int
    trunc_err: float
    seconds: float
    site_seconds: List[float]
    site_energies: List[float]
    # wall-clock of the decomposition stage (all svd_split calls) this sweep,
    # in seconds — the per-stage split bench_dist.py reports.  For the
    # planned path this includes the singular-value device sync, so it
    # reflects real SVD compute; the remainder of ``seconds`` is
    # contraction + Davidson + environment work.
    svd_seconds: float = 0.0
    # wall-clock of the environment stage (all left/right env updates) this
    # sweep, in seconds — fused jitted updates when ``jit_env`` is on, the
    # seed three-contraction path otherwise.  Host-side dispatch time (jax
    # is async); the ``env.update`` spans of ``repro.obs`` time the same
    # stage per update under a profiler session.
    env_seconds: float = 0.0
    # Davidson health ledger for the sweep (core/davidson.py DavidsonInfo):
    # solves run, solves whose residual actually converged below tol (budget-
    # limited production solves stop early, so converged < solves is normal),
    # total inner iterations, Gram-Schmidt breakdown restarts, and subspace
    # exhaustions.  Restarts/exhaustions > 0 on a healthy small problem is
    # expected near convergence; they become interesting when they spike.
    davidson_solves: int = 0
    davidson_converged: int = 0
    davidson_iterations: int = 0
    davidson_restarts: int = 0
    davidson_exhausted: int = 0
    # pair optimizations that failed the fast path (NumericalHealthError /
    # injected fault) and were recovered on the seed ladder rung.  Zero on a
    # healthy run — the clean bench leg asserts it.
    pair_retries: int = 0


class DMRGEngine:
    """Alternating two-site optimization with incremental environments."""

    def __init__(
        self,
        mps: MPS,
        mpo: List[BlockSparseTensor],
        algo: str = "list",
        davidson_iters: int = 2,
        seed: int = 0,
        jit_matvec: bool = False,
        pad_matvec: Optional[bool] = None,
        shard_policy: Optional[BlockShardPolicy] = None,
        engine: Optional[Callable] = None,
        svd_method: Optional[str] = None,
        jit_env: Optional[bool] = None,
        restored_envs=None,
    ):
        assert mps.n_sites == len(mpo)
        self.mps = mps
        self.mpo = mpo
        self.algo = algo
        self.contract_fn = engine if engine is not None else get_contractor(algo)
        self.jit_matvec = jit_matvec
        # bucket-pad the Davidson operands so the jitted matvec sees a small
        # set of block structures (compile-once); defaults to on iff jitting
        self.pad_matvec = jit_matvec if pad_matvec is None else pad_matvec
        # the MPO is immutable for the run — pad each site tensor once,
        # not on every pair optimization
        self._mpo_padded: List[Optional[BlockSparseTensor]] = [None] * len(mpo)
        if svd_method not in (None, "unplanned", "svd", "randomized", "auto"):
            raise ValueError(f"unknown svd_method: {svd_method!r}")
        if isinstance(self.contract_fn, ContractionEngine):
            # decomposition stage: engines route svd_split through their
            # planned DecompositionEngine ("svd" exact, "randomized", "auto"
            # cost model); "unplanned" forces the seed per-sector loop.  The
            # svd_method and shard_policy parameters are the single source of
            # truth: set them on the engine, or reset configuration left over
            # from a previous DMRGEngine that reused this engine instance
            self.svd_planned = svd_method != "unplanned"
            self.contract_fn.decomp.method = (
                svd_method if svd_method in ("svd", "randomized", "auto")
                else "svd"
            )
            self.contract_fn.policy = shard_policy
            # environment stage: fused plan-cached jitted updates
            # (dist/envcore.py) by default for engines; jit_env=False keeps
            # the seed extend_left/extend_right three-call path
            self.jit_env = True if jit_env is None else bool(jit_env)
        else:
            # bare contractors (the *_unplanned algos, or a plain callable
            # passed via engine=) have no gather step (sharded blocks would
            # deadlock eager CPU collectives), no jit pipeline and no planned
            # decomposition; fail loudly instead of hanging / silently
            # ignoring the flag
            backend = (
                f"algo={algo!r}" if engine is None
                else f"engine={type(engine).__name__}"
            )
            if shard_policy is not None:
                raise ValueError(
                    f"shard_policy requires a ContractionEngine backend, "
                    f"not {backend}"
                )
            if jit_matvec:
                raise ValueError(
                    f"jit_matvec requires a ContractionEngine backend, "
                    f"not {backend}"
                )
            if svd_method not in (None, "unplanned"):
                raise ValueError(
                    f"svd_method={svd_method!r} requires a ContractionEngine "
                    f"backend, not {backend}; bare contractors use the seed "
                    f"svd_split_unplanned"
                )
            if jit_env:
                raise ValueError(
                    f"jit_env requires a ContractionEngine backend, "
                    f"not {backend}; bare contractors use the seed "
                    f"extend_left/extend_right"
                )
            self.svd_planned = False
            self.jit_env = False
        if shard_policy is not None:
            self.mps.tensors = shard_policy.place_mps(self.mps.tensors)
            self.mpo = shard_policy.place_mps(self.mpo)
        self.shard_policy = shard_policy
        self.davidson_iters = davidson_iters
        self.seed = seed
        self.n = mps.n_sites
        if restored_envs is not None:
            # checkpoint resume (core/checkpoint.py): the serialized env
            # lists are exact copies of the live ones at save time, so
            # restoring them — rather than recomputing via _init_envs —
            # keeps a mid-sweep resume bit-identical to the uninterrupted
            # run (the right envs mid-LR-sweep are partially stale, a state
            # a fresh rebuild could not reproduce)
            self.left_envs, self.right_envs = restored_envs
            assert len(self.left_envs) == self.n + 1
            assert len(self.right_envs) == self.n + 1
        else:
            self._init_envs()

    def _init_envs(self):
        n = self.n
        T, W = self.mps.tensors, self.mpo
        self.left_envs: List[Optional[BlockSparseTensor]] = [None] * (n + 1)
        self.right_envs: List[Optional[BlockSparseTensor]] = [None] * (n + 1)
        # edges placed too: under an spmd-mode policy this is the one-time
        # device-resident upload — every stored env (and the MPS/MPO placed
        # in __init__) lives replicated on the mesh from here on and is
        # never re-materialized on host between sites
        self.left_envs[0] = self._place(left_edge(T[0], W[0]))
        self.right_envs[n - 1] = self._place(right_edge(T[n - 1], W[n - 1]))
        # build right envs down to site 1 (first pair needs right_envs[1]) —
        # one planned right-to-left pass: fused jitted updates when jit_env
        for j in range(n - 2, 0, -1):
            self.right_envs[j] = self._place(self._extend_right_env(j))

    def _extend_left_env(self, j: int) -> BlockSparseTensor:
        """A_{j+1} from A_j: absorb site j into the left environment.

        Planned fused jitted update (``engine.env_update_left``) when
        ``jit_env`` is on; the seed three-contraction ``extend_left``
        otherwise (and always for bare contractors).
        """
        A, T, W = self.left_envs[j], self.mps.tensors[j], self.mpo[j]
        if self.jit_env:
            try:
                return self.contract_fn.env_update_left(
                    A, T, W, mpo_padded=self._padded_mpo(j)
                )
            except (FaultInjected, NumericalHealthError):
                # degradation ladder (DESIGN.md 3.8): fused core failed —
                # recover on the seed three-contraction path, which matches
                # it to <1e-10 block-for-block, and keep sweeping
                self.contract_fn.note_retry("env")
                self.contract_fn.note_degradation("env_seed")
        return extend_left(A, T, W, self.contract_fn)

    def _extend_right_env(self, j: int) -> BlockSparseTensor:
        """B_j from B_{j+1}: absorb site j+1 into the right environment."""
        B, T, W = self.right_envs[j + 1], self.mps.tensors[j + 1], self.mpo[j + 1]
        if self.jit_env:
            try:
                return self.contract_fn.env_update_right(
                    B, T, W, mpo_padded=self._padded_mpo(j + 1)
                )
            except (FaultInjected, NumericalHealthError):
                self.contract_fn.note_retry("env")
                self.contract_fn.note_degradation("env_seed")
        return extend_right(B, T, W, self.contract_fn)

    def _padded_mpo(self, j: int) -> BlockSparseTensor:
        if self._mpo_padded[j] is None:
            self._mpo_padded[j] = pad_block_sparse(self.mpo[j])
        return self._mpo_padded[j]

    def _place(self, t: BlockSparseTensor) -> BlockSparseTensor:
        """Mesh-shard a stored tensor (env / site) when a policy is attached."""
        return t if self.shard_policy is None else self.shard_policy.place(t)

    def _optimize_pair(self, j: int, max_bond: int, cutoff: float, absorb: str):
        """Optimize pair (j, j+1), recovering failures on the seed rung.

        The fast path is the full engine pipeline (planned matvec, batched
        SVD).  A ``NumericalHealthError`` (a health guard at a host sync saw
        non-finite values — e.g. a NaN-poisoned GEMM surfacing at the
        Davidson Rayleigh-Ritz read) or an injected fault aborts the pair
        BEFORE any MPS tensor is written, so the retry starts from exactly
        the pre-pair state and re-runs on the seed code path: eager seed
        ``contract`` matvec, seed per-sector SVD, no engine involvement —
        immune to any engine-layer fault still armed.  Seed-equality
        guarantees (<1e-10) make the recovered energy match a clean run.
        """
        try:
            return self._optimize_pair_fast(j, max_bond, cutoff, absorb)
        except (NumericalHealthError, FaultInjected):
            if isinstance(self.contract_fn, ContractionEngine):
                self.contract_fn.note_retry("pair")
                self.contract_fn.note_degradation("pair_seed")
            return self._optimize_pair_seed(j, max_bond, cutoff, absorb)

    def _optimize_pair_seed(
        self, j: int, max_bond: int, cutoff: float, absorb: str
    ):
        """Bottom degradation rung: the pair on seed-only code paths."""
        T, W = self.mps.tensors, self.mpo
        A, B = self.left_envs[j], self.right_envs[j + 1]
        Tj, Tj1, Wj, Wj1 = T[j], T[j + 1], W[j], W[j + 1]
        if self.shard_policy is not None:
            # the seed contract is eager per-block; gather sharded operands
            # first (same rule as the engine's storage-mode gather)
            rep = self.shard_policy.replicated
            A, B = rep(A), rep(B)
            Tj, Tj1, Wj, Wj1 = rep(Tj), rep(Tj1), rep(Wj), rep(Wj1)
        theta = contract(Tj, Tj1, ((2,), (0,)))

        def mv(x):
            return matvec_two_site(A, Wj, Wj1, B, x, contract)

        lam, theta, info = davidson(
            mv, theta, n_iter=self.davidson_iters, seed=self.seed + j
        )
        t_svd = time.perf_counter()
        U, V, _, err = svd_split_unplanned(
            theta, 2, max_bond=max_bond, cutoff=cutoff, absorb=absorb
        )
        svd_dt = time.perf_counter() - t_svd
        T[j] = self._place(flip_flow(U, 2))
        T[j + 1] = self._place(flip_flow(V, 0))
        return lam, err, svd_dt, info

    def _optimize_pair_fast(
        self, j: int, max_bond: int, cutoff: float, absorb: str
    ):
        T, W = self.mps.tensors, self.mpo
        A, B = self.left_envs[j], self.right_envs[j + 1]
        with obs.span("sweep.theta"):
            theta = self.contract_fn(T[j], T[j + 1], ((2,), (0,)))

        pad = (
            self.pad_matvec and isinstance(self.contract_fn, ContractionEngine)
        )
        if pad:
            # round every sector dim up to a power of two: zero-padding is
            # exact (padded operator entries are zero) and quantizes the
            # traced structure, so the jitted matvec compiles once per
            # bucketed structure instead of once per site per sweep
            with obs.span("sweep.pad"):
                orig_indices = theta.indices
                A, B = pad_block_sparse(A), pad_block_sparse(B)
                Wjp, Wj1p = self._padded_mpo(j), self._padded_mpo(j + 1)
                theta = pad_block_sparse(theta)
        else:
            Wjp, Wj1p = W[j], W[j + 1]

        if isinstance(self.contract_fn, ContractionEngine):
            with obs.span("sweep.operator"):
                mv = self.contract_fn.matvec_fn(
                    A, Wjp, Wj1p, B, jit=self.jit_matvec
                )
        else:
            def mv(x):
                return matvec_two_site(A, Wjp, Wj1p, B, x, self.contract_fn)

        # padded operands bound the block structures, so the subspace
        # algebra runs as compiled programs too (core/davidson.py)
        lam, theta, dinfo = davidson(
            mv, theta, n_iter=self.davidson_iters, seed=self.seed + j,
            fused=pad,
        )
        if pad:
            with obs.span("sweep.unpad"):
                theta = unpad_block_sparse(theta, orig_indices)
        # decomposition stage: planned engines stay in device-land — one
        # batched SVD core call plus a single singular-value sync for the
        # global truncation — while the seed path loops sectors on host
        t_svd = time.perf_counter()
        if self.svd_planned:
            U, V, _, err = self.contract_fn.svd_split(
                theta, 2, max_bond=max_bond, cutoff=cutoff, absorb=absorb
            )
        else:
            U, V, _, err = svd_split_unplanned(
                theta, 2, max_bond=max_bond, cutoff=cutoff, absorb=absorb
            )
        svd_dt = time.perf_counter() - t_svd
        with obs.span("sweep.place"):
            T[j] = self._place(flip_flow(U, 2))
            T[j + 1] = self._place(flip_flow(V, 0))
        return lam, err, svd_dt, dinfo

    def sweep(
        self,
        max_bond: int,
        cutoff: float = 1e-12,
        resume: Optional[Dict] = None,
        on_site: Optional[Callable[[Optional[Dict]], None]] = None,
    ) -> SweepStats:
        """One full left-to-right + right-to-left sweep; returns stats.

        ``resume`` restarts mid-sweep from a state dict previously handed to
        ``on_site`` (phase, next site, partial accumulators) — together with
        restored MPS/env lists this continues an interrupted sweep with
        energies identical to the uninterrupted run (core/checkpoint.py).
        ``on_site(state)`` fires after every completed site update (pair
        optimization + env extension) with the resume state that would
        restart right after it, or ``None`` when the sweep just finished.
        The ``sweep.kill`` fault point fires after ``on_site`` — a test can
        checkpoint site k and die before site k+1, like a real crash.
        """
        n = self.n
        r = resume or {}
        energies: List[float] = list(r.get("energies", []))
        site_secs: List[float] = list(r.get("site_seconds", []))
        max_err = float(r.get("max_err", 0.0))
        svd_secs = float(r.get("svd_seconds", 0.0))
        env_secs = float(r.get("env_seconds", 0.0))
        secs_base = float(r.get("seconds", 0.0))
        dav = dict(r.get("davidson", {}))
        pair_retries = int(r.get("pair_retries", 0))
        phase = r.get("phase", "LR")
        start_j = int(r.get("j", 0 if phase == "LR" else n - 2))
        t0 = time.perf_counter()

        def _site(j: int, absorb: str):
            nonlocal max_err, svd_secs, env_secs, pair_retries
            ts = time.perf_counter()
            before = 0
            if isinstance(self.contract_fn, ContractionEngine):
                before = self.contract_fn.retries.get("pair", 0)
            with obs.span("sweep.pair"):
                lam, err, svd_dt, dinfo = self._optimize_pair(
                    j, max_bond, cutoff, absorb=absorb
                )
                te = time.perf_counter()
                with obs.span("env.update"):
                    if absorb == "right":
                        self.left_envs[j + 1] = self._place(
                            self._extend_left_env(j))
                    else:
                        self.right_envs[j] = self._place(
                            self._extend_right_env(j))
                env_secs += time.perf_counter() - te
            if isinstance(self.contract_fn, ContractionEngine):
                pair_retries += self.contract_fn.retries.get("pair", 0) - before
            energies.append(lam)
            site_secs.append(time.perf_counter() - ts)
            max_err = max(max_err, err)
            svd_secs += svd_dt
            dav["solves"] = dav.get("solves", 0) + 1
            dav["converged"] = dav.get("converged", 0) + int(dinfo.converged)
            dav["iterations"] = dav.get("iterations", 0) + dinfo.iterations
            dav["restarts"] = dav.get("restarts", 0) + dinfo.restarts
            dav["exhausted"] = dav.get("exhausted", 0) + int(dinfo.exhausted)

        def _after_site(state: Optional[Dict]):
            if on_site is not None:
                if state is not None:
                    state.update(
                        energies=list(energies),
                        site_seconds=list(site_secs),
                        max_err=max_err,
                        svd_seconds=svd_secs,
                        env_seconds=env_secs,
                        seconds=secs_base + time.perf_counter() - t0,
                        davidson=dict(dav),
                        pair_retries=pair_retries,
                    )
                on_site(state)
            if faults.fire("sweep.kill") is not None:
                raise FaultInjected(
                    "sweep.kill", "sweep killed after a site update"
                )

        if phase == "LR":
            for j in range(start_j, n - 1):  # left -> right
                _site(j, "right")
                nxt = (
                    {"phase": "LR", "j": j + 1}
                    if j + 1 < n - 1
                    else {"phase": "RL", "j": n - 2}
                )
                _after_site(nxt)
            start_j = n - 2

        for j in range(start_j, -1, -1):  # right -> left
            _site(j, "left")
            _after_site({"phase": "RL", "j": j - 1} if j > 0 else None)

        return SweepStats(
            energy=energies[-1],
            max_bond=self.mps.max_bond(),
            trunc_err=max_err,
            seconds=secs_base + time.perf_counter() - t0,
            site_seconds=site_secs,
            site_energies=energies,
            svd_seconds=svd_secs,
            env_seconds=env_secs,
            davidson_solves=dav.get("solves", 0),
            davidson_converged=dav.get("converged", 0),
            davidson_iterations=dav.get("iterations", 0),
            davidson_restarts=dav.get("restarts", 0),
            davidson_exhausted=dav.get("exhausted", 0),
            pair_retries=pair_retries,
        )
