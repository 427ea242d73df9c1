"""ContractionEngine: plan-cached, mesh-sharded block-sparse contraction.

The engine is a drop-in replacement for the bare ``contract_fn`` threaded
through ``core/env.py`` / ``core/sweep.py``: it is callable as
``engine(a, b, axes)`` and returns a ``BlockSparseTensor``.  Per call it

1. fetches (or builds) the ``ContractionPlan`` for the contraction's
   structural signature from a ``PlanCache``, skipping the per-call hash
   join / charge bookkeeping the seed algorithms re-derive every time;
2. picks a backend — "list" (one tensordot per block pair), "dense" (embed +
   one GEMM), "batched" (shape-bucketed stacked GEMMs + segment-sum, see
   dist/batch.py), or "csr" (padded batched block GEMM) — either fixed or by
   a flop-and-dispatch cost model ("auto").  "auto" chooses between list,
   dense and batched; csr joins the auto candidate set only with
   ``allow_csr=True``, since without a real Pallas target (TPU) the csr
   execution path is not wall-time competitive however favorable its
   padded-flop count looks;
3. executes the plan and, when a ``BlockShardPolicy`` is attached, places the
   output blocks on the device mesh (outside jit; under tracing XLA owns
   layout).  Under an spmd-mode policy the backend choice is overridden:
   every contraction executes the batched bucket tables through the
   shard_map collective GEMM of ``dist/spmd.py`` (DESIGN.md 3.10), with
   operands device-resident and outputs replicated on the mesh.

``two_site_matvec`` is the planned Davidson matvec of paper Fig. 1d;
``matvec_fn`` optionally jits it.  Because ``BlockSparseTensor`` is a pytree
whose aux data (indices, charge, block keys) is static, jax's own trace cache
keys compiled executables by block structure, so repeated sweeps at the same
bond dimensions reuse both the plans and the compiled matvec.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels.block_gemm.ops import block_sparse_matmul
from ..tensor.block_csr import pack_blocks
from ..tensor.blocksparse import BlockKey, BlockSparseTensor
from .batch import (
    execute_batched,
    execute_pairs,
    is_tracing as _is_tracing,
    matricize_lhs,
    matricize_rhs,
    memo_dev_idx,
)
from . import persist, spmd as spmd_mod
from .decomp import DecompositionEngine
from .envcore import EnvironmentEngine
from .plan import Axes, ContractionPlan, PlanCache, global_plan_cache
from .shard import BlockShardPolicy

# cost-model overhead charged per dispatched block GEMM, in equivalent flops:
# on small DMRG blocks the per-op dispatch dominates, which is exactly why the
# paper's dense algorithm wins at small m (their Fig. 5 crossover).
PAIR_OVERHEAD_FLOPS = 16384.0

class ContractionEngine:
    """Executes cached ContractionPlans through a pluggable backend.

    Backend-equality guarantee: every backend ("list", "dense", "csr",
    "batched") and the "auto" cost-model choice computes the same
    charge-conserving contraction — output blocks match the seed list
    algorithm to <=1e-12 on random tensors and DMRG energies to <1e-10
    (tests/test_dist.py, tests/test_batch.py); sharding via ``policy`` is a
    pure layout hint and never changes values.  ``svd_split`` fronts the
    decomposition engine with the analogous guarantee (``dist.decomp``).
    ``stats()`` documents the units of every counter it reports.
    """

    def __init__(
        self,
        backend: str = "auto",
        cache: Optional[PlanCache] = None,
        policy: Optional[BlockShardPolicy] = None,
        *,
        use_kernel: bool = False,
        interpret: bool = False,  # compiled Pallas by default, like block_csr
        allow_csr: bool = False,
        pair_overhead: float = PAIR_OVERHEAD_FLOPS,
        decomp: Optional[DecompositionEngine] = None,
        env: Optional[EnvironmentEngine] = None,
    ):
        assert backend in ("auto", "list", "dense", "csr", "batched")
        self.backend = backend
        self.cache = cache if cache is not None else global_plan_cache
        self.policy = policy
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.allow_csr = allow_csr
        self.pair_overhead = pair_overhead
        # decomposition stage (dist/decomp.py): per-engine so stats() reports
        # this run's SVD counters, sharing the global DecompPlanCache
        self.decomp = decomp if decomp is not None else DecompositionEngine()
        # environment stage (dist/envcore.py): per-engine for the same
        # reason, sharing the global EnvPlanCache and its compiled cores
        self.env = env if env is not None else EnvironmentEngine()
        zero = {"list": 0, "dense": 0, "csr": 0, "batched": 0, "spmd": 0}
        self.backend_counts: Dict[str, int] = dict(zero)
        self.backend_flops: Dict[str, float] = {k: 0.0 for k in zero}
        self.jit_retraces = 0
        self._jit_mv = None
        # loaded/attempted matvec exports keyed by (conf, operand, x)
        # structure: deserializing + jit-wrapping an artifact costs real time,
        # so it must happen once per structure per process, not per solve
        self._export_mv: Dict = {}
        # degradation ladder ledger (DESIGN.md 3.8): stage-keyed counts of
        # failed first attempts and which lower rung recovered them.  Shared
        # with the sweep layer via note_retry/note_degradation so one
        # stats() call reports the whole run's recovery history.
        self.retries: Dict[str, int] = {}
        self.degradations: Dict[str, int] = {}

    # ------------------------------------------------------ health bookkeeping
    def note_retry(self, stage: str) -> None:
        """Record a failed first attempt at ``stage`` (sweep layers call this
        so per-run recovery counts live on the engine the run owns)."""
        self.retries[stage] = self.retries.get(stage, 0) + 1

    def note_degradation(self, stage: str) -> None:
        """Record that ``stage`` recovered on a lower ladder rung."""
        self.degradations[stage] = self.degradations.get(stage, 0) + 1

    # ----------------------------------------------------------------- entry
    def __call__(
        self,
        a: BlockSparseTensor,
        b: BlockSparseTensor,
        axes: Axes,
        *,
        a_mats=None,
        b_mats=None,
    ) -> BlockSparseTensor:
        plan = self.cache.get(a, b, axes)
        if self._spmd_mode:
            # spmd-mode policy: every contraction runs the shard_map bucket
            # GEMMs (dist/spmd.py) so compute partitions over the mesh
            backend = "spmd"
        elif self.backend != "auto":
            backend = self.backend
        else:
            backend = self.choose_backend(plan)
        self.backend_counts[backend] += 1
        self.backend_flops[backend] += self._plan_flops(plan, backend)
        if (
            self.policy is not None
            and self.policy.storage_only
            and not (_is_tracing(a) or _is_tracing(b))
        ):
            a, b = self.policy.replicated(a), self.policy.replicated(b)
        if backend in ("batched", "spmd"):
            out = getattr(self, f"_execute_{backend}")(
                plan, a, b, a_mats=a_mats, b_mats=b_mats
            )
        else:
            out = getattr(self, f"_execute_{backend}")(plan, a, b)
        # spmd mode constrains output layout; storage mode leaves compute
        # results replicated — the sweep re-places what it actually stores
        if (
            self.policy is not None
            and not self.policy.storage_only
            and not _is_tracing(out)
        ):
            out = self.policy.place(out)
        return out

    # ------------------------------------------------------------ cost model
    def choose_backend(self, plan: ContractionPlan) -> str:
        # dense pays one GEMM over the padded full dims plus a per-block
        # dispatch for embedding/extraction (to_dense is .at[].set per block);
        # list pays per-pair GEMM dispatch; batched pays the exact list flops
        # but dispatches per unique operand block (matricize), per bucket
        # (stack + batched GEMM + segment-sum) and per output slot, all
        # cheaper than a GEMM dispatch; csr pays padding flops but a single
        # batched kernel.  All in equivalent flops.
        n_embed = plan.num_in_blocks + len(plan.out_keys)
        cost = {
            "list": plan.flops_list + self.pair_overhead * plan.num_pairs,
            "dense": plan.flops_dense + self.pair_overhead * n_embed,
        }
        if plan.num_pairs:
            L = plan.batched
            n_disp = 0.5 * L.num_unique + 2.0 * L.num_buckets + 0.25 * L.num_out_slots
            cost["batched"] = plan.flops_list + self.pair_overhead * n_disp
        if self.allow_csr and plan.num_pairs:
            cost["csr"] = plan.flops_csr + self.pair_overhead * plan.num_pairs * 0.25
        return min(cost, key=cost.get)

    @staticmethod
    def _plan_flops(plan: ContractionPlan, backend: str) -> float:
        if backend == "dense":
            return plan.flops_dense
        if backend == "csr":
            return plan.flops_csr if plan.num_pairs else 0.0
        # list, batched and spmd execute the exact pair flops (spmd's P/N
        # divisibility zero-padding adds no counted work)
        return plan.flops_list

    @property
    def _spmd_mode(self) -> bool:
        return self.policy is not None and self.policy.mode == "spmd"

    # -------------------------------------------------------------- backends
    def _execute_list(
        self, plan: ContractionPlan, a: BlockSparseTensor, b: BlockSparseTensor
    ) -> BlockSparseTensor:
        out_blocks = execute_pairs(plan, a.blocks, b.blocks)
        return BlockSparseTensor(plan.out_indices, out_blocks, plan.out_charge)

    def _execute_dense(
        self, plan: ContractionPlan, a: BlockSparseTensor, b: BlockSparseTensor
    ) -> BlockSparseTensor:
        dense = jnp.tensordot(a.to_dense(), b.to_dense(), axes=(plan.ax_a, plan.ax_b))
        blocks = {k: dense[sl] for k, sl in plan.dense_out_slices()}
        return BlockSparseTensor(plan.out_indices, blocks, plan.out_charge)

    def _execute_batched(
        self,
        plan: ContractionPlan,
        a: BlockSparseTensor,
        b: BlockSparseTensor,
        *,
        a_mats=None,
        b_mats=None,
    ) -> BlockSparseTensor:
        return execute_batched(
            plan,
            a,
            b,
            a_mats=a_mats,
            b_mats=b_mats,
            use_kernel=self.use_kernel,
            interpret=self.interpret,
            mesh=self._mesh_key(),
        )

    def _execute_spmd(
        self,
        plan: ContractionPlan,
        a: BlockSparseTensor,
        b: BlockSparseTensor,
        *,
        a_mats=None,
        b_mats=None,
    ) -> BlockSparseTensor:
        """The batched bucket tables executed through the shard_map
        collective GEMM (dist/spmd.py): pairs over "row", output columns
        over "col", one psum + one all_gather per bucket."""
        return execute_batched(
            plan,
            a,
            b,
            a_mats=a_mats,
            b_mats=b_mats,
            mesh=self._mesh_key(),
            gemm_fn=spmd_mod.make_spmd_gemm(
                self.policy.mesh, self.policy.row_axis, self.policy.col_axis
            ),
        )

    def _mesh_key(self):
        return None if self.policy is None else self.policy.mesh

    def _execute_csr(
        self, plan: ContractionPlan, a: BlockSparseTensor, b: BlockSparseTensor
    ) -> BlockSparseTensor:
        if not plan.pairs:
            return BlockSparseTensor(plan.out_indices, {}, plan.out_charge)
        L = plan.csr
        lhs_all = pack_blocks(a, L.a_keys, plan.keep_a, plan.ax_a, L.bm, L.bk, True)
        rhs_all = pack_blocks(b, L.b_keys, plan.keep_b, plan.ax_b, L.bk, L.bn, False)
        li, ri, oi = memo_dev_idx(
            L,
            self._mesh_key(),
            _is_tracing(a) or _is_tracing(b),
            (L.li, L.ri, L.oi),
        )
        lhs = lhs_all[li]
        rhs = rhs_all[ri]
        out_padded = block_sparse_matmul(
            lhs,
            rhs,
            oi,
            len(L.out_keys),
            interpret=self.interpret,
            use_kernel=self.use_kernel,
        )
        out_blocks: Dict[BlockKey, jax.Array] = {}
        for o, (kc, (r, c)) in enumerate(zip(L.out_keys, L.out_rc)):
            out_blocks[kc] = out_padded[o, :r, :c].reshape(plan.out_block_shape(kc))
        return BlockSparseTensor(plan.out_indices, out_blocks, plan.out_charge)

    # ------------------------------------------------------- two-site matvec
    def two_site_matvec(
        self,
        A: BlockSparseTensor,
        Wj: BlockSparseTensor,
        Wj1: BlockSparseTensor,
        B: BlockSparseTensor,
        x: BlockSparseTensor,
        mats=None,
    ) -> BlockSparseTensor:
        """y = K x with K = A . W_j . W_{j+1} . B (paper Fig. 1d).

        ``mats`` optionally carries the pre-matricized fixed operands
        (A as lhs of step 1; W_j, W_{j+1}, B as rhs of steps 2-4), computed
        once per Davidson solve by ``matvec_fn`` instead of inside every
        call; only the batched backend consumes them.
        """
        mA, mWj, mWj1, mB = mats if mats is not None else (None,) * 4
        t = self(A, x, ((2,), (0,)), a_mats=mA)
        t = self(t, Wj, ((1, 2), (0, 2)), b_mats=mWj)
        t = self(t, Wj1, ((4, 1), (0, 2)), b_mats=mWj1)
        t = self(t, B, ((4, 1), (1, 2)), b_mats=mB)
        return t

    def _fixed_operand_mats(self, A, Wj, Wj1, B):
        """Matricized fixed Davidson operands for the batched backend.

        The matricization axes are static per matvec step (A contracts its
        mode 2 in step 1; W_j / W_{j+1} contract modes (0, 2); B contracts
        modes (1, 2)), so these 2-D forms never depend on x's structure.
        """
        return (
            matricize_lhs(A, (0, 1), (2,)),
            matricize_rhs(Wj, (1, 3), (0, 2)),
            matricize_rhs(Wj1, (1, 3), (0, 2)),
            matricize_rhs(B, (0,), (1, 2)),
        )

    def matvec_fn(
        self,
        A: BlockSparseTensor,
        Wj: BlockSparseTensor,
        Wj1: BlockSparseTensor,
        B: BlockSparseTensor,
        jit: bool = False,
    ) -> Callable[[BlockSparseTensor], BlockSparseTensor]:
        """Davidson matvec closure; with ``jit=True`` the planned pipeline is
        compiled once per block structure (plan metadata is static aux)."""
        if self.policy is not None and self.policy.storage_only:
            # gather the fixed operands once, not on every Davidson iteration
            A = self.policy.replicated(A)
            Wj = self.policy.replicated(Wj)
            Wj1 = self.policy.replicated(Wj1)
            B = self.policy.replicated(B)
        # "auto" may route any matvec step to the batched backend, and spmd
        # mode routes every step through the bucketed spmd GEMM, so both
        # precompute the fixed-operand mats (unused steps ignore them)
        mats = (
            self._fixed_operand_mats(A, Wj, Wj1, B)
            if self.backend in ("batched", "auto") or self._spmd_mode
            else None
        )
        if not jit:
            return lambda x: self.two_site_matvec(A, Wj, Wj1, B, x, mats=mats)
        if self._jit_mv is None:

            def matvec_core(A_, Wj_, Wj1_, B_, mats_, x_):
                self.jit_retraces += 1  # body runs only when jax (re)traces
                return self.two_site_matvec(A_, Wj_, Wj1_, B_, x_, mats=mats_)

            self._jit_mv = jax.jit(matvec_core)
        store = persist.active_store()
        if store is None or self.policy is not None:
            # no store (or mesh-placed operands, whose shardings must not be
            # baked into a portable artifact): the plain jitted path
            return lambda x: self._jit_mv(A, Wj, Wj1, B, mats, x)
        return self._exported_matvec(store, A, Wj, Wj1, B, mats)

    def _exported_matvec(self, store, A, Wj, Wj1, B, mats):
        """Matvec closure backed by the persistent export store.

        The matvec is the dominant cold-start cost: every padded structure
        traces the whole planned pipeline through Python and lowers it to
        StableHLO even when the XLA *compile* hits the persistent cache.  A
        primed store replays the exported StableHLO directly — no re-trace,
        no re-lower.  The exported body takes the fixed-operand mats as
        positional tuples (their dict form, keyed by block keys, is not a
        serializable treedef) with the key lists folded in as statics; x's
        structure keys the per-solve memo because Davidson solves at
        different sites share this engine's ``_jit_mv`` but not avals.
        A missing entry exports best-effort and falls back to ``_jit_mv``.
        """
        engine = self
        mat_keys = mats_vals = None
        if mats is not None:
            mat_keys = tuple(tuple(sorted(d)) for d in mats)
            mats_vals = tuple(
                tuple(d[k] for k in ks) for d, ks in zip(mats, mat_keys)
            )

        def _export_body(A_, Wj_, Wj1_, B_, mv_, x_):
            mats_ = (
                tuple(dict(zip(ks, vs)) for ks, vs in zip(mat_keys, mv_))
                if mv_ is not None
                else None
            )
            return engine.two_site_matvec(A_, Wj_, Wj1_, B_, x_, mats=mats_)

        ops_sig = tuple(
            (t.indices, t.charge, tuple(sorted(t.blocks)))
            for t in (A, Wj, Wj1, B)
        )
        conf = (self.backend, self.use_kernel, self.interpret, self.allow_csr)

        def call(x):
            if any(isinstance(b, jax.core.Tracer) for b in x.blocks.values()):
                # deserialized artifacts are opaque executables and cannot
                # be traced through (e.g. an outer vmap/jit over the solve)
                return engine._jit_mv(A, Wj, Wj1, B, mats, x)
            xsig = (x.indices, x.charge, tuple(sorted(x.blocks)))
            ekey = ("matvec", conf, ops_sig, xsig)
            fn = self._export_mv.get(ekey)
            if fn is None:
                args = (A, Wj, Wj1, B, mats_vals, x)
                fn = store.load_export(ekey, args)
                if fn is None:
                    store.save_export(ekey, _export_body, args)
                    fn = False  # remembered: this structure has no artifact
                self._export_mv[ekey] = fn
            if fn is False:
                return engine._jit_mv(A, Wj, Wj1, B, mats, x)
            return fn(A, Wj, Wj1, B, mats_vals, x)

        return call

    # ------------------------------------------------------------ decomp API
    def svd_split(
        self,
        theta: BlockSparseTensor,
        n_row_modes: int,
        max_bond: int,
        cutoff: float = 1e-12,
        absorb: str = "right",
    ):
        """Planned blockwise truncated SVD through the decomposition engine.

        Same signature and return value as the seed
        ``tensor.blocksparse.svd_split_unplanned`` and the same <1e-10
        equality guarantee (up to per-singular-vector sign gauge) as
        ``dist.decomp``; sharded inputs are gathered to replicated form
        first under a storage-mode policy, like contraction operands.
        """
        if (
            self.policy is not None
            and self.policy.storage_only
            and not _is_tracing(theta)
        ):
            theta = self.policy.replicated(theta)
        U, V, svals, err = self.decomp.svd_split(
            theta, n_row_modes, max_bond, cutoff=cutoff, absorb=absorb
        )
        if self.policy is not None and not self.policy.storage_only:
            U, V = self.policy.place(U), self.policy.place(V)
        return U, V, svals, err

    # --------------------------------------------------------------- env API
    def env_update_left(
        self,
        A: BlockSparseTensor,
        T: BlockSparseTensor,
        W: BlockSparseTensor,
        *,
        mpo_padded: Optional[BlockSparseTensor] = None,
    ) -> BlockSparseTensor:
        """Planned fused left env update through the environment engine.

        Same result as the seed ``core.env.extend_left(A, T, W)`` to <1e-10
        block-for-block (``dist.envcore``), executed as one compiled call;
        sharded inputs are gathered to replicated form first under a
        storage-mode policy, and the output is placed under an spmd policy,
        like contraction results.
        """
        return self._env_update("left", A, T, W, mpo_padded)

    def env_update_right(
        self,
        B: BlockSparseTensor,
        T: BlockSparseTensor,
        W: BlockSparseTensor,
        *,
        mpo_padded: Optional[BlockSparseTensor] = None,
    ) -> BlockSparseTensor:
        """Planned fused right env update; see ``env_update_left``."""
        return self._env_update("right", B, T, W, mpo_padded)

    def _env_update(self, side, env, T, W, mpo_padded):
        if (
            self.policy is not None
            and self.policy.storage_only
            and not (_is_tracing(env) or _is_tracing(T))
        ):
            env, T, W = (
                self.policy.replicated(env),
                self.policy.replicated(T),
                self.policy.replicated(W),
            )
            if mpo_padded is not None:
                # keep the caller's per-site padded-MPO cache: gathering the
                # padded form is cheaper than re-padding the gathered W on
                # every one of the 2(n-1) updates per sweep
                mpo_padded = self.policy.replicated(mpo_padded)
        fn = self.env.update_left if side == "left" else self.env.update_right
        out = fn(
            env,
            T,
            W,
            mpo_padded=mpo_padded,
            # spmd mode: the fused core's three contractions run as shard_map
            # bucket GEMMs on the policy mesh (envcore builds/caches the
            # spmd variant of the core per mesh)
            spmd_mesh=self.policy.mesh if self._spmd_mode else None,
        )
        if (
            self.policy is not None
            and not self.policy.storage_only
            and not _is_tracing(out)
        ):
            out = self.policy.place(out)
        return out

    # ------------------------------------------------------------- reporting
    def stats(self) -> Dict:
        """Plan-cache, backend-dispatch, flop and retrace counters.

        ``backend_counts`` / ``backend_flops`` increment when ``__call__``
        runs, i.e. at trace time under a jitted matvec — compiled replays
        bypass Python, so with ``jit_matvec=True`` they reflect unique traced
        structures, not total executed contractions.
        ``jit_retraces`` counts how many times the jitted matvec was
        (re)traced — the compile-time side of the ledger, vs steady-state
        replays.  ``decomp`` is the decomposition-stage sub-ledger (SVD
        calls/flops/retraces; see ``DecompositionEngine.stats``) and ``env``
        the environment-stage one (fused update count/flops/retraces; see
        ``EnvironmentEngine.stats``).  Stage times come from the spans of
        ``repro.obs`` under a profiler session.

        ``retries`` / ``degradations`` are the degradation-ladder ledger
        (DESIGN.md 3.8): stage-keyed counts of failed first attempts and the
        ladder rung that recovered them (e.g. ``env_seed``, ``pair_seed``).
        Both empty on a healthy run — the clean tier-1 bench leg asserts
        exactly that.
        """
        return {
            "plan_cache": self.cache.stats(),
            "backend_counts": dict(self.backend_counts),
            "backend_flops": dict(self.backend_flops),
            "jit_retraces": self.jit_retraces,
            "retries": dict(self.retries),
            "degradations": dict(self.degradations),
            "decomp": self.decomp.stats(),
            "env": self.env.stats(),
            # process-wide SPMD collective ledger (dist/spmd.py): gemm
            # calls, fallbacks, traced psum/all_gather counts.  Module-level
            # because compiled SPMD programs are shared across engines.
            "spmd": spmd_mod.stats(),
        }
