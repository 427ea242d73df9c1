"""Environment engine: plan-cached fused left/right env updates.

The environment stage (paper Fig. 1d, Sec. II-C) absorbs one site into the
left or right environment after every pair optimization — three chained
block-sparse contractions per site per half-sweep, plus a full right-to-left
rebuild at startup.  The seed ``extend_left`` / ``extend_right`` issue those
three contractions as separate eager calls: each pays a host-side plan
lookup, a per-pair GEMM dispatch fan-out, and materializes its intermediate
before the next call starts.  After PRs 1-3 industrialized the matvec and
the SVD split, this was the last uncompiled cost center of the sweep.

This module brings it under the plan/execute architecture, mirroring
``dist/decomp.py``:

1. An ``EnvironmentPlan`` (``dist/plan.py``, cached by the composite
   structural signature of the (env, site, MPO) triple + sweep direction)
   chains the three per-site ``ContractionPlan``s — fetched from the shared
   contraction ``PlanCache`` — and resolves every intermediate block
   structure ahead of time, including the bra (conjugate) structure and the
   final transpose.
2. ``EnvironmentEngine.update_left/right`` executes the plan as ONE fused
   jit-compiled core: all three contractions, the conjugation and the
   transpose trace into a single XLA program with no host round-trips
   between them — intermediates never materialize as Python-side tensors.
3. Operands are power-of-two padded first (``pad_block_sparse``, the same
   compile-once trick as the bucketed matvec): zero-padding is exact for
   contractions, and it quantizes the traced structure so the core compiles
   once per *bucketed* structure instead of once per site per sweep.  The
   result is sliced back to the true (unpadded) env structure, which is
   derived directly from the site/MPO indices.

Backend-equality guarantee: the fused core computes exactly the seed
three-contraction pipeline (same pair tables, list-order accumulation
within each step), so its output matches ``extend_left`` / ``extend_right``
block-for-block to <1e-10 on all backends (tests/test_env.py; DMRG
energies with ``jit_env=True`` equal seed to <1e-10).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..tensor.blocksparse import BlockSparseTensor
from ..tensor.qn import Index
from . import faults, persist
from .batch import execute_pairs, pad_block_sparse, unpad_block_sparse
from .faults import FaultInjected
from .plan import (
    EnvPlanCache,
    EnvironmentPlan,
    global_env_cache,
)


def env_out_indices(
    site: BlockSparseTensor, mpo: BlockSparseTensor, side: str
) -> Tuple[Index, ...]:
    """The (i', k', l') structure an env update produces, from operands alone.

    Left update: the new env bonds are the site tensor's *right* index (bra
    side dualized) and the MPO's right bond; right update symmetrically uses
    the left indices.  Used to slice the padded fused-core output back to
    the true structure — two different unpadded triples may share one padded
    plan, so the unpadded target cannot live on the plan.
    """
    if side == "left":
        return (site.indices[2].dual(), mpo.indices[3], site.indices[2])
    return (site.indices[0].dual(), mpo.indices[0], site.indices[0])


def env_core_body(plan: EnvironmentPlan):
    """All three contractions + conj + transpose, one traceable function.

    Module-level (like ``decomp.svd_core_body``) so the engine's jitted
    wrapper and the ``jax.export`` persistence path (dist/persist.py) trace
    the identical body.  Input: the (padded) env/site/MPO block arrays in
    the plan's sorted key order; output: env blocks in ``plan.out_keys``
    order.  Plan metadata folds into the trace as constants.
    """
    p1, p2, p3 = plan.steps
    left = plan.side == "left"
    perm = plan.perm

    def body(env_blocks, site_blocks, mpo_blocks):
        e = dict(zip(plan.env_keys, env_blocks))
        t = dict(zip(plan.site_keys, site_blocks))
        w = dict(zip(plan.mpo_keys, mpo_blocks))
        bra = {k: jnp.conj(v) for k, v in t.items()}
        if left:
            x = execute_pairs(p1, e, t)
            x = execute_pairs(p2, x, w)
            x = execute_pairs(p3, bra, x)
        else:
            x = execute_pairs(p1, t, e)
            x = execute_pairs(p2, x, w)
            x = execute_pairs(p3, x, bra)
        return tuple(jnp.transpose(x[k], perm) for k in plan.pre_out_keys)

    return body


class EnvironmentEngine:
    """Executes cached EnvironmentPlans as fused jitted env updates.

    Parameters
    ----------
    cache: ``EnvPlanCache`` (defaults to the global one, shared with any
        other engine — plans and their compiled cores are reused).
    jit: compile the fused three-contraction core once per padded structure
        (default); ``False`` runs the same fused body eagerly, for debugging.
    pad: power-of-two-pad the operands before planning (default).  Padding
        is exact (padded operator entries are zero) and quantizes the traced
        structure — without it every bond-sector drift during convergence
        retraces the core.

    ``stats()`` reports cumulative counters; see its docstring for units.
    """

    def __init__(
        self,
        cache: Optional[EnvPlanCache] = None,
        *,
        jit: bool = True,
        pad: bool = True,
    ):
        self.cache = cache if cache is not None else global_env_cache
        self.jit = jit
        self.pad = pad
        self.env_updates = 0
        self.env_flops = 0.0
        self.jit_retraces = 0

    # ------------------------------------------------------------- jit core
    def _build_core(self, plan: EnvironmentPlan, body=None):
        """Compile (or wrap eagerly) the shared ``env_core_body``.

        One compiled executable per padded block structure — plan metadata
        folds into the trace as constants.  ``body`` overrides the traced
        body (the spmd variant passes ``spmd.spmd_env_core_body``).
        """
        engine = self
        if body is None:
            body = env_core_body(plan)
        if not self.jit:
            return body

        def env_core(env_blocks, site_blocks, mpo_blocks):
            engine.jit_retraces += 1  # body runs only when jax (re)traces
            return body(env_blocks, site_blocks, mpo_blocks)

        return jax.jit(env_core)

    # ----------------------------------------------------------------- entry
    def update_left(
        self,
        A: BlockSparseTensor,
        T: BlockSparseTensor,
        W: BlockSparseTensor,
        *,
        mpo_padded: Optional[BlockSparseTensor] = None,
        spmd_mesh=None,
    ) -> BlockSparseTensor:
        """A' = A · T · W · conj(T): absorb site T into the left env.

        ``spmd_mesh`` (a ("row","col") mesh) switches the fused core to the
        shard_map-collective variant (``dist/spmd.py``): same plan, same
        three contractions, bucket GEMMs partitioned over the mesh, fused
        into one compiled core (safe because the bucket programs keep
        replicated shard_map boundaries; see ``_update``).
        """
        return self._update("left", A, T, W, mpo_padded, spmd_mesh)

    def update_right(
        self,
        B: BlockSparseTensor,
        T: BlockSparseTensor,
        W: BlockSparseTensor,
        *,
        mpo_padded: Optional[BlockSparseTensor] = None,
        spmd_mesh=None,
    ) -> BlockSparseTensor:
        """B' = T · W · conj(T) · B: absorb site T into the right env."""
        return self._update("right", B, T, W, mpo_padded, spmd_mesh)

    def _update(self, side, env, T, W, mpo_padded=None, spmd_mesh=None):
        # fault point: exception out of the fused env core, standing in for
        # a compilation/launch failure of the jitted program.  Raised before
        # any work so the caller's seed-extend fallback sees a clean slate.
        if faults.fire("env.exception") is not None:
            raise FaultInjected("env.exception", "fused env core failed")
        if self.pad:
            # the MPO is immutable for a run, so callers (the sweep) may pass
            # its padded form once instead of re-padding every site visit
            env_p = pad_block_sparse(env)
            T_p = pad_block_sparse(T)
            W_p = mpo_padded if mpo_padded is not None else pad_block_sparse(W)
        else:
            env_p, T_p, W_p = env, T, W
        plan = self.cache.get(env_p, T_p, W_p, side)
        args = (
            tuple(env_p.blocks[k] for k in plan.env_keys),
            tuple(T_p.blocks[k] for k in plan.site_keys),
            tuple(W_p.blocks[k] for k in plan.mpo_keys),
        )
        # export round-trip (dist/persist.py), mirroring the decomp engine:
        # primed store -> replay StableHLO, no Python re-trace; cold run
        # with store -> export what was built (best-effort).  Deserialized
        # artifacts are opaque executables, so the path is skipped entirely
        # when the operands are tracers (the stacked serve pipeline vmaps
        # through this engine) — only the traceable built core can inline.
        core = None
        tracing = any(
            isinstance(x, jax.core.Tracer) for xs in args for x in xs
        )
        if spmd_mesh is not None:
            # spmd cores close over a live mesh (shard_map) — never
            # exportable, cached per mesh so globally shared plans don't
            # replay one mesh's program under another.  Jitting the fused
            # core over the inlined shard_map programs is safe ONLY because
            # the bucket GEMMs keep replicated boundaries (dist/spmd.py):
            # sharded shard_map in_specs under an enclosing jit trigger the
            # XLA partitioner's rematerialization path, which corrupts
            # values on CPU meshes (16x inflation observed).
            from .spmd import spmd_env_core_body

            key = ("spmd", spmd_mesh, self.jit)
            core = plan._exec.get(key)
            if core is None:
                core = self._build_core(
                    plan, body=spmd_env_core_body(plan, spmd_mesh)
                )
                plan._exec[key] = core
            blocks = core(*args)
            out = BlockSparseTensor(
                plan.out_indices, dict(zip(plan.out_keys, blocks)), plan.out_charge
            )
            if self.pad:
                out = unpad_block_sparse(out, env_out_indices(T, W, side))
            self.env_updates += 1
            self.env_flops += plan.flops
            return out
        store = persist.active_store() if self.jit and not tracing else None
        if store is not None:
            core = plan._exec.get("export")
            if core is None:
                ekey = ("env_core", plan.signature)
                core = store.load_export(ekey, args)
                if core is None:
                    store.save_export(ekey, env_core_body(plan), args)
                    core = False  # remembered: no artifact for this plan
                plan._exec["export"] = core
            if core is False:
                core = None
        if core is None:
            core = plan._exec.get(self.jit)
            if core is None:
                core = self._build_core(plan)
                plan._exec[self.jit] = core
        blocks = core(*args)
        out = BlockSparseTensor(
            plan.out_indices, dict(zip(plan.out_keys, blocks)), plan.out_charge
        )
        if self.pad:
            out = unpad_block_sparse(out, env_out_indices(T, W, side))
        self.env_updates += 1
        self.env_flops += plan.flops
        return out

    # ------------------------------------------------------------- reporting
    def stats(self) -> Dict:
        """Cumulative environment-stage counters.

        - ``plan_cache``: hits/misses/size of the EnvPlanCache.
        - ``env_updates``: number of fused left/right updates executed.
        - ``env_flops``: summed pair-table flops of the executed plans —
          counted on the *padded* structure (what actually runs), a
          cost-model estimate, not a hardware counter.
        - ``jit_retraces``: times the fused core was (re)traced; with
          padding on, this stops growing at structural steady state
          (compile-once).  Cores are cached on the globally shared plan, so
          a trace is attributed to the engine that first compiled it.
        """
        return {
            "plan_cache": self.cache.stats(),
            "env_updates": self.env_updates,
            "env_flops": self.env_flops,
            "jit_retraces": self.jit_retraces,
        }


# Shared default engine (module-level so plans and compiled cores persist
# across calls); sweep-owned ContractionEngines carry their own
# EnvironmentEngine for per-run stats.
default_env_engine = EnvironmentEngine()
