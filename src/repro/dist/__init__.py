"""Distributed contraction + decomposition engines (DESIGN.md Sec. 3).

Layers, mirroring the paper's separation of symbolic planning from numeric
execution:

- ``plan``:   ``ContractionPlan`` — the static (lhs, rhs) -> out block-pair
              table, output indices/charges and matricized shapes — and
              ``DecompositionPlan`` — sector grouping, row/col layouts and
              the gather tables of the blockwise SVD — each derived once per
              block structure and cached by structural signature.
- ``persist``: ``PlanStore`` — versioned on-disk persistence for the three
              plan caches and
              ``jax.export``ed bucket cores, plus the one rule that picks
              the JAX persistent compilation cache directory, so a fresh
              process's first sweep skips the plan/trace/compile pipeline
              (DESIGN.md Sec. 3.9).
- ``shard``:  ``BlockShardPolicy`` — places blocks on the 2-D ("row",
              "col") mesh: "spmd" mode pins tensors device-resident
              (replicated, uploaded once) for shard_map compute; "storage"
              mode keeps the sharded-storage / gather-before-compute
              fallback with divisibility-aware mode assignment.
- ``spmd``:   the true-SPMD compute layer (DESIGN.md 3.10): each shape
              bucket's stacked GEMM as ONE shard_map program over the mesh
              (pairs over "row", output columns over "col", one psum + one
              tiled all_gather per bucket), plus the spmd variant of the
              fused env core and the process-wide collective ledger
              (``spmd.stats()``).
- ``batch``:  shape-bucketed batched contraction execution (stacked
              same-shape GEMMs + segment-sum scatter) and the power-of-two
              sector padding that makes the jitted matvec compile once.
- ``decomp``: ``DecompositionEngine`` — the blockwise truncated SVD executed
              as one batched SVD per padded shape bucket (host LAPACK
              for float64), with a single host sync for the global
              truncation and an optional randomized-SVD path.
- ``envcore``: ``EnvironmentEngine`` — the left/right environment updates
              (and the startup right-to-left rebuild) executed as ONE fused
              jitted core per padded structure: the three chained
              contractions of ``extend_left``/``extend_right`` with no host
              round-trips between them.
- ``faults``: deterministic fault injection — named injection points armed
              via ``inject(...)`` / ``REPRO_FAULTS`` — plus the
              ``NumericalHealthError`` the health guards raise (DESIGN.md
              Sec. 3.8).
- ``engine``: ``ContractionEngine`` — executes plans through a pluggable
              list / dense / csr / batched backend chosen by a
              flop-and-dispatch cost model, jits the planned two-site
              matvec, and fronts the decomposition engine (``svd_split``)
              and the environment engine (``env_update_left/right``).

All execution paths compute the same physics: every backend and the planned
SVD agree with the seed algorithms to <1e-10 (tests/test_dist.py,
tests/test_batch.py, tests/test_decomp.py).
"""
from .batch import pad_block_sparse, unpad_block_sparse
from .decomp import DecompositionEngine, svd_split_planned
from .engine import ContractionEngine
from .envcore import EnvironmentEngine
from .faults import (
    FAULT_POINTS,
    FaultInjected,
    FaultRegistry,
    NumericalHealthError,
    inject,
    registry as fault_registry,
)
from .persist import (
    PERSIST_VERSION,
    PlanStore,
    activate_store,
    active_store,
    canonical_signature,
    deactivate_store,
    configure_compilation_cache,
    signature_digest,
    store_stats,
    using_store,
)
from .plan import (
    ContractionPlan,
    DecompPlanCache,
    DecompositionPlan,
    EnvPlanCache,
    EnvironmentPlan,
    PlanCache,
    get_decomp_plan,
    get_env_plan,
    get_plan,
    global_decomp_cache,
    global_env_cache,
    global_plan_cache,
)
from .shard import BlockShardPolicy, make_block_mesh
from .spmd import (
    make_spmd_gemm,
    spmd_bucket_gemm,
    stats as spmd_stats,
)


def cache_stats(*engines) -> dict:
    """One dict aggregating the three global plan caches' hit/miss/eviction
    counters plus any passed-in engine ``stats()`` ledgers.

    The serving subsystem's stats endpoint and the ``--stats-json`` flags on
    the example drivers dump this; keys are stable so dashboards can diff
    runs.  ``engines`` may be ``ContractionEngine`` instances (anything with
    a ``stats()`` method); their ledgers land under ``"engines"`` in call
    order.  ``plan_store`` is the active persistent store's ledger
    (hits/misses/saves/corrupt/stale plus the export family; see
    ``persist.PlanStore.stats``), or None when no store is attached.
    """
    out = {
        "plan_cache": global_plan_cache.stats(),
        "decomp_plan_cache": global_decomp_cache.stats(),
        "env_plan_cache": global_env_cache.stats(),
        "plan_store": store_stats(),
    }
    if engines:
        out["engines"] = [e.stats() for e in engines]
    return out


__all__ = [
    "ContractionEngine",
    "ContractionPlan",
    "DecompositionEngine",
    "DecompositionPlan",
    "DecompPlanCache",
    "EnvironmentEngine",
    "EnvironmentPlan",
    "EnvPlanCache",
    "PlanCache",
    "get_plan",
    "get_decomp_plan",
    "get_env_plan",
    "global_plan_cache",
    "global_decomp_cache",
    "global_env_cache",
    "cache_stats",
    "PERSIST_VERSION",
    "PlanStore",
    "activate_store",
    "active_store",
    "canonical_signature",
    "deactivate_store",
    "configure_compilation_cache",
    "signature_digest",
    "store_stats",
    "using_store",
    "FAULT_POINTS",
    "FaultInjected",
    "FaultRegistry",
    "NumericalHealthError",
    "inject",
    "fault_registry",
    "svd_split_planned",
    "BlockShardPolicy",
    "make_block_mesh",
    "make_spmd_gemm",
    "spmd_bucket_gemm",
    "spmd_stats",
    "pad_block_sparse",
    "unpad_block_sparse",
]
