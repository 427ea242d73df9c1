"""True SPMD execution of the bucketed batched GEMMs via ``shard_map``.

This is the distributed-compute half the paper actually claims: instead of
gathering every block to host before computing (the ``BlockShardPolicy``
"storage" fallback), each shape bucket's stacked batched GEMM runs as ONE
SPMD program over the 2-D ("row", "col") device mesh, with collectives
replacing the host gather.

Mesh-axis mapping (per bucket GEMM ``lhs[P,M,K] @ rhs[P,K,N] -> out[O,M,N]``):

- ``P`` (the stacked block-pair axis) is sharded over the **"row"** mesh
  axis — each row shard owns a slice of the pairs and segment-sums its
  partial products locally, so the cross-shard reduction is ONE ``psum``
  over "row" per bucket (the paper's reduction over the processor rows
  that co-own a block's contributions).
- ``N`` (the output block columns) is sharded over the **"col"** mesh axis —
  each col shard computes its column slice, rejoined by ONE tiled
  ``all_gather`` over "col" per bucket.
- ``M``, ``K`` and the output-slot axis ``O`` are unsharded (they ride along
  replicated inside each shard).

Divisibility never forces the storage fallback: ``P`` is zero-padded up to a
multiple of the "row" size (padded pairs carry zero operands and point at
slot 0 — exactly zero contribution) and ``N`` up to a multiple of the "col"
size (the zero columns are sliced off after the gather), so any bucket runs
on any mesh.  Only when the padding would inflate the work past
``PAD_OVERHEAD_LIMIT`` does a call fall back to the plain replicated
segment-sum GEMM (no collectives; counted in ``stats()["fallback_calls"]``).

Equality guarantee: the SPMD bucket GEMM computes the same sum as the
single-device ``block_sparse_matmul`` reference with the per-pair products
reduced in a different association (local segment-sum per row shard, then
``psum``), so outputs agree to floating-point reassociation error — <=1e-12
on random f64 buckets (tests/test_spmd.py) and DMRG energies match the list
backend to <1e-10 at every device count in {1, 2, 4, 8}.

Host-sync count: zero.  Every function here returns device arrays without
blocking; inputs are uploaded once (device-resident replicated placement by
``BlockShardPolicy(mode="spmd")``) and outputs come back fully replicated on
the mesh, so downstream eager block math stays collective-free and the CPU
fake-device runtime cannot deadlock.  The only host syncs in an SPMD sweep
are the ones the sweep always had: the Davidson Rayleigh-Ritz read per
iteration and the one truncation sync per SVD split.

``spmd_env_core_body`` assembles the fused three-contraction environment
update (dist/envcore.py) from the same SPMD bucket GEMMs, so the env stage
partitions over the identical mesh axes as the matvec stage.

Compile unit: the outer fused matvec / env core, with the per-bucket
shard_map programs inlined.  Inlining shard_map under an enclosing
``jax.jit`` is safe here ONLY because the bucket programs keep *replicated
boundaries* (in/out specs all ``P()``, shards slice their own work chunk
inside the body — see ``_build_spmd_gemm``): sharded in_specs would make
XLA's partitioner insert layout transitions at the shard_map boundary,
which cost a reshard per call and, inside an enclosing jit, trigger its
"Involuntary full rematerialization" path that *corrupts values* (a 16x
inflation was observed on a (2, 4) CPU fake-device mesh).  With replicated
boundaries the glue between buckets fuses into the outer program and the
steady-state sweep runs at batched-backend speed plus one psum + one tiled
all_gather per bucket.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .plan import EnvironmentPlan

# padding a bucket past this work-inflation factor is slower than just
# computing it replicated; such calls take the collective-free fallback
PAD_OVERHEAD_LIMIT = 4.0

# ledger, reported by ``stats()``; see its docstring for counter semantics
_counters = {
    "gemm_calls": 0,
    "fallback_calls": 0,
    "psum_traced": 0,
    "all_gather_traced": 0,
}

# jitted SPMD executables keyed by (mesh, P, M, K, N, O): one compile per
# bucket shape per mesh, shared across plans, sites, sweeps and engines —
# the same executable-reuse story as kernels/block_gemm
_GEMM_CACHE: Dict = {}


def stats() -> Dict:
    """SPMD collective-execution counters (cumulative, process-wide).

    - ``gemm_calls``: Python-level entries into the SPMD bucket GEMM.  Under
      an outer jit (the compiled matvec / env core) these count trace-time
      calls, like the engine's ``backend_counts`` — compiled replays bypass
      Python.
    - ``fallback_calls``: of those, how many took the replicated no-collective
      fallback because padding would inflate work > ``PAD_OVERHEAD_LIMIT``.
    - ``psum_traced`` / ``all_gather_traced``: collectives *traced* into
      compiled SPMD programs (one each per unique bucket shape per mesh).
      Executed-collective counts per replay are ``2 * (gemm_calls -
      fallback_calls)`` for the structures those calls traced.
    - ``unique_programs``: distinct compiled SPMD executables alive.
    """
    return dict(_counters, unique_programs=len(_GEMM_CACHE))


def reset_stats() -> None:
    for k in _counters:
        _counters[k] = 0


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _build_spmd_gemm(mesh: Mesh, row_axis: str, col_axis: str,
                     p: int, m: int, k: int, n: int, num_out: int):
    """Jitted SPMD program for one bucket shape on one mesh.

    Replicated-boundary design: in_specs and out_specs are all ``P()`` —
    every device receives the full (replicated) operands and each shard
    *slices its own work chunk* inside the body via ``axis_index`` (pairs
    by "row" rank, output columns by "col" rank).  The alternative —
    sharded in_specs like ``P(row, None, col)`` — makes XLA's partitioner
    insert replicated->sharded layout transitions at the shard_map
    boundary; on CPU meshes those transitions both cost a reshard per call
    and, under an enclosing jit, trigger the partitioner's "Involuntary
    full rematerialization" path which *corrupts values* (16x inflation
    observed on a (2, 4) mesh).  With replicated boundaries there is
    nothing to reshard: the program is safe to inline into an outer jitted
    matvec or env core, and the only cross-device traffic is the one psum
    + one tiled all_gather per bucket.
    """
    rows = int(mesh.shape[row_axis])
    cols = int(mesh.shape[col_axis])
    pp = _ceil_to(p, rows)
    np_ = _ceil_to(n, cols)
    p_chunk = pp // rows
    n_chunk = np_ // cols

    def body(lhs, rhs, oi):
        _counters["psum_traced"] += 1
        _counters["all_gather_traced"] += 1
        r = jax.lax.axis_index(row_axis)
        c = jax.lax.axis_index(col_axis)
        lhs_loc = jax.lax.dynamic_slice_in_dim(lhs, r * p_chunk, p_chunk, 0)
        rhs_loc = jax.lax.dynamic_slice_in_dim(rhs, r * p_chunk, p_chunk, 0)
        rhs_loc = jax.lax.dynamic_slice_in_dim(rhs_loc, c * n_chunk, n_chunk, 2)
        oi_loc = jax.lax.dynamic_slice_in_dim(oi, r * p_chunk, p_chunk, 0)
        part = jax.ops.segment_sum(
            jnp.einsum("pmk,pkn->pmn", lhs_loc, rhs_loc),
            oi_loc,
            num_segments=num_out,
        )
        part = jax.lax.psum(part, row_axis)
        return jax.lax.all_gather(part, col_axis, axis=2, tiled=True)

    # the psum + tiled all_gather leave the output replicated, but shard_map
    # cannot infer that statically -> check_vma=False; equality is pinned by
    # tests/test_spmd.py instead
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    def fn(lhs, rhs, oi):
        # zero-padded pairs point at slot 0 with zero operands (exact); the
        # padded output columns are sliced off after the gather (exact)
        if pp != p:
            lhs = jnp.pad(lhs, ((0, pp - p), (0, 0), (0, 0)))
            rhs = jnp.pad(rhs, ((0, pp - p), (0, 0), (0, 0)))
            oi = jnp.pad(jnp.asarray(oi), (0, pp - p))
        if np_ != n:
            rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, np_ - n)))
        out = mapped(lhs, rhs, jnp.asarray(oi))
        return out[:, :, :n] if np_ != n else out

    return jax.jit(fn)


# replicated fallback: same semantics, no collectives — used when padding
# would inflate the bucket's work past PAD_OVERHEAD_LIMIT
@functools.partial(jax.jit, static_argnames=("num_out",))
def _ref_gemm(lhs, rhs, oi, *, num_out):
    return jax.ops.segment_sum(
        jnp.einsum("pmk,pkn->pmn", lhs, rhs), oi, num_segments=num_out
    )


def spmd_bucket_gemm(
    lhs, rhs, oi, num_out: int, *, mesh: Mesh,
    row_axis: str = "row", col_axis: str = "col",
    pad_overhead_limit: float = PAD_OVERHEAD_LIMIT,
):
    """``out[o] = sum_{p: oi[p]=o} lhs[p] @ rhs[p]`` as one SPMD program.

    Drop-in for ``kernels.block_gemm.ops.block_sparse_matmul`` (same
    contract), executed under ``shard_map`` over ``mesh`` with the pair axis
    on ``row_axis`` and the output columns on ``col_axis``; the result is
    fully replicated on the mesh.  See the module docstring for the
    mesh-axis mapping, padding rules and equality guarantee.
    """
    p, m, k = lhs.shape
    n = rhs.shape[2]
    _counters["gemm_calls"] += 1
    rows = int(mesh.shape[row_axis])
    cols = int(mesh.shape[col_axis])
    overhead = (_ceil_to(p, rows) * _ceil_to(n, cols)) / max(p * n, 1)
    if overhead > pad_overhead_limit:
        _counters["fallback_calls"] += 1
        return _ref_gemm(lhs, rhs, jnp.asarray(oi), num_out=num_out)
    key = (mesh, row_axis, col_axis, p, m, k, n, num_out)
    fn = _GEMM_CACHE.get(key)
    if fn is None:
        fn = _build_spmd_gemm(mesh, row_axis, col_axis, p, m, k, n, num_out)
        _GEMM_CACHE[key] = fn
    return fn(lhs, rhs, oi)


def make_spmd_gemm(mesh: Mesh, row_axis: str = "row", col_axis: str = "col"):
    """Bind a mesh: returns a ``gemm_fn(lhs, rhs, oi, num_out)`` for
    ``batch.execute_batched`` / ``batch.execute_batched_blocks``."""

    def gemm_fn(lhs, rhs, oi, num_out):
        return spmd_bucket_gemm(
            lhs, rhs, oi, num_out,
            mesh=mesh, row_axis=row_axis, col_axis=col_axis,
        )

    return gemm_fn


def spmd_env_core_body(plan: EnvironmentPlan, mesh: Mesh):
    """The fused env update with every contraction on the SPMD bucket GEMM.

    Same structure (and accumulation-order caveat: <=1e-12 reassociation
    instead of the exact list order) as ``envcore.env_core_body``; the
    three chained contractions run through ``execute_batched_blocks`` with
    the SPMD gemm, so intermediates never leave the mesh and the traced
    program's only cross-device traffic is the per-bucket psum/all_gather
    pairs.  Never exported to the plan store — shard_map programs close
    over a live mesh.
    """
    from .batch import execute_batched_blocks, matricize_lhs, matricize_rhs

    p1, p2, p3 = plan.steps
    left = plan.side == "left"
    perm = plan.perm
    gemm = make_spmd_gemm(mesh)

    def _step(p, a_blocks, b_blocks):
        if not p.pairs:
            return {}
        a_mats = matricize_lhs(a_blocks, p.keep_a, p.ax_a)
        b_mats = matricize_rhs(b_blocks, p.keep_b, p.ax_b)
        return execute_batched_blocks(
            p, a_mats, b_mats, mesh=mesh, gemm_fn=gemm
        )

    def body(env_blocks, site_blocks, mpo_blocks):
        e = dict(zip(plan.env_keys, env_blocks))
        t = dict(zip(plan.site_keys, site_blocks))
        w = dict(zip(plan.mpo_keys, mpo_blocks))
        bra = {k: jnp.conj(v) for k, v in t.items()}
        if left:
            x = _step(p1, e, t)
            x = _step(p2, x, w)
            x = _step(p3, bra, x)
        else:
            x = _step(p1, t, e)
            x = _step(p2, x, w)
            x = _step(p3, x, bra)
        return tuple(jnp.transpose(x[k], perm) for k in plan.pre_out_keys)

    return body


def replicate_sharding(mesh: Mesh) -> NamedSharding:
    """The fully-replicated mesh sharding device-resident tensors live in."""
    return NamedSharding(mesh, P())
