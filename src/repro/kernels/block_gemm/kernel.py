"""Pallas TPU kernel: batched block-sparse GEMM with scalar-prefetched routing.

This is the TPU-native adaptation of the paper's *sparse-sparse* contraction
algorithm (Sec. IV-A).  Cyclops contracts one distributed element-sparse
tensor pair per Davidson step; the TPU analogue keeps the sparsity at block
(tile) granularity: a static table of (lhs block, rhs block) -> output block
pairs, executed as ONE kernel launch (the paper's O(1) BSP supersteps), with
the MXU running dense 128-aligned tiles inside each quantum-number block.

Layout:
  lhs      [P, BM, BK]   packed/padded LHS block per pair
  rhs      [P, BK, BN]   packed/padded RHS block per pair
  out_idx  [P] int32     output block id per pair, MUST be sorted ascending,
                         and every o in [0, num_out) must appear at least once
                         (pack so each output block has >= 1 contributing pair)
  out      [O, BM, BN]   accumulated output blocks

Grid is (BM/bm, BN/bn, P, BK/bk) — pairs sweep contiguously for a fixed
output-tile position with k innermost, so consecutive pairs hitting the same
output block accumulate in a float32 VMEM scratch without round-tripping to
HBM.  The output BlockSpec index_map reads the scalar-prefetched ``out_idx``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(out_idx_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, nk: int):
    p = pl.program_id(2)
    k = pl.program_id(3)
    num_p = pl.num_programs(2)

    # first visit of this output tile by this group of pairs
    prev = out_idx_ref[jnp.maximum(p - 1, 0)]
    new_group = jnp.logical_or(p == 0, out_idx_ref[p] != prev)

    @pl.when(jnp.logical_and(new_group, k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[0], rhs_ref[0], preferred_element_type=acc_ref.dtype
    )

    # flush when this is the last k-step of the last pair of the group
    nxt = out_idx_ref[jnp.minimum(p + 1, out_idx_ref.shape[0] - 1)]
    last_of_group = jnp.logical_or(p == num_p - 1, out_idx_ref[p] != nxt)

    @pl.when(jnp.logical_and(last_of_group, k == nk - 1))
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def block_sparse_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    out_idx: jax.Array,
    num_out: int,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """out[o] = sum_{p: out_idx[p]==o} lhs[p] @ rhs[p] via one pallas_call."""
    P, BM, BK = lhs.shape
    _, BK2, BN = rhs.shape
    assert BK == BK2 and out_idx.shape == (P,)
    bm, bn, bk = min(bm, BM), min(bn, BN), min(bk, BK)
    assert BM % bm == 0 and BN % bn == 0 and BK % bk == 0
    nm, nn, nk = BM // bm, BN // bn, BK // bk
    out_dtype = out_dtype or lhs.dtype
    if not interpret and jnp.finfo(lhs.dtype).bits > 32:
        # Mosaic refuses 64-bit operands with a bare NotImplementedError
        raise TypeError(
            f"block_sparse_matmul: the compiled TPU kernel takes 32-bit or "
            f"narrower operands, got {jnp.dtype(lhs.dtype).name} (a TPU has "
            f"no float64 unit); use use_kernel=False for float64 blocks, or "
            f"interpret=True for CPU validation"
        )
    # accumulate in f32 on the MXU; f64 only in interpret mode (CPU
    # validation of float64 blocks)
    acc_dtype = jnp.float64 if lhs.dtype == jnp.float64 else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, P, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda m, n, p, k, idx: (p, m, k)),
            pl.BlockSpec((1, bk, bn), lambda m, n, p, k, idx: (p, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda m, n, p, k, idx: (idx[p], m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_out, BM, BN), out_dtype),
        interpret=interpret,
    )(out_idx, lhs, rhs)
