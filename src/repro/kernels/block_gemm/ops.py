"""Jit'd public wrapper for the block-sparse GEMM kernel.

Handles pair sorting and MXU-tile padding.  The compiled kernel is a TPU
target; ``interpret=True``, which CPU tests pass themselves, executes the
kernel body in Python (per-kernel tests assert allclose vs ``ref.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import block_sparse_matmul as _kernel_call
from .ref import block_sparse_matmul_ref


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(
    jax.jit, static_argnames=("num_out", "bm", "bn", "bk", "interpret")
)
def _kernel_covered(
    lhs: jax.Array,
    rhs: jax.Array,
    out_idx: jax.Array,
    num_out: int,
    *,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jax.Array:
    """Pallas path; every output id in [0, num_out) must appear in out_idx."""
    P, BM, BK = lhs.shape
    _, _, BN = rhs.shape

    def _pad_dim(d: int, tile: int, align: int) -> int:
        p = _round_up(d, align)  # sublane/lane alignment
        return _round_up(p, tile) if p > tile else p  # tile divisibility

    pm = _pad_dim(BM, bm, 8)
    pk = _pad_dim(BK, bk, 128)
    pn = _pad_dim(BN, bn, 128)
    lhs_p = jnp.pad(lhs, ((0, 0), (0, pm - BM), (0, pk - BK)))
    rhs_p = jnp.pad(rhs, ((0, 0), (0, pk - BK), (0, pn - BN)))
    out = _kernel_call(
        lhs_p,
        rhs_p,
        out_idx.astype(jnp.int32),
        num_out,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=interpret,
    )
    return out[:, :BM, :BN]


_ref_jit = jax.jit(block_sparse_matmul_ref, static_argnames=("num_out",))

# The segment-sum's scatter-add stages one [M, N] update window per pair in
# VMEM.  Above this many elements a v5e can refuse it (a float64 bucket
# with M*N = 2**20 asked for 20 MiB of scoped VMEM against a 16 MiB limit),
# so larger products are reduced in row chunks of at most this window.
SCATTER_WINDOW = 1 << 16


def _ref_rows_chunked(lhs, rhs, out_idx, num_out):
    """``block_sparse_matmul_ref`` with the rows of each product cut into
    chunks whose scatter window fits ``SCATTER_WINDOW``; a bucket that
    already fits runs as one call, unchanged."""
    m, n = lhs.shape[1], rhs.shape[2]
    rows = max(1, SCATTER_WINDOW // n)
    if m <= rows:
        return _ref_jit(lhs, rhs, out_idx, num_out)
    return jnp.concatenate(
        [
            _ref_jit(lhs[:, r:r + rows], rhs, out_idx, num_out)
            for r in range(0, m, rows)
        ],
        axis=1,
    )


def block_sparse_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    out_idx: jax.Array,
    num_out: int,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    """Batched block-sparse GEMM: out[o] = sum_{p:out_idx[p]=o} lhs[p]@rhs[p].

    ``lhs``: [P, BM, BK]; ``rhs``: [P, BK, BN]; ``out_idx``: [P] int32 sorted.
    Pads BM/BK/BN up to multiples of the tile sizes (MXU alignment), runs the
    Pallas kernel, and slices the padding back off.

    Output blocks with no contributing pair are zero-filled: the ref path's
    ``segment_sum`` does this natively, and the Pallas kernel — which
    requires full output coverage — is handled by compacting to the covered
    ids and scattering into zeros.  Coverage is checked when ``out_idx`` is
    host-resident (numpy); plan-built device index tables always cover their
    outputs by construction and skip the check.
    """
    if not use_kernel:
        return _ref_rows_chunked(lhs, rhs, out_idx, num_out)
    kw = dict(bm=bm, bn=bn, bk=bk, interpret=interpret)
    if isinstance(out_idx, np.ndarray):
        covered = np.unique(out_idx)
        if covered.size < num_out:
            remap = np.zeros(num_out, np.int32)
            remap[covered] = np.arange(covered.size, dtype=np.int32)
            compact = _kernel_covered(
                lhs, rhs, remap[out_idx], int(covered.size), **kw
            )
            _, BM, _ = lhs.shape
            _, _, BN = rhs.shape
            zeros = jnp.zeros((num_out, BM, BN), compact.dtype)
            return zeros.at[covered].set(compact)
    return _kernel_covered(lhs, rhs, out_idx, num_out, **kw)


def pack_pairs(pairs, num_out):
    """Sort (lhs_i, rhs_i, out_i) triples by out block id; return index arrays.

    Output ids must lie in ``[0, num_out)`` (raises ``ValueError`` otherwise)
    but need not cover it: output blocks with zero contributing pairs are
    legal and come back zero-filled from ``block_sparse_matmul`` — the ref
    path's ``segment_sum`` zero-fills missing segments natively, and the
    Pallas path compacts to the covered ids and scatters into zeros.  That
    coverage check needs a host-resident (numpy) ``out_idx``, which is what
    this function returns; device-resident ids passed to the Pallas path
    are assumed to cover every output (see ``block_sparse_matmul``).
    """
    if not len(pairs):
        raise ValueError("pack_pairs: empty pair list")
    pairs = sorted(pairs, key=lambda t: t[2])
    li = np.array([p[0] for p in pairs], np.int32)
    ri = np.array([p[1] for p in pairs], np.int32)
    oi = np.array([p[2] for p in pairs], np.int32)
    if oi[0] < 0 or oi[-1] >= num_out:
        raise ValueError(
            f"pack_pairs: output ids must lie in [0, {num_out}), "
            f"got range [{oi[0]}, {oi[-1]}]"
        )
    return li, ri, oi
