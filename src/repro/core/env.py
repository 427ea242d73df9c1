"""Left/right environment tensors (paper Fig. 1d and Sec. II-C).

Environment index convention (bra, mpo, ket):
  A_j (left env, sites < j):  i: IN (bra bond), k: OUT (mpo bond), l: OUT (ket bond)
  B_j (right env, sites > j): i: OUT, k: IN, l: IN
so that every contraction with site/MPO/bra tensors type-checks by flow.

The contraction backend is pluggable: "list" (paper Alg. 2), "dense"
(sparse-dense), "csr" (sparse-sparse, TPU block-CSR adaptation), "batched"
(shape-bucketed stacked GEMMs, dist/batch.py), or "auto" (cost-model
choice).  All of them now execute through the plan-cached
``dist.ContractionEngine``; ``get_contractor`` is kept as a thin compat shim
over it.  The ``*_unplanned`` names expose the seed per-call algorithms for
A/B benchmarking.

``extend_left`` / ``extend_right`` here are the seed environment updates —
three chained ``contract_fn`` calls — kept verbatim as the bare-contract
fallback and the reference the fused environment engine
(``dist/envcore.py``, ``jit_env`` in ``core/sweep.py``) is tested against
block-for-block.
"""
from __future__ import annotations

from typing import Callable, List

import jax.numpy as jnp

from ..dist.engine import ContractionEngine
from ..tensor.blocksparse import BlockSparseTensor, contract, contract_dense
from ..tensor.block_csr import contract_block_csr
from ..tensor.qn import IN, Index, OUT


def get_contractor(algo: str) -> Callable:
    """Compat shim: algorithm name -> plan-cached ContractionEngine.

    The returned object is callable as ``fn(a, b, axes)`` exactly like the
    bare contraction functions it replaces; sweep code that wants the engine
    extras (jitted matvec, sharding policy, the planned ``svd_split``
    decomposition stage, the fused ``env_update_left/right`` environment
    stage, stats) can use them when present.  Engine-backed
    names carry the <1e-10 seed-equality guarantee of ``dist.engine``; the
    ``*_unplanned`` names ARE the seed algorithms.
    """
    if algo in ("list", "dense", "batched"):
        return ContractionEngine(backend=algo)
    if algo == "csr":
        # compiled Pallas (a TPU target); interpret mode only where a caller
        # builds the engine with interpret=True itself, as CPU tests do
        return ContractionEngine(backend="csr", use_kernel=True)
    if algo == "csr_ref":
        return ContractionEngine(backend="csr", use_kernel=False)
    if algo in ("auto", "planned"):
        return ContractionEngine(backend="auto")
    # seed per-call algorithms, kept for A/B comparison in bench_dist
    if algo == "list_unplanned":
        return contract
    if algo == "dense_unplanned":
        return contract_dense
    if algo == "csr_unplanned":
        return lambda a, b, axes: contract_block_csr(a, b, axes, use_kernel=False)
    raise ValueError(f"unknown contraction algorithm: {algo}")


def left_edge(mps_t0: BlockSparseTensor, mpo_w0: BlockSparseTensor) -> BlockSparseTensor:
    lq = mps_t0.indices[0].sectors  # ((q0, 1),)
    kq = mpo_w0.indices[0].sectors
    i = Index(lq, IN, "env_i")
    k = Index(kq, OUT, "env_k")
    l = Index(lq, OUT, "env_l")
    return BlockSparseTensor([i, k, l], {(0, 0, 0): jnp.ones((1, 1, 1), mps_t0.dtype)})


def right_edge(mps_tn: BlockSparseTensor, mpo_wn: BlockSparseTensor) -> BlockSparseTensor:
    rq = mps_tn.indices[2].sectors
    kq = mpo_wn.indices[3].sectors
    i = Index(rq, OUT, "env_i")
    k = Index(kq, IN, "env_k")
    l = Index(rq, IN, "env_l")
    return BlockSparseTensor([i, k, l], {(0, 0, 0): jnp.ones((1, 1, 1), mps_tn.dtype)})


def extend_left(
    A: BlockSparseTensor,
    T: BlockSparseTensor,
    W: BlockSparseTensor,
    contract_fn: Callable = contract,
) -> BlockSparseTensor:
    """A' = A . T_j . W_j . conj(T_j), cost O(m^3 k d) + O(m^2 k^2 d^2)."""
    bra = T.conj()
    tmp = contract_fn(A, T, ((2,), (0,)))            # (i, k, s, r)
    tmp = contract_fn(tmp, W, ((1, 2), (0, 2)))      # (i, r, so, k')
    out = contract_fn(bra, tmp, ((0, 1), (0, 2)))    # (r_bra, r_ket, k')
    return out.transpose((0, 2, 1))                  # (i', k', l')


def extend_right(
    B: BlockSparseTensor,
    T: BlockSparseTensor,
    W: BlockSparseTensor,
    contract_fn: Callable = contract,
) -> BlockSparseTensor:
    """B' = T_j . W_j . conj(T_j) . B (absorb site j into the right env)."""
    bra = T.conj()
    tmp = contract_fn(T, B, ((2,), (2,)))            # (l, s, i', k')
    tmp = contract_fn(tmp, W, ((3, 1), (3, 2)))      # (l, i', lw, so)
    out = contract_fn(tmp, bra, ((1, 3), (2, 1)))    # (l, lw, l_bra)
    return out.transpose((2, 1, 0))                  # (i', k', l')


def matvec_two_site(
    A: BlockSparseTensor,
    Wj: BlockSparseTensor,
    Wj1: BlockSparseTensor,
    B: BlockSparseTensor,
    x: BlockSparseTensor,
    contract_fn: Callable = contract,
) -> BlockSparseTensor:
    """y = K x with K = A . W_j . W_{j+1} . B (paper Fig. 1d), O(m^3 k d)."""
    t = contract_fn(A, x, ((2,), (0,)))              # (i, k, s1, s2, r)
    t = contract_fn(t, Wj, ((1, 2), (0, 2)))         # (i, s2, r, so1, k1)
    t = contract_fn(t, Wj1, ((4, 1), (0, 2)))        # (i, r, so1, so2, k2)
    t = contract_fn(t, B, ((4, 1), (1, 2)))          # (i, so1, so2, i')
    return t


def expectation(
    mps_tensors: List[BlockSparseTensor],
    mpo: List[BlockSparseTensor],
    contract_fn: Callable = contract,
):
    """<psi|H|psi> via a full left-to-right environment sweep."""
    A = left_edge(mps_tensors[0], mpo[0])
    for T, W in zip(mps_tensors, mpo):
        A = extend_left(A, T, W, contract_fn)
    acc = 0.0
    for b in A.blocks.values():
        acc = acc + jnp.sum(b)
    return jnp.real(acc)
