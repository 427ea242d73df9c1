"""Block-sparse tensors and the *list* contraction algorithm (paper Alg. 2).

A ``BlockSparseTensor`` stores one dense array per nonzero quantum-number
block, exactly as the paper's list format stores "a set of memory distributed
tensor blocks T_{q^(l)}".  On TPU, each block array is a ``jax.Array`` that may
itself be sharded over the full device mesh by the caller — this mirrors the
paper's key decision to distribute *every block over all processors* instead
of assigning blocks to nodes (which load-imbalances because the largest block
scales ~ m, their Fig. 2a).

The class is registered as a pytree so whole DMRG sweep steps jit cleanly;
the block keys / index metadata are static, the block arrays are leaves.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .qn import Charge, IN, Index, OUT, qadd, qscale, qzero

BlockKey = Tuple[int, ...]  # sector position along each mode


class BlockSparseTensor:
    """List-format block-sparse tensor (paper Sec. IV-A, "list algorithm")."""

    def __init__(
        self,
        indices: Sequence[Index],
        blocks: Dict[BlockKey, jax.Array],
        charge: Charge | None = None,
    ):
        self.indices = tuple(indices)
        self.charge = charge if charge is not None else qzero(self.indices[0].nq)
        self.blocks = dict(blocks)

    # ------------------------------------------------------------------ meta
    @property
    def ndim(self) -> int:
        return len(self.indices)

    @property
    def dtype(self):
        for b in self.blocks.values():
            return b.dtype
        return jnp.float64

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(ix.dim for ix in self.indices)

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    @property
    def nnz(self) -> int:
        return sum(int(np.prod(b.shape)) for b in self.blocks.values())

    def block_shape(self, key: BlockKey) -> Tuple[int, ...]:
        return tuple(ix.sector_dim(s) for ix, s in zip(self.indices, key))

    def key_charge(self, key: BlockKey) -> Charge:
        q = qzero(self.indices[0].nq)
        for ix, s in zip(self.indices, key):
            q = qadd(q, qscale(ix.charge(s), ix.flow))
        return q

    def is_valid_key(self, key: BlockKey) -> bool:
        return self.key_charge(key) == self.charge

    def valid_keys(self) -> List[BlockKey]:
        """All sector combinations consistent with the tensor charge."""
        out: List[BlockKey] = []

        def rec(i: int, q: Charge, key: BlockKey):
            if i == len(self.indices):
                if q == self.charge:
                    out.append(key)
                return
            ix = self.indices[i]
            for s in range(ix.num_sectors):
                rec(i + 1, qadd(q, qscale(ix.charge(s), ix.flow)), key + (s,))

        rec(0, qzero(self.indices[0].nq), ())
        return out

    def check(self):
        for k, b in self.blocks.items():
            assert self.is_valid_key(k), f"block {k} violates charge conservation"
            assert tuple(b.shape) == self.block_shape(k), (
                f"block {k} shape {b.shape} != {self.block_shape(k)}"
            )

    # ------------------------------------------------------------- construct
    @staticmethod
    def zeros(indices: Sequence[Index], charge: Charge | None = None, dtype=jnp.float64):
        t = BlockSparseTensor(indices, {}, charge)
        t.blocks = {k: jnp.zeros(t.block_shape(k), dtype) for k in t.valid_keys()}
        return t

    @staticmethod
    def random(
        indices: Sequence[Index],
        charge: Charge | None = None,
        key: jax.Array | None = None,
        dtype=jnp.float64,
    ):
        t = BlockSparseTensor(indices, {}, charge)
        key = key if key is not None else jax.random.PRNGKey(0)
        blocks = {}
        for k in t.valid_keys():
            key, sub = jax.random.split(key)
            blocks[k] = jax.random.normal(sub, t.block_shape(k), dtype)
        t.blocks = blocks
        return t

    # --------------------------------------------------------------- algebra
    def scale(self, a) -> "BlockSparseTensor":
        return BlockSparseTensor(self.indices, {k: a * b for k, b in self.blocks.items()}, self.charge)

    def __mul__(self, a):
        return self.scale(a)

    __rmul__ = __mul__

    def __add__(self, other: "BlockSparseTensor") -> "BlockSparseTensor":
        assert self.indices == other.indices and self.charge == other.charge
        blocks = dict(self.blocks)
        for k, b in other.blocks.items():
            blocks[k] = blocks[k] + b if k in blocks else b
        return BlockSparseTensor(self.indices, blocks, self.charge)

    def __sub__(self, other: "BlockSparseTensor") -> "BlockSparseTensor":
        return self + other.scale(-1.0)

    def conj(self) -> "BlockSparseTensor":
        """Complex conjugate + flip all flows (bra tensor)."""
        return BlockSparseTensor(
            [ix.dual() for ix in self.indices],
            {k: jnp.conj(b) for k, b in self.blocks.items()},
            qscale(self.charge, -1),
        )

    def transpose(self, perm: Sequence[int]) -> "BlockSparseTensor":
        perm = tuple(perm)
        return BlockSparseTensor(
            [self.indices[p] for p in perm],
            {tuple(k[p] for p in perm): jnp.transpose(b, perm) for k, b in self.blocks.items()},
            self.charge,
        )

    def norm_sq(self):
        acc = 0.0
        for b in self.blocks.values():
            acc = acc + jnp.sum(jnp.abs(b) ** 2)
        return jnp.real(acc)

    def norm(self):
        return jnp.sqrt(self.norm_sq())

    def inner(self, other: "BlockSparseTensor"):
        """<self|other> = sum over shared blocks of conj(self).other."""
        acc = 0.0
        for k, b in self.blocks.items():
            if k in other.blocks:
                acc = acc + jnp.sum(jnp.conj(b) * other.blocks[k])
        return acc

    # ------------------------------------------------------------- densify
    def to_dense(self) -> jax.Array:
        """Embed blocks at sector offsets (the sparse-dense layout)."""
        out = jnp.zeros(self.shape, self.dtype)
        offs = [ix.offsets() for ix in self.indices]
        for k, b in self.blocks.items():
            sl = tuple(
                slice(offs[i][s], offs[i][s] + self.indices[i].sector_dim(s))
                for i, s in enumerate(k)
            )
            out = out.at[sl].set(b)
        return out

    @staticmethod
    def from_dense(
        dense: jax.Array, indices: Sequence[Index], charge: Charge | None = None
    ) -> "BlockSparseTensor":
        t = BlockSparseTensor(indices, {}, charge)
        offs = [ix.offsets() for ix in indices]
        blocks = {}
        for k in t.valid_keys():
            sl = tuple(
                slice(offs[i][s], offs[i][s] + indices[i].sector_dim(s))
                for i, s in enumerate(k)
            )
            blocks[k] = dense[sl]
        t.blocks = blocks
        return t


# --------------------------------------------------------------------- pytree
def _bst_flatten(t: BlockSparseTensor):
    keys = tuple(sorted(t.blocks.keys()))
    children = tuple(t.blocks[k] for k in keys)
    aux = (t.indices, t.charge, keys)
    return children, aux


def _bst_unflatten(aux, children) -> BlockSparseTensor:
    indices, charge, keys = aux
    return BlockSparseTensor(indices, dict(zip(keys, children)), charge)


jax.tree_util.register_pytree_node(BlockSparseTensor, _bst_flatten, _bst_unflatten)


def flip_flow(t: BlockSparseTensor, axis: int) -> BlockSparseTensor:
    """Replace Index(q, flow) with Index(-q, -flow) on one mode (no-op on data).

    flow*q is invariant, so charge conservation is untouched; used to
    re-orient bond arrows after ``svd_split`` (e.g. MPO compression keeps
    l: IN / r: OUT).  Both sides of a bond must be flipped together.
    """
    ix = t.indices[axis]
    perm = sorted(range(ix.num_sectors), key=lambda s: tuple(-c for c in ix.charge(s)))
    new_ix = Index(
        tuple((tuple(-c for c in ix.charge(s)), ix.sector_dim(s)) for s in perm),
        -ix.flow,
        ix.name,
    )
    inv = {old: new for new, old in enumerate(perm)}
    blocks = {
        k[:axis] + (inv[k[axis]],) + k[axis + 1 :]: b for k, b in t.blocks.items()
    }
    indices = list(t.indices)
    indices[axis] = new_ix
    return BlockSparseTensor(indices, blocks, t.charge)


# ------------------------------------------------------------------ contract
def contract(
    a: BlockSparseTensor,
    b: BlockSparseTensor,
    axes: Tuple[Sequence[int], Sequence[int]],
) -> BlockSparseTensor:
    """Paper Algorithm 2: list-format block-sparse contraction.

    Enumerates all block pairs whose charges match along the contracted modes
    and tensordot-s them, accumulating into output blocks keyed by the
    remaining sector labels.  Under ``jit`` the Python loop unrolls into one
    XLA graph, so independent block GEMMs overlap (the TPU analogue of the
    paper's O(N_b) BSP supersteps collapsing into one program).

    This is the reference algorithm every other backend (dense, csr,
    batched, and the plan-executed engine paths) is tested against: all of
    them must reproduce its output blocks to <=1e-12 on random charged
    tensors and DMRG energies to <1e-10.
    """
    ax_a, ax_b = tuple(axes[0]), tuple(axes[1])
    assert len(ax_a) == len(ax_b)
    for ia, ib in zip(ax_a, ax_b):
        assert a.indices[ia].can_contract(b.indices[ib]), (
            f"mode {ia} of A cannot contract mode {ib} of B: "
            f"{a.indices[ia]} vs {b.indices[ib]}"
        )
    keep_a = [i for i in range(a.ndim) if i not in ax_a]
    keep_b = [i for i in range(b.ndim) if i not in ax_b]
    out_indices = [a.indices[i] for i in keep_a] + [b.indices[i] for i in keep_b]
    out_charge = qadd(a.charge, b.charge)

    # index B blocks by their contracted-sector signature (hash join, not the
    # O(N_a * N_b) double loop in the paper's pseudocode)
    b_by_sig: Dict[Tuple[int, ...], List[BlockKey]] = {}
    for kb in b.blocks:
        sig = tuple(kb[i] for i in ax_b)
        b_by_sig.setdefault(sig, []).append(kb)

    out_blocks: Dict[BlockKey, jax.Array] = {}
    for ka, ablock in a.blocks.items():
        sig = tuple(ka[i] for i in ax_a)
        for kb in b_by_sig.get(sig, ()):  # matching quantum-number labels
            kc = tuple(ka[i] for i in keep_a) + tuple(kb[i] for i in keep_b)
            piece = jnp.tensordot(ablock, b.blocks[kb], axes=(ax_a, ax_b))
            if kc in out_blocks:
                out_blocks[kc] = out_blocks[kc] + piece
            else:
                out_blocks[kc] = piece

    out = BlockSparseTensor(out_indices, out_blocks, out_charge)
    return out


def contract_dense(
    a: BlockSparseTensor,
    b: BlockSparseTensor,
    axes: Tuple[Sequence[int], Sequence[int]],
) -> BlockSparseTensor:
    """Paper's *sparse-dense* algorithm: embed into dense, single tensordot.

    Storage cost rises to prod(dims) per tensor (paper: "each MPS tensor now
    has storage cost d m^2, the same as without quantum numbers") but the
    contraction is one dense GEMM that runs at MXU speed.  The embedding is a
    contraction homomorphism — mismatched blocks land on zeros — so the result
    equals the list algorithm exactly; we re-extract only charge-legal blocks.
    """
    ax_a, ax_b = tuple(axes[0]), tuple(axes[1])
    keep_a = [i for i in range(a.ndim) if i not in ax_a]
    keep_b = [i for i in range(b.ndim) if i not in ax_b]
    out_indices = [a.indices[i] for i in keep_a] + [b.indices[i] for i in keep_b]
    dense = jnp.tensordot(a.to_dense(), b.to_dense(), axes=(ax_a, ax_b))
    return BlockSparseTensor.from_dense(dense, out_indices, qadd(a.charge, b.charge))


# ------------------------------------------------------------------ SVD split
def svd_split(
    theta: BlockSparseTensor,
    n_row_modes: int,
    max_bond: int,
    cutoff: float = 1e-12,
    absorb: str = "right",
):
    """Blockwise truncated SVD across a bond (paper Fig. 1e, Sec. IV-A).

    Planned front door: delegates to the shape-bucketed batched engine in
    ``dist/decomp.py`` (one gather-assembled batched SVD per padded
    sector-shape bucket, one host sync per call).  The seed per-sector
    loop remains available as ``svd_split_unplanned``; the planned path
    matches it to <1e-10 up to the per-singular-vector sign gauge (products
    U·V, singular values, retained sectors and ``trunc_err`` agree
    unconditionally), except on *exact* singular-value ties at the truncation
    threshold, where the planned path breaks ties deterministically by
    (sector charge, position) and keeps the total bond ≤ ``max_bond`` while
    the seed path keeps every tied value (and can exceed ``max_bond``).

    Semantics (both paths): ``theta`` is matricized with the first
    ``n_row_modes`` modes as rows, blocks are grouped by the fused row
    charge, each charge sector is SVD'd, and truncation is *global* across
    sectors — keep at most ``max_bond`` values, dropping those ``<= cutoff *
    s_max`` (the comparison is strict ``>`` for keeping); at least one value
    is always kept.  ``absorb`` multiplies the retained singular values into
    U ("left") or V ("right"); any other string leaves both isometric
    (singular values absorbed into neither).

    Returns ``(U_tensor, V_tensor, svals_by_sector, trunc_err)`` with the
    new bond index carrying one sector per retained charge and ``trunc_err``
    the sum of squared discarded singular values (= the squared Frobenius
    reconstruction error of the absorbed product U·V).  Must be called with
    concrete (non-tracer) blocks: truncation syncs singular values to host.
    """
    from ..dist.decomp import svd_split_planned  # lazy: tensor -> dist only here

    return svd_split_planned(
        theta, n_row_modes, max_bond, cutoff=cutoff, absorb=absorb
    )


def svd_split_unplanned(
    theta: BlockSparseTensor,
    n_row_modes: int,
    max_bond: int,
    cutoff: float = 1e-12,
    absorb: str = "right",
):
    """Seed blockwise truncated SVD: the per-sector loop, kept for A/B.

    Matricizes ``theta`` with the first ``n_row_modes`` modes as rows, groups
    blocks by the fused charge across the cut, SVDs each charge sector
    independently (one dense assembly + one ``jnp.linalg.svd`` + one host
    sync per sector), then truncates *globally* by singular value, exactly
    like the paper's list-format SVD ("grouped via similar quantum numbers
    along a row or column index, and decomposed").

    Tie-break semantics this implementation actually has: the global
    threshold is the ``n_keep``-th largest value with ``n_keep =
    min(max_bond, #values > cutoff * s_max)``, and each sector keeps every
    value ``>= thresh`` (capped at ``n_keep`` per sector) — so *exact* ties
    at the threshold across sectors are all kept and the total retained bond
    can exceed ``max_bond``; ``trunc_err`` is always the tail sum beyond the
    top ``n_keep`` regardless.  ``absorb`` scales U ("left") or V ("right");
    any other string scales neither.  See ``svd_split`` for the planned
    batched path and its equality guarantee.

    Returns (U_tensor, V_tensor, svals_by_sector, trunc_err) with the
    singular values absorbed into U ("left") or V ("right") following the
    sweep direction, and the new bond index carrying one sector per retained
    charge.
    """
    if not theta.blocks:
        raise ValueError("svd_split of a tensor with no blocks")
    row_ix = theta.indices[:n_row_modes]
    col_ix = theta.indices[n_row_modes:]

    # group blocks by fused row charge q (flow OUT along the new bond)
    groups: Dict[Charge, List[BlockKey]] = {}
    for k in theta.blocks:
        q = qzero(theta.indices[0].nq)
        for ix, s in zip(row_ix, k[:n_row_modes]):
            q = qadd(q, qscale(ix.charge(s), ix.flow))
        groups.setdefault(q, []).append(k)

    # per charge sector: assemble dense matrix [sum(row dims), sum(col dims)]
    sector_data = []  # (q, U, S, Vh, row_layout, col_layout)
    for q, keys in sorted(groups.items()):
        row_keys = sorted({k[:n_row_modes] for k in keys})
        col_keys = sorted({k[n_row_modes:] for k in keys})
        rdim = {rk: int(np.prod([ix.sector_dim(s) for ix, s in zip(row_ix, rk)] or [1])) for rk in row_keys}
        cdim = {ck: int(np.prod([ix.sector_dim(s) for ix, s in zip(col_ix, ck)] or [1])) for ck in col_keys}
        roff, acc = {}, 0
        for rk in row_keys:
            roff[rk] = acc
            acc += rdim[rk]
        R = acc
        coff, acc = {}, 0
        for ck in col_keys:
            coff[ck] = acc
            acc += cdim[ck]
        C = acc
        mat = jnp.zeros((R, C), theta.dtype)
        for k in keys:
            rk, ck = k[:n_row_modes], k[n_row_modes:]
            blk = theta.blocks[k].reshape(rdim[rk], cdim[ck])
            mat = mat.at[roff[rk] : roff[rk] + rdim[rk], coff[ck] : coff[ck] + cdim[ck]].set(blk)
        U, S, Vh = jnp.linalg.svd(mat, full_matrices=False)
        sector_data.append((q, U, S, Vh, (row_keys, rdim, roff), (col_keys, cdim, coff)))

    # global truncation across sectors (concretizes: SVD sizes are data-dep)
    all_s = np.concatenate([np.asarray(S) for _, _, S, _, _, _ in sector_data])
    order = np.argsort(all_s)[::-1]
    smax = float(all_s[order[0]]) if len(order) else 1.0
    keep_vals = all_s[order]
    n_keep = int(min(max_bond, np.sum(keep_vals > cutoff * smax)))
    n_keep = max(n_keep, 1)
    thresh = keep_vals[n_keep - 1]
    trunc_err = float(np.sum(keep_vals[n_keep:] ** 2))

    new_sectors, u_blocks, v_blocks, svals = [], {}, {}, {}
    for q, U, S, Vh, (row_keys, rdim, roff), (col_keys, cdim, coff) in sector_data:
        m_q = int(np.sum(np.asarray(S) >= thresh))
        m_q = min(m_q, n_keep)  # guard exact ties
        if m_q == 0:
            continue
        Uq, Sq, Vq = U[:, :m_q], S[:m_q], Vh[:m_q, :]
        if absorb == "right":
            Vq = Sq[:, None] * Vq
        elif absorb == "left":
            Uq = Uq * Sq[None, :]
        svals[q] = Sq
        new_sectors.append((q, m_q))
        for rk in row_keys:
            shp = tuple(ix.sector_dim(s) for ix, s in zip(row_ix, rk)) + (m_q,)
            u_blocks[(q, rk)] = Uq[roff[rk] : roff[rk] + rdim[rk], :].reshape(shp)
        for ck in col_keys:
            shp = (m_q,) + tuple(ix.sector_dim(s) for ix, s in zip(col_ix, ck))
            v_blocks[(q, ck)] = Vq[:, coff[ck] : coff[ck] + cdim[ck]].reshape(shp)

    # New bond carries the fused row charge q: on U it flows IN
    # (row-charge q + IN*q = 0 = U.charge), on V it flows OUT
    # (OUT*q + col-charge (Q - q) = Q = theta.charge); IN/OUT are contractible.
    bond_u = Index(tuple(new_sectors), IN, "bond")
    bond_v = Index(tuple(new_sectors), OUT, "bond")
    sector_index = {q: i for i, (q, _) in enumerate(new_sectors)}

    U_t = BlockSparseTensor(
        list(row_ix) + [bond_u],
        {rk + (sector_index[q],): b for (q, rk), b in u_blocks.items()},
        qzero(theta.indices[0].nq),
    )
    V_t = BlockSparseTensor(
        [bond_v] + list(col_ix),
        {(sector_index[q],) + ck: b for (q, ck), b in v_blocks.items()},
        theta.charge,
    )
    return U_t, V_t, svals, trunc_err
