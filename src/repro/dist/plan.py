"""Contraction and decomposition plans: the static, cacheable half.

Everything the list / dense / csr algorithms derive from quantum numbers —
the (lhs, rhs) -> out block-pair table, output indices and charge, output
block shapes, matricized (row, col) dims and padded batch shapes — is a pure
function of ``(a.indices, a.charge, a block keys, b.indices, b.charge,
b block keys, axes)``.  The seed code re-derived all of it in Python on every
``contract()`` call, i.e. 4 contractions x davidson_iters x 2N sites per
sweep.  A ``ContractionPlan`` computes it once and a ``PlanCache`` keyed by
that structural signature reuses it for the whole sweep (the analogue of
CTF's one-time output-sparsity precomputation, paper Sec. IV-B).

The same split applies to the blockwise truncated SVD (paper Fig. 1e): a
``DecompositionPlan`` precomputes sector grouping, row/column layouts and
the gather tables that assemble each padded sector-matrix stack, cached in
a ``DecompPlanCache`` by the analogous ``decomp_signature``; execution lives
in ``dist/decomp.py``.

And to the environment stage (paper Fig. 1d, Sec. II-C): an
``EnvironmentPlan`` chains the three per-site contraction plans of
``extend_left`` / ``extend_right`` into one resolved pipeline — every
intermediate block structure precomputed — cached in an ``EnvPlanCache`` by
the composite ``env_signature`` of the (env, site, MPO) triple; execution
lives in ``dist/envcore.py``.

Plans hold only Python/numpy metadata — no jax arrays — so building them
never touches a device and they are safe to share across jit traces (block
keys and Index metadata are concrete even under tracing).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..tensor.blocksparse import BlockKey, BlockSparseTensor
from ..tensor.qn import Charge, Index, qadd, qscale, qzero

PlanSignature = Tuple

Axes = Tuple[Tuple[int, ...], Tuple[int, ...]]

# Process-wide persistent plan store (dist/persist.py sets this via
# ``activate_store``).  Lives here — not in persist.py — so the caches can
# consult it without importing persist (which imports this module).  A cache
# instance's own ``store`` attribute, when set, takes precedence.
_ACTIVE_STORE = None


def plan_signature(
    a: BlockSparseTensor, b: BlockSparseTensor, axes: Axes
) -> PlanSignature:
    """Structural signature of a contraction: indices, charges, keys, axes.

    Two contractions with equal signatures have identical symbolic structure
    (same pair table, same output blocks), whatever their numeric contents.
    Index is a frozen dataclass (name excluded from equality) and charges /
    keys are int tuples, so the signature is hashable.
    """
    ax_a, ax_b = tuple(axes[0]), tuple(axes[1])
    return (
        a.indices,
        a.charge,
        tuple(sorted(a.blocks)),
        b.indices,
        b.charge,
        tuple(sorted(b.blocks)),
        ax_a,
        ax_b,
    )


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def bucket_dim(d: int) -> int:
    """Round a dimension up to the next power of two (shape-bucket size)."""
    p = 1
    while p < d:
        p *= 2
    return p


def svd_flop_estimate(rp: int, cp: int) -> float:
    """~LAPACK gesdd flop estimate for one [rp, cp] economy SVD.

    Single source of truth for the decomposition cost model: used for
    ``DecompositionPlan.svd_flops`` and by the engine's auto rsvd-vs-svd
    choice and ``svd_flops`` stats counter (dist/decomp.py).
    """
    kp = min(rp, cp)
    return 8.0 * rp * cp * kp + 9.0 * kp**3


@dataclasses.dataclass
class CsrLayout:
    """Packed-batch layout for the block-CSR backend (see block_csr.py)."""

    a_keys: Tuple[BlockKey, ...]          # participating lhs keys, pack order
    b_keys: Tuple[BlockKey, ...]          # participating rhs keys, pack order
    bm: int                               # padded matricized row dim
    bk: int                               # padded contracted dim
    bn: int                               # padded matricized col dim
    li: np.ndarray                        # [P] lhs pack slot per pair
    ri: np.ndarray                        # [P] rhs pack slot per pair
    oi: np.ndarray                        # [P] output slot per pair (sorted)
    out_keys: Tuple[BlockKey, ...]        # output key per output slot
    out_rc: Tuple[Tuple[int, int], ...]   # unpadded (rows, cols) per out slot
    # (li, ri, oi) device arrays memoized PER MESH: plans live in the global
    # cache and outlive any one shard policy, so arrays committed under one
    # mesh must not be replayed under another (keyed None = no policy)
    dev_idx: Dict = dataclasses.field(default_factory=dict)

    # device arrays are process-local handles: never persisted, rebuilt by
    # ``batch.memo_dev_idx`` on first use in the loading process
    def __getstate__(self):
        state = dict(self.__dict__)
        state["dev_idx"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.dev_idx = {}


@dataclasses.dataclass
class ShapeBucket:
    """All block pairs of a contraction sharing one matricized (M, K, N).

    Every lhs block in the bucket matricizes to exactly (m, k) and every rhs
    block to (k, n) — no padding — so the bucket executes as ONE stacked
    batched GEMM with a segment-sum scatter over its output slots (the
    fused same-shape batches of Menczer et al., arXiv:2407.07411).
    """

    m: int
    k: int
    n: int
    a_keys: Tuple[BlockKey, ...]          # unique participating lhs keys
    b_keys: Tuple[BlockKey, ...]          # unique participating rhs keys
    li: np.ndarray                        # [P] lhs slot per pair
    ri: np.ndarray                        # [P] rhs slot per pair
    oi: np.ndarray                        # [P] output slot per pair, ascending
    out_keys: Tuple[BlockKey, ...]        # bucket-local output key per slot
    li_identity: bool = False             # li == arange(P): gather is a no-op
    ri_identity: bool = False


@dataclasses.dataclass
class BatchedLayout:
    """Shape-group table: the pair list bucketed by matricized (M, K, N)."""

    buckets: Tuple[ShapeBucket, ...]
    num_unique: int                       # sum over buckets of |a_keys|+|b_keys|
    num_out_slots: int                    # sum over buckets of |out_keys|
    dev_idx: Dict = dataclasses.field(default_factory=dict)  # per-mesh, as CsrLayout

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    # per-mesh device handles: dropped on pickle, exactly like CsrLayout
    def __getstate__(self):
        state = dict(self.__dict__)
        state["dev_idx"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.dev_idx = {}


@dataclasses.dataclass
class ContractionPlan:
    """Precomputed symbolic structure of one block-sparse contraction."""

    signature: PlanSignature
    ax_a: Tuple[int, ...]
    ax_b: Tuple[int, ...]
    keep_a: Tuple[int, ...]
    keep_b: Tuple[int, ...]
    out_indices: Tuple[Index, ...]
    out_charge: Charge
    # (ka, kb, kc) per multiplied block pair, recorded in the block-dict
    # insertion order of the tensors the plan was built from — the same order
    # seed `contract` iterates.  On a cache hit from a structurally-equal
    # tensor with a *different* insertion order, the multiset of pairs is
    # identical but the accumulation order is the plan builder's, so results
    # may differ from seed in the last ulp (well inside the 1e-10 contract).
    pairs: Tuple[Tuple[BlockKey, BlockKey, BlockKey], ...]
    out_keys: Tuple[BlockKey, ...]        # unique output keys, first-seen order
    # cost model inputs
    flops_list: float                     # sum over pairs of 2*M*K*N
    flops_dense: float                    # one dense tensordot over full dims
    num_in_blocks: int = 0                # len(a.blocks) + len(b.blocks)
    _csr: Optional[CsrLayout] = None
    _batched: Optional[BatchedLayout] = None
    _dense_out_slices: Optional[Tuple[Tuple[BlockKey, Tuple[slice, ...]], ...]] = None

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(
        a: BlockSparseTensor, b: BlockSparseTensor, axes: Axes
    ) -> "ContractionPlan":
        ax_a, ax_b = tuple(axes[0]), tuple(axes[1])
        assert len(ax_a) == len(ax_b)
        for ia, ib in zip(ax_a, ax_b):
            assert a.indices[ia].can_contract(b.indices[ib]), (
                f"mode {ia} of A cannot contract mode {ib} of B: "
                f"{a.indices[ia]} vs {b.indices[ib]}"
            )
        keep_a = tuple(i for i in range(a.ndim) if i not in ax_a)
        keep_b = tuple(i for i in range(b.ndim) if i not in ax_b)
        out_indices = tuple(a.indices[i] for i in keep_a) + tuple(
            b.indices[i] for i in keep_b
        )
        out_charge = qadd(a.charge, b.charge)

        b_by_sig: Dict[Tuple[int, ...], List[BlockKey]] = {}
        for kb in b.blocks:
            b_by_sig.setdefault(tuple(kb[i] for i in ax_b), []).append(kb)

        pairs: List[Tuple[BlockKey, BlockKey, BlockKey]] = []
        out_keys: List[BlockKey] = []
        seen: Dict[BlockKey, int] = {}
        flops_list = 0.0
        for ka in a.blocks:
            sig = tuple(ka[i] for i in ax_a)
            for kb in b_by_sig.get(sig, ()):
                kc = tuple(ka[i] for i in keep_a) + tuple(kb[i] for i in keep_b)
                if kc not in seen:
                    seen[kc] = len(out_keys)
                    out_keys.append(kc)
                pairs.append((ka, kb, kc))
                m = _prod(a.indices[i].sector_dim(ka[i]) for i in keep_a)
                k = _prod(a.indices[i].sector_dim(ka[i]) for i in ax_a)
                n = _prod(b.indices[i].sector_dim(kb[i]) for i in keep_b)
                flops_list += 2.0 * m * k * n

        dense_m = _prod(a.indices[i].dim for i in keep_a)
        dense_k = _prod(a.indices[i].dim for i in ax_a)
        dense_n = _prod(b.indices[i].dim for i in keep_b)
        flops_dense = 2.0 * dense_m * dense_k * dense_n

        plan = ContractionPlan(
            signature=plan_signature(a, b, axes),
            ax_a=ax_a,
            ax_b=ax_b,
            keep_a=keep_a,
            keep_b=keep_b,
            out_indices=out_indices,
            out_charge=out_charge,
            pairs=tuple(pairs),
            out_keys=tuple(out_keys),
            flops_list=flops_list,
            flops_dense=flops_dense,
            num_in_blocks=len(a.blocks) + len(b.blocks),
        )
        return plan

    @staticmethod
    def _mshape(
        indices: Tuple[Index, ...], key: BlockKey, keep, ax
    ) -> Tuple[int, int]:
        rows = _prod([indices[i].sector_dim(key[i]) for i in keep] or [1])
        cols = _prod([indices[i].sector_dim(key[i]) for i in ax] or [1])
        return rows, cols

    def _build_csr(self) -> CsrLayout:
        """Padded-batch layout: the csr half of block_csr.py, symbolically.

        Built lazily on first ``csr``/``flops_csr`` access so list/dense runs
        never pay for it; every input comes from the structural signature,
        not live tensors.
        """
        a_indices, _, a_keys_sorted, b_indices, _, b_keys_sorted = self.signature[:6]
        a_pos = {k: i for i, k in enumerate(a_keys_sorted)}
        b_pos = {k: i for i, k in enumerate(b_keys_sorted)}
        out_pos = {k: i for i, k in enumerate(self.out_keys)}
        trip = sorted(
            ((a_pos[ka], b_pos[kb], out_pos[kc]) for ka, kb, kc in self.pairs),
            key=lambda t: t[2],
        )
        part_a = sorted({t[0] for t in trip})
        part_b = sorted({t[1] for t in trip})
        bm = max(
            self._mshape(a_indices, a_keys_sorted[i], self.keep_a, self.ax_a)[0]
            for i in part_a
        )
        bk = max(
            max(
                self._mshape(a_indices, a_keys_sorted[i], self.keep_a, self.ax_a)[1]
                for i in part_a
            ),
            max(
                self._mshape(b_indices, b_keys_sorted[i], self.keep_b, self.ax_b)[1]
                for i in part_b
            ),
        )
        bn = max(
            self._mshape(b_indices, b_keys_sorted[i], self.keep_b, self.ax_b)[0]
            for i in part_b
        )
        a_remap = {i: n for n, i in enumerate(part_a)}
        b_remap = {i: n for n, i in enumerate(part_b)}
        nk = len(self.keep_a)
        out_rc = tuple(
            (
                _prod([self.out_indices[i].sector_dim(kc[i]) for i in range(nk)] or [1]),
                _prod(
                    [
                        self.out_indices[i].sector_dim(kc[i])
                        for i in range(nk, len(self.out_indices))
                    ]
                    or [1]
                ),
            )
            for kc in self.out_keys
        )
        return CsrLayout(
            a_keys=tuple(a_keys_sorted[i] for i in part_a),
            b_keys=tuple(b_keys_sorted[i] for i in part_b),
            bm=bm,
            bk=bk,
            bn=bn,
            li=np.array([a_remap[t[0]] for t in trip], np.int32),
            ri=np.array([b_remap[t[1]] for t in trip], np.int32),
            oi=np.array([t[2] for t in trip], np.int32),
            out_keys=self.out_keys,
            out_rc=out_rc,
        )

    def _build_batched(self) -> BatchedLayout:
        """Bucket the pair list by matricized (M, K, N) shape.

        Unlike the csr layout there is NO padding: pairs only share a bucket
        when their matricized shapes are exactly equal, so each bucket is one
        regular [P, M, K] x [P, K, N] batched GEMM whose products segment-sum
        into the bucket's output slots.  Different buckets may feed the same
        output block (same kept sectors, different contracted sector dims);
        the executor accumulates across buckets in Python — a handful of adds.
        """
        a_indices, _, _, b_indices = self.signature[:4]
        groups: Dict[Tuple[int, int, int], List[Tuple[BlockKey, BlockKey, BlockKey]]] = {}
        for ka, kb, kc in self.pairs:
            m, k = self._mshape(a_indices, ka, self.keep_a, self.ax_a)
            n = self._mshape(b_indices, kb, self.keep_b, self.ax_b)[0]
            groups.setdefault((m, k, n), []).append((ka, kb, kc))

        buckets: List[ShapeBucket] = []
        num_unique = 0
        num_out_slots = 0
        for (m, k, n), prs in sorted(groups.items()):
            prs = sorted(prs, key=lambda t: t[2])  # -> oi ascending
            a_keys: List[BlockKey] = []
            b_keys: List[BlockKey] = []
            out_keys: List[BlockKey] = []
            a_pos: Dict[BlockKey, int] = {}
            b_pos: Dict[BlockKey, int] = {}
            o_pos: Dict[BlockKey, int] = {}
            li, ri, oi = [], [], []
            for ka, kb, kc in prs:
                if ka not in a_pos:
                    a_pos[ka] = len(a_keys)
                    a_keys.append(ka)
                if kb not in b_pos:
                    b_pos[kb] = len(b_keys)
                    b_keys.append(kb)
                if kc not in o_pos:
                    o_pos[kc] = len(out_keys)
                    out_keys.append(kc)
                li.append(a_pos[ka])
                ri.append(b_pos[kb])
                oi.append(o_pos[kc])
            li = np.array(li, np.int32)
            ri = np.array(ri, np.int32)
            p = len(prs)
            buckets.append(
                ShapeBucket(
                    m=m,
                    k=k,
                    n=n,
                    a_keys=tuple(a_keys),
                    b_keys=tuple(b_keys),
                    li=li,
                    ri=ri,
                    oi=np.array(oi, np.int32),
                    out_keys=tuple(out_keys),
                    li_identity=len(a_keys) == p and bool((li == np.arange(p)).all()),
                    ri_identity=len(b_keys) == p and bool((ri == np.arange(p)).all()),
                )
            )
            num_unique += len(a_keys) + len(b_keys)
            num_out_slots += len(out_keys)
        return BatchedLayout(
            buckets=tuple(buckets),
            num_unique=num_unique,
            num_out_slots=num_out_slots,
        )

    @property
    def batched(self) -> BatchedLayout:
        if self._batched is None:
            self._batched = self._build_batched()
        return self._batched

    # ------------------------------------------------------- lazy dense layout
    def dense_out_slices(self) -> Tuple[Tuple[BlockKey, Tuple[slice, ...]], ...]:
        """All charge-legal output blocks and their dense-embedding slices.

        Matches seed ``BlockSparseTensor.from_dense`` (which extracts every
        valid key, including blocks that happen to be zero).  The valid-key
        enumeration is the expensive recursive part, so it is computed lazily
        on first dense execution and memoized on the plan.
        """
        if self._dense_out_slices is None:
            probe = BlockSparseTensor(self.out_indices, {}, self.out_charge)
            offs = [ix.offsets() for ix in self.out_indices]
            rows = []
            for k in probe.valid_keys():
                sl = tuple(
                    slice(offs[i][s], offs[i][s] + self.out_indices[i].sector_dim(s))
                    for i, s in enumerate(k)
                )
                rows.append((k, sl))
            self._dense_out_slices = tuple(rows)
        return self._dense_out_slices

    @property
    def csr(self) -> CsrLayout:
        assert self.pairs, "csr layout undefined for empty pair table"
        if self._csr is None:
            self._csr = self._build_csr()
        return self._csr

    @property
    def flops_csr(self) -> float:
        """Padded-batch csr flops: pairs * 2*BM*BK*BN (triggers lazy layout)."""
        if not self.pairs:
            return 0.0
        L = self.csr
        return 2.0 * len(self.pairs) * L.bm * L.bk * L.bn

    @property
    def num_pairs(self) -> int:
        return len(self.pairs)

    def out_block_shape(self, kc: BlockKey) -> Tuple[int, ...]:
        return tuple(ix.sector_dim(s) for ix, s in zip(self.out_indices, kc))

    def materialize(self, pair_overhead: float = 16384.0) -> "ContractionPlan":
        """Force the lazy layouts a run would build anyway, for persistence.

        Called by ``dist.persist.PlanStore.save_plan`` so the priming
        process pays the layout derivation once and every loading process
        gets it for free.  The batched layout is always worth carrying; the
        dense slice table (a recursive valid-key enumeration) only when the
        engine cost model could actually route this plan to the dense
        backend — mirrored here with the same default dispatch overhead as
        ``engine.PAIR_OVERHEAD_FLOPS``.
        """
        if self.pairs:
            _ = self.batched
        if self.flops_dense <= self.flops_list + pair_overhead * self.num_pairs:
            _ = self.dense_out_slices()
        return self


# ------------------------------------------------------------ decomposition
def decomp_signature(theta: BlockSparseTensor, n_row_modes: int) -> PlanSignature:
    """Structural signature of a blockwise SVD split.

    Everything a ``DecompositionPlan`` precomputes — sector grouping,
    row/column layouts, gather tables, padded bucket shapes — is a pure
    function of ``(theta.indices, theta.charge, theta block keys,
    n_row_modes)``, exactly like ``plan_signature`` for contractions.
    """
    return (
        theta.indices,
        theta.charge,
        tuple(sorted(theta.blocks)),
        n_row_modes,
    )


@dataclasses.dataclass
class SectorSplit:
    """Row/column layout of one fused-charge sector of the matricized theta.

    The sector matrix is ``[R, C]``: rows are the concatenation (in
    ``row_keys`` order) of the matricized row-mode blocks, columns likewise
    for the column modes — the same layout the seed ``svd_split`` builds with
    one ``.at[].set()`` per block.
    """

    q: Charge
    row_keys: Tuple[BlockKey, ...]       # sorted row-part keys
    col_keys: Tuple[BlockKey, ...]       # sorted col-part keys
    rdims: Tuple[int, ...]               # matricized row dim per row key
    cdims: Tuple[int, ...]               # matricized col dim per col key
    roffs: Tuple[int, ...]               # row offset per row key
    coffs: Tuple[int, ...]               # col offset per col key
    R: int                               # total (unpadded) rows
    C: int                               # total (unpadded) cols
    bucket: int = -1                     # index into plan.buckets
    slot: int = -1                       # stack position within the bucket

    @property
    def K(self) -> int:
        """True rank bound min(R, C): number of real singular values."""
        return min(self.R, self.C)


@dataclasses.dataclass
class SvdBucket:
    """All sectors sharing one padded matrix shape (Rp, Cp).

    The bucket executes as ONE batched SVD (``decomp.bucket_svd``) over the
    stacked ``[S, Rp, Cp]`` sector matrices, assembled with a single gather from the
    flattened theta blocks (``gather`` indexes into the flat concatenation,
    with the one-past-the-end slot reading the appended zero — structural
    zeros and padding both land there).
    """

    rp: int                              # padded rows (bucket_dim(R))
    cp: int                              # padded cols (bucket_dim(C))
    sectors: Tuple[int, ...]             # indices into plan.sectors, stack order
    gather: np.ndarray                   # [S, rp, cp] int32 into flat_ext
    k_true: np.ndarray                   # [S] int32: min(R, C) per sector

    @property
    def kp(self) -> int:
        """Padded singular-value count min(rp, cp) per stacked sector."""
        return min(self.rp, self.cp)


@dataclasses.dataclass
class DecompositionPlan:
    """Precomputed symbolic structure of one blockwise truncated SVD.

    Holds only Python/numpy metadata (no jax arrays), like
    ``ContractionPlan``; building one never touches a device.  Executed by
    ``dist.decomp.DecompositionEngine``, whose batched path is guaranteed to
    match the seed ``svd_split_unplanned`` to <1e-10 up to the per-singular-
    vector sign gauge (products U·V, singular values and truncation error
    agree unconditionally).
    """

    signature: PlanSignature
    n_row_modes: int
    row_ix: Tuple[Index, ...]
    col_ix: Tuple[Index, ...]
    block_order: Tuple[BlockKey, ...]    # canonical (sorted) flattening order
    block_offsets: Tuple[int, ...]       # flat offset per block, same order
    nnz: int                             # total elements across blocks
    sectors: Tuple[SectorSplit, ...]     # sorted by fused charge (seed order)
    buckets: Tuple[SvdBucket, ...]
    svd_flops: float                     # full-SVD flop estimate over buckets
    # compiled executables keyed by (absorb, per-bucket method, sketch size);
    # stored on the plan (like CsrLayout.dev_idx) so engines sharing the
    # global cache also share compiles
    _exec: Dict = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(theta: BlockSparseTensor, n_row_modes: int) -> "DecompositionPlan":
        if not theta.blocks:
            raise ValueError("svd_split of a tensor with no blocks")
        indices = theta.indices
        row_ix = indices[:n_row_modes]
        col_ix = indices[n_row_modes:]

        block_order = tuple(sorted(theta.blocks))
        offsets: List[int] = []
        acc = 0
        sizes: Dict[BlockKey, int] = {}
        for k in block_order:
            offsets.append(acc)
            sz = _prod(indices[i].sector_dim(s) for i, s in enumerate(k))
            sizes[k] = sz
            acc += sz
        nnz = acc

        # group block keys by fused row charge (flow-weighted), as the seed
        groups: Dict[Charge, List[BlockKey]] = {}
        for k in block_order:
            q = qzero(indices[0].nq)
            for ix, s in zip(row_ix, k[:n_row_modes]):
                q = qadd(q, qscale(ix.charge(s), ix.flow))
            groups.setdefault(q, []).append(k)

        sectors: List[SectorSplit] = []
        sector_keys: List[List[BlockKey]] = []
        for q, keys in sorted(groups.items()):
            row_keys = sorted({k[:n_row_modes] for k in keys})
            col_keys = sorted({k[n_row_modes:] for k in keys})
            rdims = tuple(
                _prod([ix.sector_dim(s) for ix, s in zip(row_ix, rk)] or [1])
                for rk in row_keys
            )
            cdims = tuple(
                _prod([ix.sector_dim(s) for ix, s in zip(col_ix, ck)] or [1])
                for ck in col_keys
            )
            roffs, a = [], 0
            for d in rdims:
                roffs.append(a)
                a += d
            R = a
            coffs, a = [], 0
            for d in cdims:
                coffs.append(a)
                a += d
            C = a
            sectors.append(
                SectorSplit(
                    q=q,
                    row_keys=tuple(row_keys),
                    col_keys=tuple(col_keys),
                    rdims=rdims,
                    cdims=cdims,
                    roffs=tuple(roffs),
                    coffs=tuple(coffs),
                    R=R,
                    C=C,
                )
            )
            sector_keys.append(keys)

        # bucket sectors by padded (Rp, Cp); build one gather table per bucket
        by_shape: Dict[Tuple[int, int], List[int]] = {}
        for si, sec in enumerate(sectors):
            by_shape.setdefault((bucket_dim(sec.R), bucket_dim(sec.C)), []).append(si)

        buckets: List[SvdBucket] = []
        svd_flops = 0.0
        key_offset = {k: o for k, o in zip(block_order, offsets)}
        for (rp, cp), sec_ids in sorted(by_shape.items()):
            gather = np.full((len(sec_ids), rp, cp), nnz, np.int32)
            for slot, si in enumerate(sec_ids):
                sec = sectors[si]
                sec.bucket = len(buckets)
                sec.slot = slot
                rpos = {rk: i for i, rk in enumerate(sec.row_keys)}
                cpos = {ck: i for i, ck in enumerate(sec.col_keys)}
                for k in sector_keys[si]:
                    ri = rpos[k[:n_row_modes]]
                    ci = cpos[k[n_row_modes:]]
                    rd, cd = sec.rdims[ri], sec.cdims[ci]
                    # block elements are already in (row-modes, col-modes)
                    # C order, so the flat block reshapes to [rd, cd] directly
                    idx = key_offset[k] + np.arange(rd * cd, dtype=np.int32)
                    gather[
                        slot,
                        sec.roffs[ri] : sec.roffs[ri] + rd,
                        sec.coffs[ci] : sec.coffs[ci] + cd,
                    ] = idx.reshape(rd, cd)
            svd_flops += len(sec_ids) * svd_flop_estimate(rp, cp)
            buckets.append(
                SvdBucket(
                    rp=rp,
                    cp=cp,
                    sectors=tuple(sec_ids),
                    gather=gather,
                    k_true=np.array(
                        [sectors[si].K for si in sec_ids], np.int32
                    ),
                )
            )

        return DecompositionPlan(
            signature=decomp_signature(theta, n_row_modes),
            n_row_modes=n_row_modes,
            row_ix=tuple(row_ix),
            col_ix=tuple(col_ix),
            block_order=block_order,
            block_offsets=tuple(offsets),
            nnz=nnz,
            sectors=tuple(sectors),
            buckets=tuple(buckets),
            svd_flops=svd_flops,
        )

    @property
    def num_sectors(self) -> int:
        return len(self.sectors)

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    # compiled executables are process-local (they close over an engine and
    # a live XLA client): never persisted, rebuilt lazily by the loading
    # process's DecompositionEngine — where the persistent compilation cache
    # and the export store make the rebuild cheap
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_exec"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._exec = {}


# ------------------------------------------------------------- environments
def env_signature(
    env: BlockSparseTensor,
    site: BlockSparseTensor,
    mpo: BlockSparseTensor,
    side: str,
) -> PlanSignature:
    """Composite structural signature of one environment update.

    The fused left/right env update (``dist/envcore.py``) is a pure function
    of the (env, site, MPO) triple's structure plus the sweep direction —
    the same structural-signature contract as ``plan_signature`` /
    ``decomp_signature``, extended to a three-tensor pipeline.
    """
    return (
        "env",
        side,
        env.indices,
        env.charge,
        tuple(sorted(env.blocks)),
        site.indices,
        site.charge,
        tuple(sorted(site.blocks)),
        mpo.indices,
        mpo.charge,
        tuple(sorted(mpo.blocks)),
    )


def _probe(
    indices: Tuple[Index, ...], charge: Charge, keys
) -> BlockSparseTensor:
    """Structure-only tensor (blocks map to None): plan building and
    signatures read block *keys* only, never block values."""
    return BlockSparseTensor(indices, dict.fromkeys(keys), charge)


def _conj_probe(t: BlockSparseTensor) -> BlockSparseTensor:
    """Structural image of ``t.conj()``: dual indices, negated charge,
    same block keys (conj never moves blocks)."""
    return _probe(
        tuple(ix.dual() for ix in t.indices),
        qscale(t.charge, -1),
        t.blocks,
    )


# the three chained contractions of extend_left / extend_right
# (core/env.py), as static axes per step, plus the final transpose
_ENV_LEFT_AXES = (((2,), (0,)), ((1, 2), (0, 2)), ((0, 1), (0, 2)))
_ENV_LEFT_PERM = (0, 2, 1)
_ENV_RIGHT_AXES = (((2,), (2,)), ((3, 1), (3, 2)), ((1, 3), (2, 1)))
_ENV_RIGHT_PERM = (2, 1, 0)


@dataclasses.dataclass
class EnvironmentPlan:
    """Precomputed symbolic structure of one fused env update.

    Chains the three per-site ``ContractionPlan``s of ``extend_left`` /
    ``extend_right`` (fetched through the shared contraction ``PlanCache``,
    so the eager three-call path and the fused core reuse the same step
    plans) plus the final transpose, resolving every intermediate block
    structure ahead of time.  Holds only Python/numpy metadata; executed by
    ``dist.envcore.EnvironmentEngine`` as ONE jitted core per structure.
    """

    signature: PlanSignature
    side: str                             # "left" | "right"
    steps: Tuple[ContractionPlan, ContractionPlan, ContractionPlan]
    perm: Tuple[int, ...]                 # final transpose of step-3 output
    env_keys: Tuple[BlockKey, ...]        # sorted operand keys, core arg order
    site_keys: Tuple[BlockKey, ...]
    mpo_keys: Tuple[BlockKey, ...]
    out_indices: Tuple[Index, ...]        # post-transpose env structure
    out_charge: Charge
    out_keys: Tuple[BlockKey, ...]        # post-transpose, sorted
    pre_out_keys: Tuple[BlockKey, ...]    # step-3 key per out_keys entry
    flops: float                          # sum over steps of flops_list
    # compiled fused cores keyed by the executing engine's jit flag; stored
    # on the plan (like DecompositionPlan._exec) so engines sharing the
    # cache also share compiles
    _exec: Dict = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(
        env: BlockSparseTensor,
        site: BlockSparseTensor,
        mpo: BlockSparseTensor,
        side: str,
        cache: Optional["PlanCache"] = None,
    ) -> "EnvironmentPlan":
        assert side in ("left", "right")
        cache = cache if cache is not None else global_plan_cache
        bra = _conj_probe(site)
        if side == "left":
            ax1, ax2, ax3 = _ENV_LEFT_AXES
            perm = _ENV_LEFT_PERM
            p1 = cache.get(env, site, ax1)
            t1 = _probe(p1.out_indices, p1.out_charge, p1.out_keys)
            p2 = cache.get(t1, mpo, ax2)
            t2 = _probe(p2.out_indices, p2.out_charge, p2.out_keys)
            p3 = cache.get(bra, t2, ax3)
        else:
            ax1, ax2, ax3 = _ENV_RIGHT_AXES
            perm = _ENV_RIGHT_PERM
            p1 = cache.get(site, env, ax1)
            t1 = _probe(p1.out_indices, p1.out_charge, p1.out_keys)
            p2 = cache.get(t1, mpo, ax2)
            t2 = _probe(p2.out_indices, p2.out_charge, p2.out_keys)
            p3 = cache.get(t2, bra, ax3)
        post_to_pre = {
            tuple(k[p] for p in perm): k for k in p3.out_keys
        }
        out_keys = tuple(sorted(post_to_pre))
        return EnvironmentPlan(
            signature=env_signature(env, site, mpo, side),
            side=side,
            steps=(p1, p2, p3),
            perm=perm,
            env_keys=tuple(sorted(env.blocks)),
            site_keys=tuple(sorted(site.blocks)),
            mpo_keys=tuple(sorted(mpo.blocks)),
            out_indices=tuple(p3.out_indices[p] for p in perm),
            out_charge=p3.out_charge,
            out_keys=out_keys,
            pre_out_keys=tuple(post_to_pre[k] for k in out_keys),
            flops=p1.flops_list + p2.flops_list + p3.flops_list,
        )

    # compiled fused cores are process-local, exactly like DecompositionPlan
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_exec"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._exec = {}


# ------------------------------------------------------------------- caches
class _SignatureLRU:
    """LRU cache of plans keyed by structural signature.

    ``hits``/``misses``/``evictions`` count lookups and capacity evictions;
    ``size`` is live entries.  Shared machinery for contraction and
    decomposition plans — subclasses provide ``_signature`` and ``_build``.

    Thread-safe: the serving subsystem (``repro/serve``) builds problems and
    fetches plans from multiple threads against the module-level global
    caches, so every mutation happens under a per-cache lock.  Builds run
    inside the lock on purpose — a plan object carries its compiled cores
    (``_exec``), so two racing builds of the same signature would silently
    drop one core set.  Lock ordering is acyclic: an ``EnvPlanCache`` build
    acquires the contraction ``PlanCache`` lock (for its three step plans),
    never the reverse.

    Persistence (dist/persist.py): on an in-memory miss the cache consults
    its attached ``PlanStore`` (``self.store``, else the process-wide
    ``_ACTIVE_STORE``) before building, and writes every fresh build back.
    ``builds`` counts actual ``_build`` invocations — with a primed store
    it stays zero, the property the cold-start regression test pins down.
    """

    # persist.PLAN_KINDS entry naming this cache's store subdirectory
    kind = "contraction"

    def __init__(self, maxsize: int = 4096):
        self.maxsize = maxsize
        self._plans: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.builds = 0
        self.store = None  # per-cache PlanStore override (None = _ACTIVE_STORE)

    def _get(self, sig, build):
        with self._lock:
            plan = self._plans.get(sig)
            if plan is not None:
                self.hits += 1
                self._plans.move_to_end(sig)
                return plan
            self.misses += 1
            store = self.store if self.store is not None else _ACTIVE_STORE
            plan = store.load_plan(self.kind, sig) if store is not None else None
            if plan is None:
                self.builds += 1
                plan = build()
                if store is not None:
                    store.save_plan(self.kind, sig, plan)
            self._plans[sig] = plan
            while len(self._plans) > self.maxsize:
                self._plans.popitem(last=False)
                self.evictions += 1
            return plan

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self):
        with self._lock:
            self._plans.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0
            self.builds = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "builds": self.builds,
                "size": len(self._plans),
            }


class PlanCache(_SignatureLRU):
    """LRU cache of ContractionPlans keyed by structural signature."""

    def get(
        self, a: BlockSparseTensor, b: BlockSparseTensor, axes: Axes
    ) -> ContractionPlan:
        sig = plan_signature(a, b, axes)
        return self._get(sig, lambda: ContractionPlan.build(a, b, axes))


class DecompPlanCache(_SignatureLRU):
    """LRU cache of DecompositionPlans keyed by structural signature."""

    kind = "decomp"

    def get(self, theta: BlockSparseTensor, n_row_modes: int) -> DecompositionPlan:
        sig = decomp_signature(theta, n_row_modes)
        return self._get(sig, lambda: DecompositionPlan.build(theta, n_row_modes))


class EnvPlanCache(_SignatureLRU):
    """LRU cache of EnvironmentPlans keyed by composite triple signature.

    ``contraction_cache`` is where the three chained step plans are fetched
    from (the global contraction cache by default, so the eager three-call
    path and the fused core share step plans).
    """

    kind = "env"

    def __init__(
        self, maxsize: int = 4096, contraction_cache: Optional[PlanCache] = None
    ):
        super().__init__(maxsize)
        self.contraction_cache = contraction_cache

    def get(
        self,
        env: BlockSparseTensor,
        site: BlockSparseTensor,
        mpo: BlockSparseTensor,
        side: str,
    ) -> EnvironmentPlan:
        sig = env_signature(env, site, mpo, side)
        return self._get(
            sig,
            lambda: EnvironmentPlan.build(
                env, site, mpo, side, cache=self.contraction_cache
            ),
        )


global_plan_cache = PlanCache()
global_decomp_cache = DecompPlanCache()
global_env_cache = EnvPlanCache()


def get_plan(
    a: BlockSparseTensor,
    b: BlockSparseTensor,
    axes: Axes,
    cache: Optional[PlanCache] = None,
) -> ContractionPlan:
    """Fetch (or build) the ContractionPlan for this structural signature."""
    return (cache or global_plan_cache).get(a, b, axes)


def get_decomp_plan(
    theta: BlockSparseTensor,
    n_row_modes: int,
    cache: Optional[DecompPlanCache] = None,
) -> DecompositionPlan:
    """Fetch (or build) the DecompositionPlan for this structural signature."""
    return (cache or global_decomp_cache).get(theta, n_row_modes)


def get_env_plan(
    env: BlockSparseTensor,
    site: BlockSparseTensor,
    mpo: BlockSparseTensor,
    side: str,
    cache: Optional[EnvPlanCache] = None,
) -> EnvironmentPlan:
    """Fetch (or build) the EnvironmentPlan for this triple's signature."""
    return (cache or global_env_cache).get(env, site, mpo, side)
