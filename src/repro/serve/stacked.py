"""Stacked block-sparse tensors: a leading problem axis over shared structure.

The multi-problem solver (DESIGN.md Sec. 3.7) batches B DMRG problems that
share one charge structure — same indices, same block keys, different block
*values* (e.g. a J/h parameter sweep) — by stacking each block along a new
leading axis: a "stacked" ``BlockSparseTensor`` carries ``[B, ...]`` block
arrays while its indices still describe the per-problem structure.

This representation composes with everything PRs 1-5 built, because the
whole plan/execute layer reads only indices / charges / block KEYS (never
values or array ranks):

- plan caches (``dist/plan.py``) accept stacked tensors directly — a batch
  shares its plans (and their compiled cores) with single-problem runs;
- ``jax.vmap`` over the block leaves makes every per-problem traced body
  (matvec, fused env update, bucketed SVD) see ordinary unbatched blocks, so
  the existing engine code runs unchanged inside the batch — ``StackedOps``
  below wraps those bodies in ``jax.jit(jax.vmap(...))`` once per structure;
- structural ops (``flip_flow``, index bookkeeping) never touch data, so
  they work on stacked tensors as-is.

What does NOT compose is anything with per-problem *scalars* (norms, inner
products, scaling): those return/consume ``[B]`` arrays here (``binner``,
``bnorm``, ``bscale``, ``bselect``), and padding must skip the problem axis
(``pad_stacked`` / ``unpad_stacked``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..dist.engine import ContractionEngine
from ..tensor.blocksparse import BlockKey, BlockSparseTensor
from ..dist.batch import pad_index


# ----------------------------------------------------------- stack / unstack
def stack_tensors(ts: Sequence[BlockSparseTensor]) -> BlockSparseTensor:
    """Stack B same-structure tensors into one stacked tensor ([B, ...] blocks).

    All inputs must agree on indices, charge and block keys — the scheduler
    guarantees this by grouping requests by structure signature; a mismatch
    here means a grouping bug, so it raises instead of broadcasting.
    """
    t0 = ts[0]
    keys = sorted(t0.blocks)
    for t in ts[1:]:
        if t.indices != t0.indices or t.charge != t0.charge:
            raise ValueError("stack_tensors: mismatched index structure")
        if sorted(t.blocks) != keys:
            raise ValueError("stack_tensors: mismatched block keys")
    blocks = {k: jnp.stack([t.blocks[k] for t in ts]) for k in keys}
    return BlockSparseTensor(t0.indices, blocks, t0.charge)


def unstack_tensor(t: BlockSparseTensor, b: int) -> BlockSparseTensor:
    """Extract problem ``b`` from a stacked tensor (unbatched view)."""
    return BlockSparseTensor(
        t.indices, {k: blk[b] for k, blk in t.blocks.items()}, t.charge
    )


def broadcast_tensor(t: BlockSparseTensor, B: int) -> BlockSparseTensor:
    """Replicate an unbatched tensor across B problems (zero-copy view)."""
    blocks = {
        k: jnp.broadcast_to(blk[None], (B,) + tuple(blk.shape))
        for k, blk in t.blocks.items()
    }
    return BlockSparseTensor(t.indices, blocks, t.charge)


def batch_size(t: BlockSparseTensor) -> int:
    for b in t.blocks.values():
        return int(b.shape[0])
    raise ValueError("batch_size of a tensor with no blocks")


# ------------------------------------------------- per-problem scalar algebra
def _bshape(c, nd: int):
    """Reshape a [B] coefficient vector for broadcasting over [B, ...] blocks."""
    return jnp.reshape(jnp.asarray(c), (-1,) + (1,) * nd)


def binner(a: BlockSparseTensor, b: BlockSparseTensor) -> jax.Array:
    """Per-problem <a|b>: a [B] array, summing over shared block keys only
    (the stacked mirror of ``BlockSparseTensor.inner``)."""
    acc = None
    for k, blk in a.blocks.items():
        other = b.blocks.get(k)
        if other is None:
            continue
        axes = tuple(range(1, blk.ndim))
        part = jnp.sum(jnp.conj(blk) * other, axis=axes)
        acc = part if acc is None else acc + part
    return acc


def bnorm_sq(t: BlockSparseTensor) -> jax.Array:
    acc = None
    for blk in t.blocks.values():
        part = jnp.sum(jnp.abs(blk) ** 2, axis=tuple(range(1, blk.ndim)))
        acc = part if acc is None else acc + part
    return jnp.real(acc)


def bnorm(t: BlockSparseTensor) -> jax.Array:
    """Per-problem Frobenius norm, a [B] array."""
    return jnp.sqrt(bnorm_sq(t))


def bscale(t: BlockSparseTensor, c) -> BlockSparseTensor:
    """Scale each problem by its own coefficient (c is a [B] array)."""
    blocks = {}
    for k, blk in t.blocks.items():
        blocks[k] = blk * _bshape(c, blk.ndim - 1).astype(blk.dtype)
    return BlockSparseTensor(t.indices, blocks, t.charge)


def bselect(
    mask, a: BlockSparseTensor, b: BlockSparseTensor
) -> BlockSparseTensor:
    """Per-problem select: problem i takes a's slice where mask[i], else b's.

    Missing blocks on either side count as zeros (like ``__add__``'s union
    semantics), so tensors produced by different pipelines can be merged.
    """
    assert a.indices == b.indices and a.charge == b.charge
    mask = jnp.asarray(mask)
    blocks: Dict[BlockKey, jax.Array] = {}
    for k in set(a.blocks) | set(b.blocks):
        ab = a.blocks.get(k)
        bb = b.blocks.get(k)
        if ab is None:
            ab = jnp.zeros_like(bb)
        if bb is None:
            bb = jnp.zeros_like(ab)
        blocks[k] = jnp.where(_bshape(mask, ab.ndim - 1), ab, bb)
    return BlockSparseTensor(a.indices, blocks, a.charge)


def blincomb(ts: Sequence[BlockSparseTensor], coeffs) -> BlockSparseTensor:
    """sum_j coeffs[:, j] * ts[j], per problem (coeffs is [B, len(ts)])."""
    coeffs = jnp.asarray(coeffs)
    out = bscale(ts[0], coeffs[:, 0])
    for j in range(1, len(ts)):
        out = out + bscale(ts[j], coeffs[:, j])
    return out


# ------------------------------------------------------------------- padding
def pad_stacked(t: BlockSparseTensor) -> BlockSparseTensor:
    """``dist.batch.pad_block_sparse`` for stacked tensors: pad every sector
    dim up to its power-of-two bucket, never touching the problem axis."""
    out = BlockSparseTensor(tuple(pad_index(ix) for ix in t.indices), {}, t.charge)
    blocks: Dict[BlockKey, jax.Array] = {}
    for k, blk in t.blocks.items():
        tgt = out.block_shape(k)
        if tgt == tuple(blk.shape[1:]):
            blocks[k] = blk
        else:
            blocks[k] = jnp.pad(
                blk,
                ((0, 0),) + tuple((0, ts - s) for ts, s in zip(tgt, blk.shape[1:])),
            )
    out.blocks = blocks
    return out


def unpad_stacked(t: BlockSparseTensor, indices) -> BlockSparseTensor:
    """Slice a padded stacked tensor back to the given per-problem structure."""
    out = BlockSparseTensor(indices, {}, t.charge)
    blocks: Dict[BlockKey, jax.Array] = {}
    for k, blk in t.blocks.items():
        tgt = out.block_shape(k)
        if tgt == tuple(blk.shape[1:]):
            blocks[k] = blk
        else:
            blocks[k] = blk[(slice(None),) + tuple(slice(0, s) for s in tgt)]
    out.blocks = blocks
    return out


# -------------------------------------------------------------- StackedOps
class StackedOps:
    """Compiled vmapped pipelines over stacked tensors, with retrace counting.

    One instance per serving process: the jitted callables in ``_fns`` (and
    jax's own trace cache behind them, keyed by block structure AND batch
    size) must persist across batches for steady-state requests to replay
    compiled code.  ``retraces`` counts every (re)trace of any wrapped body —
    the number the serve CLI's ``--check`` asserts stays zero after warmup.

    The per-problem bodies are the existing engine paths verbatim
    (``two_site_matvec``, ``env.update_left/right``, planned contraction);
    ``jax.vmap`` shows them unbatched blocks, so batching cannot change
    per-problem numerics.
    """

    def __init__(self, engine: ContractionEngine | None = None):
        self.engine = engine if engine is not None else ContractionEngine(
            backend="batched"
        )
        self.retraces = 0
        self._fns: Dict = {}

    def _jit_vmap(self, key, body):
        fn = self._fns.get(key)
        if fn is None:
            ops = self

            def stacked_core(*args):
                ops.retraces += 1  # body runs only when jax (re)traces
                return body(*args)

            fn = jax.jit(jax.vmap(stacked_core))
            self._fns[key] = fn
        return fn

    def contract(self, a, b, axes):
        fn = self._jit_vmap(
            ("c", axes), lambda a_, b_: self.engine(a_, b_, axes)
        )
        return fn(a, b)

    def matvec_fn(self, A, Wj, Wj1, B):
        """Batched Davidson matvec closure over fixed stacked operands."""
        mv = self._jit_vmap(
            "mv",
            lambda A_, Wj_, Wj1_, B_, x_: self.engine.two_site_matvec(
                A_, Wj_, Wj1_, B_, x_
            ),
        )
        return lambda x: mv(A, Wj, Wj1, B, x)

    def env_update(self, side, env, T, W):
        """Fused env update per problem (pads + plans inside the trace)."""
        body = (
            self.engine.env.update_left
            if side == "left"
            else self.engine.env.update_right
        )
        fn = self._jit_vmap(("env", side), lambda e_, t_, w_: body(e_, t_, w_))
        return fn(env, T, W)

    def stats(self) -> Dict:
        return {"retraces": self.retraces, "compiled_fns": len(self._fns)}
