"""Davidson eigensolver (paper Alg. 1).

Follows the paper's ITensor-derived implementation: no preconditioning,
modified Gram-Schmidt re-orthogonalization with randomization on breakdown,
small subspace (size 2 during production sweeps).  Operates directly on
block-sparse tensors; the matvec is the environment contraction of Fig. 1d.

The subspace update is batched: each iteration fetches the new column of
the Rayleigh matrix M[j, i] = <v_j | A v_i> AND the new column of the Gram
matrix W[j, i] = <A v_j | A v_i> in ONE fused device call (a stacked reduce
followed by a single host sync), instead of one blocking
``float(np.asarray(...))`` round-trip per inner product.  The residual norm
comes for free from the Gram identity ||A x - lam x||^2 = s^T W s - lam^2
(V orthonormal, s the Ritz coefficients, s^T M s = lam), so convergence is
checked without another sync.  The identity cancels catastrophically once
the true residual approaches sqrt(eps)·|lam| — there the estimate is pure
noise and the break decision would flip on last-ulp input differences — so
below that floor the exact residual-vector norm is measured instead (one
extra sync, only in the already-converged regime), keeping the convergence
branch as ulp-stable as the seed implementation.

One host loop, two vector algebras.  ``_davidson`` makes every decision
of a solve: the float64 ``eigh`` of the small Rayleigh matrix, the Gram
residual estimate and its noise floor, the convergence break, the
Gram-Schmidt breakdown test and random restart, the non-finite health
guard and the ``davidson.no_converge`` fault hook.  The vector algebra
under it is one of two bases with the same methods:

- ``_EagerBasis`` keeps the subspace as lists of ``BlockSparseTensor`` and
  runs one eager JAX op per block.  It takes any block structure, which is
  why the seed rung, bare contractors and unpadded engines use it: their
  structures change with every bond, so compiled algebra would compile per
  site per sweep.
- ``_FusedBasis`` keeps the subspace as two stacked tensors (every block
  with a leading axis of ``n_iter`` slots) and runs each step as one jitted
  program: ``davidson_start``, ``davidson_columns``, ``davidson_ritz`` and
  ``davidson_orthogonalize``, four per block structure and budget.  The
  caller asks for it (``fused=True``) where the operands are bucket-padded,
  which is what keeps the structures, and so the programs, few.  A matvec
  output whose structure differs from the subspace's hands the solve over
  to the eager basis, which grows the structure as the seed did.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..dist import faults
from ..dist.faults import NumericalHealthError
from ..tensor.blocksparse import BlockSparseTensor

# Shared numerical thresholds — the batched multi-problem mirror
# (repro/serve/multicore.py) must make bit-identical break decisions, so it
# imports these instead of re-stating the literals.
GRAM_NOISE_FLOOR = 1e-12   # scale factor for the Gram-identity noise floor
GS_BREAKDOWN_TOL = 1e-12   # Gram-Schmidt breakdown threshold factor


@dataclasses.dataclass
class DavidsonInfo:
    """Health record of one Davidson solve (no more silent break-outs).

    ``converged``: the residual norm dropped below ``tol`` before the
    iteration budget ran out.  Production sweeps with small ``n_iter``
    typically stop on the budget without ever measuring the final residual,
    so ``converged=False`` there means "unknown", not "diverged" — the
    interesting counters are ``restarts`` (Gram-Schmidt breakdowns answered
    with a seeded random restart) and ``exhausted`` (the restart ALSO broke
    down: the Krylov subspace is exhausted and the solve accepted the
    current Ritz pair early, which the seed implementation did silently).
    """

    converged: bool = False
    iterations: int = 0
    restarts: int = 0
    exhausted: bool = False


def _read(value, what=float):
    """A blocking device-to-host read of ``value``, converted by ``what``."""
    with obs.span("davidson.read"):
        return what(np.asarray(value))


def _apply(matvec, x: BlockSparseTensor) -> BlockSparseTensor:
    with obs.span("davidson.matvec"):
        return matvec(x)


class _EagerBasis:
    """The subspace as lists of tensors; one eager JAX op per block."""

    def __init__(self, V, AV):
        self.V, self.AV = V, AV

    @classmethod
    def start(cls, x0: BlockSparseTensor) -> "_EagerBasis":
        return cls([x0.scale(1.0 / x0.norm())], [])

    @property
    def head(self) -> BlockSparseTensor:
        """The newest basis vector: the next matvec's input."""
        return self.V[-1]

    def admit(self, t: BlockSparseTensor) -> "_EagerBasis":
        return self  # eager algebra takes any block structure

    def columns(self, av: BlockSparseTensor):
        """[<V_j|av>]_j ++ [<AV_j|av>]_j after appending ``av`` to AV."""
        self.AV.append(av)
        vals = [v.inner(av) for v in self.V] + [w.inner(av) for w in self.AV]
        return jnp.stack(vals)

    def ritz(self, s, lam: float, residual: bool):
        """Ritz vector x = V s and, with ``residual``, q = AV s - lam x.
        The norm of q is left to the caller (None): it is read only below
        the Gram noise floor."""
        x = self.V[0].scale(s[0])
        for j in range(1, len(s)):
            x = x + self.V[j].scale(s[j])
        if not residual:
            return x, None, None
        q = self.AV[0].scale(s[0])
        for j in range(1, len(s)):
            q = q + self.AV[j].scale(s[j])
        return x, q - x.scale(lam), None

    def orthogonalize(self, q: BlockSparseTensor):
        """Modified Gram-Schmidt of q against V: (q, |q| on the device)."""
        for v in self.V:
            q = q - v.scale(v.inner(q))
        return q, q.norm()

    def extend(self, q: BlockSparseTensor, qn: float) -> BlockSparseTensor:
        """Append q / qn (qn read on the host) to V; returns it."""
        self.V.append(q.scale(1.0 / qn))
        return self.head

    @staticmethod
    def unit(x: BlockSparseTensor) -> BlockSparseTensor:
        return x.scale(1.0 / x.norm())


# -------------------------------------------------- the fused algebra's programs
# A stacked tensor: a BlockSparseTensor whose every block carries a leading
# axis over the subspace slots; slots not yet filled hold zeros, so sums
# and inner products over all slots equal those over the filled ones.
def _slots(S: BlockSparseTensor) -> int:
    return next(iter(S.blocks.values())).shape[0]


def _slot(S: BlockSparseTensor, j) -> BlockSparseTensor:
    return BlockSparseTensor(
        S.indices, {k: b[j] for k, b in S.blocks.items()}, S.charge)


def _put(S: BlockSparseTensor, j, t: BlockSparseTensor) -> BlockSparseTensor:
    return BlockSparseTensor(S.indices, {
        k: jax.lax.dynamic_update_index_in_dim(b, t.blocks[k], j, 0)
        for k, b in S.blocks.items()}, S.charge)


def _stacked_inner(S: BlockSparseTensor, t: BlockSparseTensor):
    """[<S_j|t>]_j over every slot j."""
    return jnp.stack([_slot(S, j).inner(t) for j in range(_slots(S))])


def _combine(S: BlockSparseTensor, s) -> BlockSparseTensor:
    """sum_j s_j S_j, accumulated in slot order."""
    out = _slot(S, 0).scale(s[0])
    for j in range(1, _slots(S)):
        out = out + _slot(S, j).scale(s[j])
    return out


def davidson_start(x0: BlockSparseTensor, n_slots: int):
    """x0 / |x0|, and the stacked V (holding it in slot 0) and AV (empty)."""
    x = x0.scale(1.0 / x0.norm())
    V = BlockSparseTensor(x.indices, {
        k: jnp.zeros((n_slots,) + b.shape, b.dtype).at[0].set(b)
        for k, b in x.blocks.items()}, x.charge)
    AV = jax.tree_util.tree_map(jnp.zeros_like, V)
    return x, V, AV


def davidson_columns(V, AV, av, i):
    """AV with ``av`` in slot i, and [<V_j|av>]_j ++ [<AV_j|av>]_j."""
    AV = _put(AV, i, av)
    return AV, jnp.concatenate([_stacked_inner(V, av), _stacked_inner(AV, av)])


def davidson_ritz(V, AV, s, lam):
    """The unit Ritz vector x / |x| with x = V s, the residual
    q = AV s - lam x, and |q|."""
    x = _combine(V, s)
    q = _combine(AV, s) - x.scale(lam)
    return x.scale(1.0 / x.norm()), q, q.norm()


def davidson_orthogonalize(V, q, i):
    """Modified Gram-Schmidt of q against every slot of V: V with q / |q|
    in slot i + 1, q / |q|, and |q| (a breakdown discards the first two)."""
    for j in range(_slots(V)):
        v = _slot(V, j)
        q = q - v.scale(v.inner(q))
    nrm = q.norm()
    unit = q.scale(1.0 / nrm)
    return _put(V, i + 1, unit), unit, nrm


_start = jax.jit(davidson_start, static_argnums=1)
_columns = jax.jit(davidson_columns)
_ritz = jax.jit(davidson_ritz)
_orthogonalize = jax.jit(davidson_orthogonalize)


def _fused(program, *args):
    with obs.span("davidson.fused"):
        return program(*args)


class _FusedBasis:
    """The subspace as stacked V and AV of ``n_iter`` slots; each step one
    jitted program per block structure and slot count."""

    def __init__(self, x0: BlockSparseTensor, n_iter: int):
        self.head, self.V, self.AV = _fused(_start, x0, n_iter)
        self.n_v, self.n_av = 1, 0
        self._treedef = jax.tree_util.tree_structure(self.head)
        self._dtype = self.head.dtype

    def admit(self, t: BlockSparseTensor):
        """Self when ``t`` has the subspace's block structure, else the
        same subspace as an eager basis."""
        if (jax.tree_util.tree_structure(t) == self._treedef
                and t.dtype == self._dtype):
            return self
        return _EagerBasis([_slot(self.V, j) for j in range(self.n_v)],
                           [_slot(self.AV, j) for j in range(self.n_av)])

    def columns(self, av: BlockSparseTensor):
        self.AV, cols = _fused(_columns, self.V, self.AV, av, self.n_av)
        self.n_av += 1
        return cols

    def ritz(self, s, lam: float, residual: bool):
        """The Ritz vector (already unit), q and |q|, whatever ``residual``:
        one program either way."""
        padded = np.zeros(_slots(self.V), dtype=np.asarray(s).dtype)
        padded[: len(s)] = s
        return _fused(_ritz, self.V, self.AV, padded, lam)

    def orthogonalize(self, q: BlockSparseTensor):
        V, unit, nrm = _fused(_orthogonalize, self.V, q, self.n_v - 1)
        return (V, unit), nrm

    def extend(self, candidate, qn: float) -> BlockSparseTensor:
        self.V, self.head = candidate
        self.n_v += 1
        return self.head

    @staticmethod
    def unit(x: BlockSparseTensor) -> BlockSparseTensor:
        return x  # ``ritz`` returned it unit


def davidson(
    matvec: Callable[[BlockSparseTensor], BlockSparseTensor],
    x0: BlockSparseTensor,
    n_iter: int = 2,
    tol: float = 1e-10,
    seed: int = 0,
    *,
    fused: bool = False,
) -> Tuple[float, BlockSparseTensor, DavidsonInfo]:
    """Return (smallest eigenvalue, eigenvector approximation, health info).

    Health guard: the Rayleigh-Ritz column read is the solve's one existing
    host sync per iteration — a non-finite entry there (a NaN-poisoned
    matvec, an overflowed contraction) would otherwise propagate silently
    into the eigh and out through the MPS, so it raises
    ``NumericalHealthError(stage="davidson")`` at zero extra sync cost.

    ``fused`` runs the subspace algebra as the jitted programs of
    ``_FusedBasis`` (one ``davidson.fused`` span each); callers set it where
    the operands are bucket-padded, so the block structures are few.  The
    decisions, the reads and the results are those of the eager algebra.
    """
    with obs.span("davidson.solve"):
        return _davidson(matvec, x0, n_iter, tol, seed, fused)


def _davidson(matvec, x0, n_iter, tol, seed, fused):
    info = DavidsonInfo()
    # injected non-convergence: suppress the residual break so the solve
    # runs its full budget and honestly reports converged=False
    force_no_converge = faults.fire("davidson.no_converge") is not None
    if n_iter <= 0:
        x = x0.scale(1.0 / x0.norm())
        lam = _read(x.inner(_apply(matvec, x)), lambda a: float(np.real(a)))
        if not np.isfinite(lam):
            raise NumericalHealthError(
                "non-finite Rayleigh quotient", stage="davidson"
            )
        return lam, x, info

    basis = _FusedBasis(x0, n_iter) if fused else _EagerBasis.start(x0)
    dim = n_iter + 1
    M = np.zeros((dim, dim))  # <v_j | A v_i>
    W = np.zeros((dim, dim))  # <A v_j | A v_i>
    av = _apply(matvec, basis.head)

    for i in range(n_iter):
        basis = basis.admit(av)
        cols = _read(basis.columns(av), np.real)
        half = len(cols) // 2
        m_col, w_col = cols[: i + 1], cols[half : half + i + 1]
        if not (np.isfinite(m_col).all() and np.isfinite(w_col).all()):
            raise NumericalHealthError(
                f"non-finite Rayleigh-Ritz entries at iteration {i}",
                stage="davidson",
            )
        info.iterations = i + 1
        M[: i + 1, i] = M[i, : i + 1] = m_col
        W[: i + 1, i] = W[i, : i + 1] = w_col
        evals, evecs = np.linalg.eigh(M[: i + 1, : i + 1])
        lam, s = float(evals[0]), evecs[:, 0]

        # Ritz vector, and the residual q = A x - lam x (device-side)
        last = i == n_iter - 1
        x, q, q_norm = basis.ritz(s, lam, residual=not last)
        if last:
            break

        # residual norm from the Gram identity when that is well above the
        # cancellation noise floor, and measured exactly otherwise
        # (converged regime only)
        qn2_gram = float(s @ W[: i + 1, : i + 1] @ s - lam * lam)
        noise_floor = GRAM_NOISE_FLOOR * max(1.0, lam * lam)
        if qn2_gram > noise_floor:
            qn = float(np.sqrt(qn2_gram))
        else:
            qn = _read(q.norm() if q_norm is None else q_norm)
        if qn < tol and not force_no_converge:
            info.converged = True
            break

        # modified Gram-Schmidt vs all v_j, randomize on breakdown (paper)
        candidate, qn2 = basis.orthogonalize(q)
        qn2 = _read(qn2)
        if qn2 < GS_BREAKDOWN_TOL * max(qn, 1.0):
            # restart with A·(random): confined to range(A), so under the
            # bucket-padded matvec (dist/batch.py) the new direction stays
            # in the invariant unpadded subspace instead of acquiring O(1)
            # weight in the padded rows where the operator is zero
            info.restarts += 1
            q = _apply(matvec, BlockSparseTensor.random(
                x.indices, x.charge, jax.random.PRNGKey(seed + i), dtype=x.dtype
            ))
            basis = basis.admit(q)
            candidate, qn2 = basis.orthogonalize(q)
            qn2 = _read(qn2)
            if qn2 < GS_BREAKDOWN_TOL * max(qn, 1.0):
                info.exhausted = True
                break  # subspace exhausted; accept the current Ritz pair
        av = _apply(matvec, basis.extend(candidate, qn2))

    return lam, basis.unit(x), info
