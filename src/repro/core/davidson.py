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


def _new_columns(V, AV, i) -> np.ndarray:
    """Fetch M[j, i] and W[j, i] for j <= i in one device round-trip."""
    vals = [V[j].inner(AV[i]) for j in range(i + 1)]
    vals += [AV[j].inner(AV[i]) for j in range(i + 1)]
    stacked = jnp.stack(vals)
    with obs.span("davidson.read"):
        return np.real(np.asarray(jax.device_get(stacked)))


def _read_norm(t: BlockSparseTensor) -> float:
    """``t.norm()`` on the host: a blocking device read."""
    nrm = t.norm()
    with obs.span("davidson.read"):
        return float(np.asarray(nrm))


def _apply(matvec, x: BlockSparseTensor) -> BlockSparseTensor:
    with obs.span("davidson.matvec"):
        return matvec(x)


def davidson(
    matvec: Callable[[BlockSparseTensor], BlockSparseTensor],
    x0: BlockSparseTensor,
    n_iter: int = 2,
    tol: float = 1e-10,
    seed: int = 0,
) -> Tuple[float, BlockSparseTensor, DavidsonInfo]:
    """Return (smallest eigenvalue, eigenvector approximation, health info).

    Health guard: the Rayleigh-Ritz column read is the solve's one existing
    host sync per iteration — a non-finite entry there (a NaN-poisoned
    matvec, an overflowed contraction) would otherwise propagate silently
    into the eigh and out through the MPS, so it raises
    ``NumericalHealthError(stage="davidson")`` at zero extra sync cost.
    """
    with obs.span("davidson.solve"):
        return _davidson(matvec, x0, n_iter, tol, seed)


def _davidson(matvec, x0, n_iter, tol, seed):
    info = DavidsonInfo()
    # injected non-convergence: suppress the residual break so the solve
    # runs its full budget and honestly reports converged=False
    force_no_converge = faults.fire("davidson.no_converge") is not None
    nrm = x0.norm()
    x = x0.scale(1.0 / nrm)
    V = [x]
    AV = [_apply(matvec, x)]
    if n_iter <= 0:
        rayleigh = V[0].inner(AV[0])
        with obs.span("davidson.read"):
            lam = float(np.real(np.asarray(rayleigh)))
        if not np.isfinite(lam):
            raise NumericalHealthError(
                "non-finite Rayleigh quotient", stage="davidson"
            )
        return lam, x, info

    dim = n_iter + 1
    M = np.zeros((dim, dim))  # <v_j | A v_i>
    W = np.zeros((dim, dim))  # <A v_j | A v_i>
    lam, x = 0.0, V[0]

    for i in range(n_iter):
        cols = _new_columns(V, AV, i)
        if not np.isfinite(cols).all():
            raise NumericalHealthError(
                f"non-finite Rayleigh-Ritz entries at iteration {i}",
                stage="davidson",
            )
        info.iterations = i + 1
        M[: i + 1, i] = M[i, : i + 1] = cols[: i + 1]
        W[: i + 1, i] = W[i, : i + 1] = cols[i + 1 :]
        evals, evecs = np.linalg.eigh(M[: i + 1, : i + 1])
        lam, s = float(evals[0]), evecs[:, 0]

        # Ritz vector (device-side; no sync)
        x = V[0].scale(s[0])
        for j in range(1, i + 1):
            x = x + V[j].scale(s[j])
        if i == n_iter - 1:
            break

        # residual q = A x - lam x (device-side), with its norm from the
        # Gram identity when that is well above the cancellation noise
        # floor, and measured exactly otherwise (converged regime only)
        q = AV[0].scale(s[0])
        for j in range(1, i + 1):
            q = q + AV[j].scale(s[j])
        q = q - x.scale(lam)
        qn2_gram = float(s @ W[: i + 1, : i + 1] @ s - lam * lam)
        noise_floor = GRAM_NOISE_FLOOR * max(1.0, lam * lam)
        if qn2_gram > noise_floor:
            qn = float(np.sqrt(qn2_gram))
        else:
            qn = _read_norm(q)
        if qn < tol and not force_no_converge:
            info.converged = True
            break

        # modified Gram-Schmidt vs all v_j, randomize on breakdown (paper)
        for j in range(i + 1):
            q = q - V[j].scale(V[j].inner(q))
        qn2 = _read_norm(q)
        if qn2 < GS_BREAKDOWN_TOL * max(qn, 1.0):
            # restart with A·(random): confined to range(A), so under the
            # bucket-padded matvec (dist/batch.py) the new direction stays
            # in the invariant unpadded subspace instead of acquiring O(1)
            # weight in the padded rows where the operator is zero
            info.restarts += 1
            q = _apply(matvec, BlockSparseTensor.random(
                x.indices, x.charge, jax.random.PRNGKey(seed + i), dtype=x.dtype
            ))
            for j in range(i + 1):
                q = q - V[j].scale(V[j].inner(q))
            qn2 = _read_norm(q)
            if qn2 < GS_BREAKDOWN_TOL * max(qn, 1.0):
                info.exhausted = True
                break  # subspace exhausted; accept the current Ritz pair
        q = q.scale(1.0 / qn2)
        V.append(q)
        AV.append(_apply(matvec, q))

    return lam, x.scale(1.0 / x.norm()), info
