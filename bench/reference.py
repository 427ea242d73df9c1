"""Plain dense numpy reference for the J1-J2 cells.

Independent of the program: it builds its own lattice, its own
uncompressed finite-state MPO and its own dense environments, and imports
nothing from ``src/``.  Every function computes in the dtype of its inputs,
so the same code run on float32 copies is the lower-precision control.

Local basis of every site: index 0 is spin up (2Sz = +1), index 1 spin down.
An MPO tensor is ``W[w_left, s_out, s_in, w_right]``; an environment is
``L[bra, mpo, ket]``; an MPS tensor is ``A[left, phys, right]``.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

SZ = np.diag([0.5, -0.5])
SP = np.array([[0.0, 1.0], [0.0, 0.0]])  # S+ |down> = |up>
SM = SP.T
ID = np.eye(2)

Bond = Tuple[int, int, float]


def j1j2_bonds(lx: int, ly: int, j1: float, j2: float,
               cylinder: bool) -> List[Bond]:
    """Distinct (i, j, J) bonds, i < j, of the J1-J2 model on an lx x ly
    strip, site ``x * ly + y``: J1 to +x and +y, J2 to the two +x diagonals;
    with ``cylinder`` (and ly > 2) y wraps around."""
    wrap = cylinder and ly > 2
    out: Dict[Tuple[int, int], float] = {}

    def add(x0, y0, x1, y1, coef):
        if x1 >= lx or (not wrap and not 0 <= y1 < ly):
            return
        i, j = x0 * ly + y0 % ly, x1 * ly + y1 % ly
        if i != j:
            out.setdefault((min(i, j), max(i, j)), coef)

    for x in range(lx):
        for y in range(ly):
            add(x, y, x + 1, y, j1)
            add(x, y, x, y + 1, j1)
            add(x, y, x + 1, y + 1, j2)
            add(x, y, x + 1, y - 1, j2)
    return [(i, j, c) for (i, j), c in sorted(out.items())]


def heisenberg_mpo(n: int, bonds: Sequence[Bond], dtype=np.float64):
    """Uncompressed MPO of sum_b J_b S_i . S_j.

    Channel 0 of every MPO bond is "nothing placed yet", channel 1 "term
    complete"; each term open across a bond adds three channels, one per
    first operator (Sz, S+, S-).
    """
    open_at = [[t for t, (i, j, _) in enumerate(bonds) if i <= b < j]
               for b in range(n + 1)]
    chan = [{(t, k): 2 + 3 * p + k for p, t in enumerate(ts) for k in range(3)}
            for ts in open_at]
    first = [(1.0, SZ), (0.5, SP), (0.5, SM)]
    second = [SZ, SM, SP]
    mpo = []
    for s in range(n):
        left, right = chan[s - 1] if s else {}, chan[s]
        W = np.zeros((2 + len(left), 2, 2, 2 + len(right)))
        W[0, :, :, 0] = ID
        W[1, :, :, 1] = ID
        for t, (i, j, c) in enumerate(bonds):
            for k in range(3):
                if s == i:
                    W[0, :, :, right[(t, k)]] = c * first[k][0] * first[k][1]
                elif i < s < j:
                    W[left[(t, k)], :, :, right[(t, k)]] = ID
                elif s == j:
                    W[left[(t, k)], :, :, 1] = second[k]
        mpo.append(W.astype(dtype))
    return mpo


def left_edge(W0, dtype):
    L = np.zeros((1, W0.shape[0], 1), dtype)
    L[0, 0, 0] = 1
    return L


def right_edge(Wn, dtype):
    R = np.zeros((1, Wn.shape[3], 1), dtype)
    R[0, 1, 0] = 1
    return R


def _left(L, A, W):
    """L'[x, v, y] = conj(A[a, s, x]) L[a, w, b] W[w, s, t, v] A[b, t, y]."""
    t = np.tensordot(L, A, axes=(2, 0))                      # a w t y
    t = np.tensordot(t, W, axes=((1, 2), (0, 2)))            # a y s v
    t = np.tensordot(A.conj(), t, axes=((0, 1), (0, 2)))     # x y v
    return t.transpose(0, 2, 1)


def _right(R, B, W):
    """R'[a, w, b] = conj(B[a, s, x]) W[w, s, t, v] B[b, t, y] R[x, v, y]."""
    t = np.tensordot(B, R, axes=(2, 2))                      # b t x v
    t = np.tensordot(t, W, axes=((1, 3), (2, 3)))            # b x w s
    t = np.tensordot(B.conj(), t, axes=((1, 2), (3, 1)))     # a b w
    return t.transpose(0, 2, 1)


def left_envs(mps, mpo, upto: int):
    """[L_0 .. L_upto]: L_k holds sites < k."""
    out = [left_edge(mpo[0], mps[0].dtype)]
    for k in range(upto):
        out.append(_left(out[-1], mps[k], mpo[k]))
    return out


def right_env(mps, mpo, start: int):
    """R_start holding sites >= start (R_n is the right edge)."""
    n = len(mps)
    R = right_edge(mpo[n - 1], mps[0].dtype)
    for k in range(n - 1, start - 1, -1):
        R = _right(R, mps[k], mpo[k])
    return R


def matvec(L, W1, W2, R, theta):
    """y = H_eff theta for a two-site theta[a, s1, s2, c]."""
    t = np.tensordot(L, theta, axes=(2, 0))              # a w t1 t2 d
    t = np.tensordot(t, W1, axes=((1, 2), (0, 2)))       # a t2 d s1 v
    t = np.tensordot(t, W2, axes=((4, 1), (0, 2)))       # a d s1 s2 u
    t = np.tensordot(t, R, axes=((4, 1), (1, 2)))        # a s1 s2 c
    return t


def energy(mps, mpo) -> float:
    """<psi|H|psi> / <psi|psi> by a full left-to-right contraction."""
    L = left_envs(mps, mpo, len(mps))[-1]
    R = right_edge(mpo[-1], mps[0].dtype)
    num = np.tensordot(L, R, axes=((0, 1, 2), (0, 1, 2)))
    N = np.ones((1, 1), mps[0].dtype)
    for A in mps:
        N = np.tensordot(np.tensordot(N, A, axes=(1, 0)), A.conj(),
                         axes=((0, 1), (0, 1))).T
    return float(num) / float(N.sum())


def gauge_error(mps, center: int) -> float:
    """Largest |A^dag A - 1| over sites left of the two-site ``center`` pair
    and |B B^dag - 1| right of it: how far the stored tensors are from the
    mixed canonical form a two-site sweep keeps."""
    worst = 0.0
    for k, A in enumerate(mps):
        if k < center:
            g = np.tensordot(A.conj(), A, axes=((0, 1), (0, 1)))
        elif k > center + 1:
            g = np.tensordot(A, A.conj(), axes=((1, 2), (1, 2)))
        else:
            continue
        worst = max(worst, float(np.abs(g - np.eye(g.shape[0])).max()))
    return worst


def truncated_split(y, max_bond: int, cutoff: float):
    """Singular values of y across (a s1 | s2 c), the number kept by the
    global rule ``min(max_bond, #(s > cutoff * s_max))`` (at least one), and
    the discarded weight."""
    a, s1, s2, c = y.shape
    s = np.linalg.svd(y.reshape(a * s1, s2 * c), compute_uv=False)
    keep = max(1, min(int(max_bond), int(np.sum(s > cutoff * s[0]))))
    return s, keep, float(np.sum(s[keep:].astype(np.float64) ** 2))


def ground_energy(n: int, bonds: Sequence[Bond], dtype=np.float64) -> float:
    """Exact lowest energy in the 2Sz = 0 sector by dense diagonalization."""
    states = [sum(1 << k for k in ups)
              for ups in itertools.combinations(range(n), n // 2)]
    pos = {s: i for i, s in enumerate(states)}
    H = np.zeros((len(states), len(states)))
    for col, st in enumerate(states):
        for i, j, c in bonds:
            bi, bj = (st >> i) & 1, (st >> j) & 1
            H[col, col] += c * (0.25 if bi == bj else -0.25)
            if bi != bj:
                H[pos[st ^ (1 << i) ^ (1 << j)], col] += 0.5 * c
    return float(np.linalg.eigvalsh(H.astype(dtype))[0])
