"""Steady two-site sweeps at a fixed bond on one ``DMRGEngine``.

Set-up builds the engine as ``run_dmrg`` does (compressed MPO, the
production solver flags of the configuration), but starts it from a seeded
random state on the exact block structure: at every bond, each 2Sz the two
halves can share, with the smaller of their counts.  The bond is the exact
one (``max_bond`` at least the widest bond), so every sweep keeps that
structure and the seed changes values only, never the programs.  A
rehearsal engine, from another random state of the same structure, sweeps
until a whole sweep builds no program; the window's engine shares its
compiled cores, starts from the seed's own random state and sweeps for
``seconds``, so the window holds the solve's convergence and then steady
sweeps.  It stops after the first pair update that ends past ``seconds``.

Check, once the window has closed: at the pair the sweep would update next,
the program's own compiled matvec, split and stored environments against the
dense reference (``bench/reference.py``) on the state the window produced,
and the program's energy there against exact diagonalization.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import reference as R

PHYS_UP = (1,)  # the charge (2Sz) of the reference's basis state 0


class _Stop(Exception):
    pass


def _bonds(cfg):
    return R.j1j2_bonds(cfg["lx"], cfg["ly"], cfg["J1"], cfg["J2"],
                        cfg["cylinder"])


def bond_sectors(n, k):
    """(2Sz, dim) sectors of the bond after ``k`` of ``n`` spins in the
    2Sz = 0 sector: the bond charge is minus the 2Sz of the left ``k``
    spins, and its dim the smaller of the two halves' counts."""
    out = []
    for q in range(-k, k + 1, 2):
        left = math.comb(k, (k - q) // 2)
        r = n - k
        if abs(q) > r or (r + q) % 2:
            continue
        out.append(((q,), min(left, math.comb(r, (r + q) // 2))))
    return tuple(out)


def random_state(n, max_bond, seed, salt, dtype):
    """A seeded random MPS of the program's own kind on the exact block
    structure; values drawn on the host from ``(seed, salt)``."""
    import jax.numpy as jnp

    from repro.core import MPS, spin_half_space
    from repro.tensor import IN, OUT, BlockSparseTensor, Index

    bonds = [bond_sectors(n, k) for k in range(n + 1)]
    widest = max(sum(d for _, d in b) for b in bonds)
    if widest > max_bond:
        raise ValueError(f"max_bond {max_bond} is under the exact bond "
                         f"{widest}: the random start needs the exact one")
    rng = np.random.default_rng([seed % 2**64, salt])
    phys = spin_half_space().index
    tensors = []
    for k in range(n):
        t = BlockSparseTensor([Index(bonds[k], IN, "l"), phys,
                               Index(bonds[k + 1], OUT, "r")], {})
        t.blocks = {key: jnp.asarray(rng.standard_normal(t.block_shape(key)),
                                     dtype)
                    for key in t.valid_keys()}
        tensors.append(t)
    return MPS(tensors)


def setup(cfg, mix, seed, ctx):
    import jax.numpy as jnp

    from repro.core import DMRGEngine, build_mpo, compress_mpo, spin_half_space
    from repro.core.models import heisenberg_j1j2_terms
    dtype = jnp.dtype(cfg["dtype"])
    n = cfg["lx"] * cfg["ly"]
    terms = heisenberg_j1j2_terms(cfg["lx"], cfg["ly"], cfg["J1"], cfg["J2"],
                                  cylinder=cfg["cylinder"])
    with ctx.phase("mpo"):
        mpo = compress_mpo(build_mpo(spin_half_space(), terms, n, dtype=dtype),
                           cutoff=cfg["mpo_cutoff"])

    def engine(salt, contract_fn=None):
        return DMRGEngine(
            random_state(n, cfg["max_bond"], seed, salt, dtype), mpo,
            algo=cfg["algo"], davidson_iters=cfg["davidson_iters"],
            jit_matvec=cfg["jit_matvec"], engine=contract_fn,
            seed=seed % 2**31)

    with ctx.phase("rehearsal engine"):
        rehearsal = engine(1)
    settled = None
    for k in range(mix["settle_sweeps_max"]):
        with ctx.phase(f"rehearsal sweep m={cfg['max_bond']}") as built:
            rehearsal.sweep(max_bond=cfg["max_bond"], cutoff=cfg["cutoff"])
        if built()["programs"] == 0:
            settled = k + 1
            break
    ctx.log(settle_sweeps=settled)
    with ctx.phase("engine"):
        eng = engine(0, rehearsal.contract_fn)
    # the next pair to update, and the sweep direction there
    return {"engine": eng, "cfg": cfg, "position": (0, "LR"),
            "ground": R.ground_energy(n, _bonds(cfg))}


def window(st, seconds, spans, meter):
    import jax

    from repro.dist.decomp import host_svd_stats

    eng, cfg = st["engine"], st["cfg"]
    counts = {"updates": 0, "iterations": 0, "solves": 0, "retries": 0}
    partial = {}
    durations = []
    stop = []
    snap, host0 = meter.snapshot(), host_svd_stats()["seconds"]

    def on_site(state):
        now = time.perf_counter()
        spans.end(open_span[0])
        durations.append(now - last[0])
        last[0] = now
        counts["updates"] += 1
        st["position"] = ((state["j"], state["phase"]) if state is not None
                          else (0, "LR"))
        if state is not None:
            partial.update(state["davidson"], retries=state["pair_retries"])
        if now - t0 >= seconds:
            stop.append(now)
            if state is not None:
                raise _Stop
            return
        open_span[0] = spans.begin("bench.pair_update")

    def add(iterations, solves, retries):
        counts["iterations"] += iterations
        counts["solves"] += solves
        counts["retries"] += retries

    with spans.span("bench.window"):
        t0 = time.perf_counter()
        last = [t0]
        open_span = [spans.begin("bench.pair_update")]
        while not stop:
            partial.clear()
            try:
                s = eng.sweep(max_bond=cfg["max_bond"], cutoff=cfg["cutoff"],
                              on_site=on_site)
            except _Stop:
                add(partial.get("iterations", 0), partial.get("solves", 0),
                    partial.get("retries", 0))
                break
            add(s.davidson_iterations, s.davidson_solves, s.pair_retries)
        jax.block_until_ready(
            [t.blocks for t in eng.mps.tensors]
            + [e.blocks for e in eng.left_envs + eng.right_envs if e is not None])
        t_end = time.perf_counter()
    win = t_end - t0
    return {
        "window_s": win,
        "attempted": counts["updates"],
        "failed": counts["retries"],
        "end_to_end": {"pair_updates_per_s": counts["updates"] / win},
        "counters": {
            **counts,
            "host_lapack_s": host_svd_stats()["seconds"] - host0,
            "update_s": durations,
            "window_programs": meter.since(snap)["programs"],
        },
    }


# ------------------------------------------------------------------ check
def dense(t, phys_axes):
    """A block-sparse tensor of the program as one numpy array: bond modes
    in sector order, physical modes in the reference's basis (up, down)."""
    import jax

    offs = []
    for ax, ix in enumerate(t.indices):
        if ax in phys_axes:
            offs.append([0 if q == PHYS_UP else 1 for q, _ in ix.sectors])
        else:
            offs.append(list(np.cumsum([0] + [d for _, d in ix.sectors])[:-1]))
    out = np.zeros(tuple(ix.dim for ix in t.indices))
    keys = list(t.blocks)
    for key, b in zip(keys, jax.device_get([t.blocks[k] for k in keys])):
        sl = tuple(slice(offs[i][s], offs[i][s] + t.indices[i].sectors[s][1])
                   for i, s in enumerate(key))
        out[sl] = b
    return out


def program_side(st):
    """What the program's own compiled path gives at the next pair: the
    state, theta, H_eff theta, and the truncated split of H_eff theta."""
    from repro.dist import pad_block_sparse as pad
    from repro.dist import unpad_block_sparse

    eng, cfg = st["engine"], st["cfg"]
    p, phase = st["position"]
    T = eng.mps.tensors
    theta = eng.contract_fn(T[p], T[p + 1], ((2,), (0,)))
    mv = eng.contract_fn.matvec_fn(
        pad(eng.left_envs[p]), pad(eng.mpo[p]), pad(eng.mpo[p + 1]),
        pad(eng.right_envs[p + 1]), jit=eng.jit_matvec)
    y = unpad_block_sparse(mv(pad(theta)), theta.indices)
    U, V, svals, _ = eng.contract_fn.svd_split(
        y, 2, max_bond=cfg["max_bond"], cutoff=cfg["cutoff"],
        absorb="right" if phase == "LR" else "left")
    theta_d, y_d = dense(theta, (1, 2)), dense(y, (1, 2))
    s = np.sort(np.concatenate([np.asarray(v).ravel()
                                for v in svals.values()]))[::-1]
    return {
        "mps": [dense(t, (1,)) for t in T],
        "theta": theta_d,
        "y": y_d,
        "s_kept": s,
        "uv": np.tensordot(dense(U, (1,)), dense(V, (1,)), axes=(2, 0)),
    }


def control_side(st, prog, dtype=np.float32):
    """The reference itself in ``dtype`` put in the program's place, on the
    state the program produced: the lower-precision control."""
    cfg = st["cfg"]
    p, _ = st["position"]
    n = len(prog["mps"])
    mps = [a.astype(dtype) for a in prog["mps"]]
    mpo = R.heisenberg_mpo(n, _bonds(cfg), dtype)
    theta = np.tensordot(mps[p], mps[p + 1], axes=(2, 0))
    y = R.matvec(R.left_envs(mps, mpo, p)[-1], mpo[p], mpo[p + 1],
                 R.right_env(mps, mpo, p + 2), theta)
    a, s1, s2, c = y.shape
    u, s, vh = np.linalg.svd(y.reshape(a * s1, s2 * c), full_matrices=False)
    keep = max(1, min(cfg["max_bond"], int(np.sum(s > cfg["cutoff"] * s[0]))))
    uv = (u[:, :keep] * s[:keep]) @ vh[:keep]
    return {"mps": mps, "theta": theta, "y": y, "s_kept": s[:keep],
            "uv": uv.reshape(y.shape)}


def compare(st, side) -> dict:
    """The numbers compared: each side's results against the float64
    reference computed on the same inputs."""
    cfg = st["cfg"]
    p, _ = st["position"]
    n = len(side["mps"])
    mps64 = [a.astype(np.float64) for a in side["mps"]]
    mpo = R.heisenberg_mpo(n, _bonds(cfg))
    theta64 = side["theta"].astype(np.float64)
    y_ref = R.matvec(R.left_envs(mps64, mpo, p)[-1], mpo[p], mpo[p + 1],
                     R.right_env(mps64, mpo, p + 2), theta64)
    e_ref = R.energy(mps64, mpo)
    y = side["y"].astype(np.float64)
    e_side = float(np.sum(side["theta"] * side["y"])
                   / np.sum(side["theta"] * side["theta"]))
    s_ref, keep, disc = R.truncated_split(y, cfg["max_bond"], cfg["cutoff"])
    s_kept = side["s_kept"].astype(np.float64)
    if len(s_kept) != keep:
        split = 1.0
    else:
        resid = float(np.sum((y - side["uv"].astype(np.float64)) ** 2))
        split = max(float(np.abs(s_kept - s_ref[:keep]).max() / s_ref[0]),
                    abs(resid - disc) / float(np.sum(y ** 2)))
    return {
        "matvec_rel": float(np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)),
        "split_rel": split,
        "energy_rel": abs(e_side - e_ref) / abs(e_ref),
        "ground_rel": abs(e_side - st["ground"]) / abs(st["ground"]),
        "gauge": R.gauge_error(side["mps"], p),
    }


def check(st) -> dict:
    return compare(st, program_side(st))


def control(st) -> dict:
    return compare(st, control_side(st, program_side(st)))
