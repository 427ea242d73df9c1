"""The Davidson solver's two vector algebras (``core/davidson.py``): the
fused programs run where the operands are bucket-padded and make the same
decisions, reads and results as the eager per-block algebra; a second
sweep builds no program; the health guard and the no-convergence fault hold
on the fused path; and the seed rung, bare contractors and unpadded engines
stay eager."""
import contextlib
import math

import jax
import numpy as np
import pytest
from jax._src.lib import _profiler

from repro import obs
from repro.core.davidson import davidson
from repro.core.models import heisenberg_j1j2_terms
from repro.core.mpo import build_mpo, compress_mpo
from repro.core.mps import neel_states, product_state_mps
from repro.core.siteops import spin_half_space
from repro.core.sweep import DMRGEngine
from repro.dist import faults, pad_block_sparse
from repro.dist.faults import NumericalHealthError
from repro.tensor import BlockSparseTensor

N_SITES, MAX_BOND, ITERS = 6, 8, 3  # the 2x3 cylinder at its exact bond
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _mpo():
    terms = heisenberg_j1j2_terms(2, 3, 1.0, 0.5, cylinder=True)
    return compress_mpo(build_mpo(spin_half_space(), terms, N_SITES),
                        cutoff=1e-13)


def _engine(**kw):
    sp = spin_half_space()
    kw = {"algo": "batched", "jit_matvec": True, **kw}
    return DMRGEngine(product_state_mps(sp, neel_states(sp, N_SITES)), _mpo(),
                      davidson_iters=ITERS, **kw)


@pytest.fixture(scope="module")
def engine():
    """The production flags of ``run_dmrg`` on the 2x3 J1-J2 cylinder,
    swept until the bond reaches its exact structure."""
    eng = _engine()
    for _ in range(3):
        eng.sweep(max_bond=MAX_BOND)
    return eng


@contextlib.contextmanager
def recording():
    """The spans of ``repro.obs`` recorded inside the block."""
    out = []
    obs.reset()
    session = _profiler.ProfilerSession(jax.profiler.ProfileOptions())
    try:
        yield out
    finally:
        session.stop_and_get_profile_data()
        out.extend(obs.records())
        obs.reset()


def _count(records, name):
    return sum(1 for r in records if r[0] == name)


def _problem(eng, j, state):
    """The padded theta and jitted matvec of pair (j, j+1), as the sweep
    builds them; ``random`` replaces theta's values by seeded noise (a
    solve far from converged), ``neel`` is the pair before any sweep (a
    matvec that grows the block structure)."""
    ce, T = eng.contract_fn, eng.mps.tensors
    theta = ce(T[j], T[j + 1], ((2,), (0,)))
    if state == "random":
        keys = jax.random.split(jax.random.PRNGKey(j), len(theta.blocks))
        theta = BlockSparseTensor(theta.indices, {
            k: jax.random.normal(kk, b.shape, b.dtype)
            for kk, (k, b) in zip(keys, theta.blocks.items())}, theta.charge)
    mv = ce.matvec_fn(pad_block_sparse(eng.left_envs[j]), eng._padded_mpo(j),
                      eng._padded_mpo(j + 1),
                      pad_block_sparse(eng.right_envs[j + 1]), jit=True)
    return pad_block_sparse(theta), mv


# before any sweep only the first pair has its left environment
CASES = [(state, j) for state in ("converged", "random")
         for j in range(N_SITES - 1)] + [("neel", 0)]


@pytest.fixture(scope="module")
def solves(engine):
    """Each case solved eagerly and fused, with the spans of each solve."""
    neel = _engine()
    out = {}
    for state, j in CASES:
        x0, mv = _problem(neel if state == "neel" else engine, j, state)
        for fused in (False, True):
            davidson(mv, x0, n_iter=ITERS, seed=j, fused=fused)  # warm
            with recording() as recs:
                lam, x, info = davidson(mv, x0, n_iter=ITERS, seed=j,
                                        fused=fused)
            out[state, j, fused] = {"lam": lam, "x": np.asarray(x.to_dense()),
                                    "info": info, "records": recs}
    return out


@pytest.mark.parametrize("state,j", CASES)
def test_fused_and_eager_give_the_same_eigenpair(solves, state, j):
    eager, fused = solves[state, j, False], solves[state, j, True]
    assert abs(fused["lam"] - eager["lam"]) <= 1e-12
    np.testing.assert_allclose(fused["x"], eager["x"], rtol=0, atol=1e-10)


def test_fused_and_eager_make_the_same_decisions_and_reads(solves):
    iterations = set()
    for state, j in CASES:
        eager, fused = solves[state, j, False], solves[state, j, True]
        assert fused["info"] == eager["info"], (state, j)
        assert (_count(fused["records"], "davidson.read")
                == _count(eager["records"], "davidson.read")), (state, j)
        iterations.add(fused["info"].iterations)
    # the cases cover a solve that stops early and one that runs the budget
    assert min(iterations) < ITERS == max(iterations)


def test_fused_spans_nest_under_the_solve(solves):
    for state, j in CASES:
        assert _count(solves[state, j, False]["records"], "davidson.fused") == 0
        recs = solves[state, j, True]["records"]
        fused = [r for r in recs if r[0] == "davidson.fused"]
        assert fused, (state, j)
        assert all(recs[r[1]][0] == "davidson.solve" for r in fused)
    # a matvec that grows the block structure hands the solve to the eager
    # algebra after the start: one fused program, whatever the iterations
    assert _count(solves["neel", 0, True]["records"], "davidson.fused") == 1


def test_a_second_sweep_builds_no_program(engine):
    built = []

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE:
            built.append(kw.get("fun_name"))

    engine.sweep(max_bond=MAX_BOND)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with recording() as recs:
            engine.sweep(max_bond=MAX_BOND)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert built == []
    assert _count(recs, "davidson.fused") > 0


def test_nan_poisoned_matvec_raises_on_the_fused_path(engine):
    x0, mv = _problem(engine, 2, "random")

    def poisoned(x):
        return jax.tree_util.tree_map(lambda b: b * math.nan, mv(x))

    with recording() as recs:
        with pytest.raises(NumericalHealthError) as err:
            davidson(poisoned, x0, n_iter=ITERS, fused=True)
    assert err.value.stage == "davidson"
    assert _count(recs, "davidson.fused") > 0


def test_no_converge_fault_runs_the_full_budget_fused(engine):
    x0, mv = _problem(engine, 0, "converged")
    for _ in range(10):  # restart until a solve converges early
        _, x0, clean = davidson(mv, x0, n_iter=ITERS, fused=True)
        if clean.converged:
            break
    assert clean.converged and clean.iterations < ITERS
    faults.registry.clear()
    try:
        with faults.inject("davidson.no_converge", count=1) as f:
            _, _, info = davidson(mv, x0, n_iter=ITERS, fused=True)
    finally:
        faults.registry.clear()
    assert f.fired
    assert info.iterations == ITERS and not info.converged


@pytest.mark.parametrize("path", ["seed_rung", "bare_contractor", "unpadded"])
def test_other_paths_keep_the_eager_algebra(path):
    if path == "bare_contractor":
        eng = _engine(algo="list_unplanned", jit_matvec=False)
    elif path == "unpadded":
        eng = _engine(jit_matvec=False)
    else:
        eng = _engine()
    update = eng._optimize_pair_seed if path == "seed_rung" else eng._optimize_pair
    with recording() as recs:
        update(0, MAX_BOND, 1e-12, "right")
    assert _count(recs, "davidson.solve") > 0
    assert _count(recs, "davidson.fused") == 0
