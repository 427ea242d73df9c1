"""The program's spans (``repro.obs``): nothing recorded and nothing
allocated without a profiler session; under one, one ``sweep.pair`` per pair
update with its stages nested inside, the same spans in the profile, every
blocking host read of a pair update inside a ``*.read`` span; and the
jitted cores under stable names."""
import importlib
import statistics
import sys
import tracemalloc

import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl
from jax._src.lib import _profiler

from repro import obs
from repro.core.models import heisenberg_j1j2_terms
from repro.core.mpo import build_mpo, compress_mpo
from repro.core.mps import neel_states, product_state_mps
from repro.core.siteops import spin_half_space
from repro.core.sweep import DMRGEngine
from repro.dist import pad_block_sparse
from repro.dist.decomp import _host_lapack_svd

# the module: ``repro.core.davidson`` names the function re-exported there
dav = importlib.import_module("repro.core.davidson")
N_SITES, MAX_BOND = 6, 8  # the 2x3 cylinder at its exact bond
# the spans directly under ``sweep.pair`` on the production path
STAGES = ("sweep.theta", "sweep.pad", "sweep.operator", "davidson.solve",
          "sweep.unpad", "split", "sweep.place", "env.update")
# the ways a jax.Array reaches the host: numpy takes the buffer protocol
# where the array lives in host memory and ``__array__`` elsewhere
CONVERSIONS = ("__buffer__", "__array__", "__float__", "__int__", "__bool__",
               "__index__", "__complex__", "tolist")


@pytest.fixture(scope="module")
def engine():
    """The production flags of ``run_dmrg`` on the 2x3 J1-J2 cylinder,
    swept until the bond reaches its exact structure."""
    sp = spin_half_space()
    terms = heisenberg_j1j2_terms(2, 3, 1.0, 0.5, cylinder=True)
    mpo = compress_mpo(build_mpo(sp, terms, N_SITES), cutoff=1e-13)
    eng = DMRGEngine(product_state_mps(sp, neel_states(sp, N_SITES)), mpo,
                     algo="batched", jit_matvec=True, davidson_iters=3)
    for _ in range(3):
        eng.sweep(max_bond=MAX_BOND)
    return eng


def _open_spans():
    return list(getattr(obs._open, "stack", []))


def _in_host_callback():
    """Inside the float64 split's LAPACK callback: XLA:CPU runs it on the
    calling thread, a TPU on a thread of its own; its conversions are the
    callback's input, not a read of the sweep's thread."""
    f = sys._getframe(2)
    while f is not None:
        if f.f_code is _host_lapack_svd.__code__:
            return True
        f = f.f_back
    return False


@pytest.fixture(scope="module")
def traced(engine):
    """One sweep under an in-memory profiler session: its stats, the span
    records, the profile's host events, and every host conversion of a
    jax.Array with the record indices of the spans open around it."""
    conversions = []
    real = {name: ArrayImpl.__dict__[name] for name in CONVERSIONS}

    def watch(name):
        def convert(self, *a, **kw):
            if not _in_host_callback():
                conversions.append((name, _open_spans()))
            return real[name](self, *a, **kw)
        return convert

    obs.reset()
    # no forced thread switch between a span's clock read and its
    # annotation's (the test runner's own threads would otherwise take the
    # interpreter lock there)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    session = _profiler.ProfilerSession(jax.profiler.ProfileOptions())
    try:
        for name in CONVERSIONS:
            setattr(ArrayImpl, name, watch(name))
        stats = engine.sweep(max_bond=MAX_BOND)
    finally:
        for name, fn in real.items():
            setattr(ArrayImpl, name, fn)
        profile = session.stop_and_get_profile_data()
        sys.setswitchinterval(interval)
    records = obs.records()
    obs.reset()
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines if line.name == "python"
            for e in line.events]
    return {"stats": stats, "records": records, "host": host,
            "conversions": conversions}


def test_no_session_records_and_allocates_nothing(engine):
    assert not _profiler.TraceMe.is_enabled()
    obs.reset()
    engine.sweep(max_bond=MAX_BOND)
    assert obs.records() == []
    assert obs.span("a") is obs.span("b")

    def spans(k):
        for _ in range(k):
            with obs.span("sweep.pair"):
                with obs.span("davidson.read"):
                    pass

    spans(10)  # warm: first calls may allocate interpreter caches
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        spans(2000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only_obs = [tracemalloc.Filter(True, obs.__file__)]
    grown = [d for d in after.filter_traces(only_obs).compare_to(
        before.filter_traces(only_obs), "lineno") if d.size_diff > 0]
    assert grown == []
    assert obs.records() == []


def test_one_pair_span_per_update_with_its_stages(traced):
    roots = obs.per_root("sweep.pair", traced["records"])
    assert len(roots) == 2 * (N_SITES - 1) == len(traced["stats"].site_energies)
    recs = traced["records"]
    for i, (name, parent, start, end) in enumerate(recs):
        assert end is not None and end >= start
        if parent is None:
            assert name == "sweep.pair"
        else:
            p_name, _, p_start, p_end = recs[parent]
            assert p_start <= start and end <= p_end
            if p_name == "sweep.pair":
                assert name in STAGES
            if name == "davidson.fused":
                assert p_name == "davidson.solve"
    for r in roots:
        assert {k: v[1] for k, v in r["spans"].items() if k in STAGES} == \
            dict.fromkeys(STAGES, 1)
        # the padded operands run the subspace algebra as fused programs
        assert r["spans"]["davidson.fused"][1] >= 1


def test_stage_spans_cover_the_pair_update(traced):
    recs = traced["records"]
    below = {}
    for name, parent, start, end in recs:
        if parent is not None and recs[parent][0] == "sweep.pair":
            below[parent] = below.get(parent, 0) + end - start
    shares = [below.get(i, 0) / (end - start)
              for i, (name, _, start, end) in enumerate(recs)
              if name == "sweep.pair"]
    assert statistics.median(shares) >= 0.90, shares


def test_every_span_is_in_the_profile(traced):
    events = {}
    for name, start, end in sorted(traced["host"], key=lambda e: e[1]):
        events.setdefault(name, []).append(end - start)
    spans = {}
    for name, _, start, end in traced["records"]:
        spans.setdefault(name, []).append(end - start)
    for name, durations in spans.items():
        assert len(events.get(name, [])) == len(durations), name
        for got, want in zip(events[name], durations):
            assert abs(got - want) < 1e6, (name, got, want)


def test_every_host_read_of_a_pair_update_is_a_read_span(traced):
    recs = traced["records"]
    in_pair = [(how, [recs[i][0] for i in stack])
               for how, stack in traced["conversions"]
               if any(recs[i][0] == "sweep.pair" for i in stack)]
    assert in_pair
    for how, names in in_pair:
        assert names[-1].endswith(".read"), (how, names)
    # and no read span without a read in it
    holding = {stack[-1] for _, stack in traced["conversions"] if stack}
    reads = {i for i, (name, *_) in enumerate(recs) if name.endswith(".read")}
    assert reads <= holding
    assert {recs[i][0] for i in reads} == {"davidson.read", "split.read"}


def test_davidson_reads_match_the_iterations(traced):
    """Per solve of k iterations: k column reads, k - 1 Gram-Schmidt norm
    reads, one more per restart and per exhaustion, and the exact residual
    norm read where the Gram estimate falls below its noise floor: on every
    converged iteration, and at most once per iteration."""
    s = traced["stats"]
    reads = sum(1 for name, *_ in traced["records"] if name == "davidson.read")
    fixed = (2 * s.davidson_iterations - s.davidson_solves
             + s.davidson_restarts + s.davidson_exhausted)
    assert fixed + s.davidson_converged <= reads <= fixed + s.davidson_iterations
    split_reads = sum(1 for name, *_ in traced["records"] if name == "split.read")
    assert split_reads == s.davidson_solves


def test_jitted_cores_have_stable_names(engine):
    eng, ce = engine, engine.contract_fn
    T = eng.mps.tensors
    theta = ce(T[0], T[1], ((2,), (0,)))
    A, B = pad_block_sparse(eng.left_envs[0]), pad_block_sparse(eng.right_envs[1])
    Wj, Wj1 = eng._padded_mpo(0), eng._padded_mpo(1)
    ce.matvec_fn(A, Wj, Wj1, B, jit=True)
    mats = ce._fixed_operand_mats(A, Wj, Wj1, B)
    lowered = {"matvec": ce._jit_mv.lower(A, Wj, Wj1, B, mats,
                                          pad_block_sparse(theta))}

    ce.svd_split(theta, 2, max_bond=MAX_BOND)
    plan = ce.decomp.cache.get(theta, 2)
    blocks_in = tuple(theta.blocks[k] for k in plan.block_order)
    for key, core in plan._exec.items():
        if key[0] == "slice":
            svd = plan._exec[key[1]]
            lowered["slice"] = core.lower(svd(blocks_in)[0])
        else:
            lowered["svd"] = core.lower(blocks_in)

    env, site = pad_block_sparse(eng.left_envs[0]), pad_block_sparse(T[0])
    W = eng._padded_mpo(0)
    eplan = ce.env.cache.get(env, site, W, "left")
    lowered["env"] = eplan._exec[True].lower(
        tuple(env.blocks[k] for k in eplan.env_keys),
        tuple(site.blocks[k] for k in eplan.site_keys),
        tuple(W.blocks[k] for k in eplan.mpo_keys))

    x = pad_block_sparse(theta)
    _, V, AV = dav._start(x, 3)
    lowered["davidson_start"] = dav._start.lower(x, 3)
    lowered["davidson_columns"] = dav._columns.lower(V, AV, x, 0)
    lowered["davidson_ritz"] = dav._ritz.lower(V, AV, np.ones(3), -1.0)
    lowered["davidson_orthogonalize"] = dav._orthogonalize.lower(V, x, 0)

    for stage, name in (("matvec", "matvec_core"), ("svd", "svd_core"),
                        ("slice", "slice_core"), ("env", "env_core"),
                        *((n, n) for n in ("davidson_start", "davidson_columns",
                                           "davidson_ritz",
                                           "davidson_orthogonalize"))):
        assert f"module @jit_{name} " in lowered[stage].as_text(), stage
