"""Fault injection, health guards, degradation ladder, checkpoint/resume,
and the serving layer's isolation/bisection/watchdog recovery (DESIGN.md 3.8).

The contract under test: an injected failure anywhere in the pipeline is
(a) detected at an existing host-sync point, (b) recovered on a documented
ladder whose bottom rung is the seed algorithms, and (c) invisible in the
final physics — recovered energies match a clean run to <1e-10 (the seed-
equality guarantee), and in a serving batch only the poisoned request
fails while its slot-mates return clean-run energies.
"""
import math
import os
import pickle

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import run_dmrg
from repro.core.checkpoint import CheckpointManager
from repro.core.models import heisenberg_chain_system
from repro.core.mpo import build_mpo, compress_mpo
from repro.core.mps import neel_states, product_state_mps
from repro.core.siteops import spin_half_space
from repro.core.sweep import DMRGEngine
from repro.dist import faults
from repro.dist.engine import ContractionEngine
from repro.dist.faults import FaultInjected, FaultRegistry, NumericalHealthError
from repro.serve import DMRGService, ProblemSpec, StackedOps
from repro.serve.problems import build_problem


@pytest.fixture(autouse=True)
def _clean_registry():
    """No fault leaks between tests: every test starts and ends disarmed."""
    faults.registry.clear()
    yield
    faults.registry.clear()


N = 6  # chain length for the single-problem recovery tests


def _engine(algo="batched", **kw):
    space, terms = heisenberg_chain_system(N, h=0.3)
    mpo = compress_mpo(build_mpo(space, terms, N), cutoff=1e-13)
    mps = product_state_mps(space, neel_states(space, N))
    return DMRGEngine(mps, mpo, algo=algo, davidson_iters=4, **kw)


def _two_sweeps(eng, m=8):
    eng.sweep(max_bond=m)
    return eng.sweep(max_bond=m)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_unknown_point_raises(self):
        reg = FaultRegistry()
        with pytest.raises(KeyError, match="unknown fault point"):
            reg.arm("decomp.typo_fail")

    def test_after_count_window(self):
        reg = FaultRegistry()
        f = reg.arm("decomp.svd_fail", after=2, count=2)
        hits = [reg.fire("decomp.svd_fail") is not None for _ in range(6)]
        assert hits == [False, False, True, True, False, False]
        assert f.seen == 6 and f.fired == 2

    def test_count_inf_fires_forever(self):
        reg = FaultRegistry()
        reg.arm("batch.gemm_nan", count=math.inf)
        assert all(reg.fire("batch.gemm_nan") is not None for _ in range(50))

    def test_inject_context_disarms(self):
        with faults.inject("env.exception") as f:
            assert faults.fire("env.exception") is not None
            assert f.fired == 1
        assert faults.fire("env.exception") is None

    def test_arm_from_env_grammar(self):
        reg = FaultRegistry()
        reg.arm_from_env(
            "decomp.svd_fail:count=inf:after=1, serve.slot_latency:value=0.25"
        )
        assert reg.fire("decomp.svd_fail") is None  # after=1 skips first
        assert reg.fire("decomp.svd_fail").count == math.inf
        assert reg.fire("serve.slot_latency").value == 0.25
        with pytest.raises(ValueError, match="bad REPRO_FAULTS knob"):
            reg.arm_from_env("decomp.svd_fail:boom=1")
        with pytest.raises(KeyError):
            reg.arm_from_env("no.such_point")

    def test_stats_reports_armed_and_fired(self):
        reg = FaultRegistry()
        reg.arm("sweep.kill")
        reg.fire("sweep.kill")
        s = reg.stats()
        assert s["armed"] == ["sweep.kill"]
        assert s["fired"] == {"sweep.kill": 1}


# ------------------------------------------------- guards + degradation ladder
class TestDegradationLadder:
    @pytest.mark.parametrize("stage", ["env", "decomp"])
    def test_non_fault_error_propagates(self, stage, monkeypatch):
        """The ladders catch only injected faults and health errors: any
        other failure — a compile or lowering error on the chip — reaches
        the caller instead of a seed-path run that looks healthy."""
        from repro.dist.decomp import DecompositionEngine
        from repro.dist.envcore import EnvironmentEngine

        def refuse(*a, **k):
            raise NotImplementedError("compiler refused the core")

        target = (EnvironmentEngine, "_update") if stage == "env" else (
            DecompositionEngine, "_execute_planned")
        eng = _engine()
        monkeypatch.setattr(*target, refuse)
        with pytest.raises(NotImplementedError, match="compiler refused"):
            _two_sweeps(eng)
        st_ = eng.contract_fn.stats()
        assert not any(st_["degradations"].values())
        assert not any(st_["decomp"]["degradations"].values())

    def test_clean_run_zero_counters(self):
        eng = _engine(algo="batched", jit_matvec=True)
        stats = _two_sweeps(eng)
        st_ = eng.contract_fn.stats()
        assert not any(st_["retries"].values())
        assert not any(st_["degradations"].values())
        assert st_["decomp"]["retries"] == 0
        assert not any(st_["decomp"]["degradations"].values())
        assert stats.pair_retries == 0

    @pytest.mark.x64
    def test_decomp_svd_fail_recovers_equal(self):
        ref = _two_sweeps(_engine())
        eng = _engine()
        with faults.inject("decomp.svd_fail", count=1) as f:
            got = _two_sweeps(eng)
        assert f.fired == 1
        assert abs(got.energy - ref.energy) < 1e-10
        d = eng.contract_fn.stats()["decomp"]
        assert d["retries"] >= 1
        assert sum(d["degradations"].values()) >= 1

    @pytest.mark.x64
    def test_env_exception_falls_back_to_seed_equal(self):
        ref = _two_sweeps(_engine())
        eng = _engine()
        with faults.inject("env.exception", count=2) as f:
            got = _two_sweeps(eng)
        assert f.fired == 2
        assert abs(got.energy - ref.energy) < 1e-10
        st_ = eng.contract_fn.stats()
        assert st_["retries"].get("env", 0) >= 2
        assert st_["degradations"].get("env_seed", 0) >= 2

    @pytest.mark.x64
    def test_gemm_nan_pair_retries_on_seed_rung_equal(self):
        """A NaN-poisoned batched GEMM surfaces at the Davidson host sync as
        a NumericalHealthError; the pair re-runs on the seed rung and the
        final energy still matches a clean run."""
        ref = _two_sweeps(_engine(algo="batched", jit_matvec=False))
        eng = _engine(algo="batched", jit_matvec=False)
        with faults.inject("batch.gemm_nan", count=1) as f:
            got = _two_sweeps(eng)
        assert f.fired == 1
        assert abs(got.energy - ref.energy) < 1e-10
        assert got.pair_retries + eng.contract_fn.retries.get("pair", 0) >= 1
        assert eng.contract_fn.degradations.get("pair_seed", 0) >= 1

    def test_davidson_health_surfaced_in_sweep_stats(self):
        clean = _two_sweeps(_engine())  # per-sweep stats: 2 passes x (N-1)
        assert clean.davidson_solves == 2 * (N - 1)
        assert clean.davidson_iterations >= clean.davidson_solves
        eng = _engine()
        with faults.inject("davidson.no_converge", count=math.inf):
            forced = _two_sweeps(eng)
        assert forced.davidson_converged == 0
        assert forced.davidson_solves == clean.davidson_solves

    def test_health_error_carries_stage_and_mask(self):
        e = NumericalHealthError("bad", stage="svd",
                                 problems=np.array([False, True]))
        assert e.stage == "svd"
        assert list(e.problems) == [False, True]
        assert isinstance(e, RuntimeError)


# ------------------------------------------------------- checkpoint/resume
class TestCheckpoint:
    def _state(self, step):
        return {"step": step, "payload": list(range(step))}

    def test_roundtrip_and_prune(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), every=1, keep=2)
        for s in range(1, 6):
            cm.save(self._state(s))
        files = sorted(os.listdir(tmp_path))
        assert files == ["ckpt_00000004.pkl", "ckpt_00000005.pkl"]
        assert cm.load_latest()["step"] == 5

    def test_maybe_save_cadence(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), every=3, keep=10)
        saved = [cm.maybe_save(self._state(s)) for s in range(1, 7)]
        assert [bool(p) for p in saved] == [False, False, True,
                                            False, False, True]

    def test_truncated_newest_degrades_to_previous(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), every=1, keep=2)
        cm.save(self._state(1))
        cm.save(self._state(2))
        newest = os.path.join(tmp_path, "ckpt_00000002.pkl")
        with open(newest, "wb") as f:
            f.write(b"\x80\x04garbage")  # crash mid-write stand-in
        assert cm.load_latest()["step"] == 1

    def test_version_mismatch_skipped(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), every=1, keep=2)
        cm.save(self._state(1))
        bad = {"step": 2, "version": 999}
        with open(os.path.join(tmp_path, "ckpt_00000002.pkl"), "wb") as f:
            pickle.dump(bad, f)
        assert cm.load_latest()["step"] == 1

    @pytest.mark.x64
    def test_kill_mid_sweep_resume_equal(self, tmp_path):
        """Kill the run after the 4th site update of the schedule; a rerun
        with the same checkpoint dir resumes MID-SWEEP and its energies
        match the uninterrupted run to <1e-10 (bit-identical in practice)."""
        space, terms = heisenberg_chain_system(N, h=0.3)
        kw = dict(bond_schedule=(8, 12), sweeps_per_bond=1,
                  davidson_iters=4, algo="batched")
        ref = run_dmrg(space, terms, N, **kw)
        ckdir = str(tmp_path / "ck")
        with faults.inject("sweep.kill", after=3, count=1) as f:
            with pytest.raises(FaultInjected):
                run_dmrg(space, terms, N, checkpoint_dir=ckdir, **kw)
        assert f.fired == 1
        res = run_dmrg(space, terms, N, checkpoint_dir=ckdir, **kw)
        assert abs(res.energy - ref.energy) < 1e-10
        for a, b in zip(res.sweep_stats, ref.sweep_stats):
            assert abs(a.energy - b.energy) < 1e-10


# ------------------------------------------------------------ serving layer
SPECS = [
    ProblemSpec.make("heisenberg", 6, J=1.0 + 0.05 * i, max_bond=8,
                     sweeps_per_bond=1, davidson_iters=4)
    for i in range(4)
]


_OPS = None
_CLEAN = None


def _get_ops():
    """One StackedOps across the serving tests: compile the pipeline once.

    A lazy module global rather than a fixture because the hypothesis test
    below cannot take fixtures (the deterministic stub in
    ``_hypothesis_stub.py`` hides the wrapped signature from pytest)."""
    global _OPS
    if _OPS is None:
        _OPS = StackedOps()
    return _OPS


def _manual_service(ops, **kw):
    """Service with no worker thread: tests drive slots deterministically."""
    return DMRGService(max_batch=4, start=False, ops=ops, **kw)


def _drain_one_slot(svc):
    """What one worker iteration does: cut a slot, mark running, solve."""
    with svc._cv:
        slot = svc.scheduler.next_batch()
        assert slot is not None
        for rid in slot.rids:
            svc._requests[rid]["status"] = "running"
    svc._run_slot(slot)
    return slot


def _get_clean_energies():
    """Reference energies: each spec solved alone through the same ops."""
    global _CLEAN
    if _CLEAN is None:
        svc = _manual_service(_get_ops())
        out = {}
        for spec in SPECS:
            rid = svc.submit(spec)
            _drain_one_slot(svc)
            out[spec] = svc.result(rid, timeout=5.0)["energy"]
        svc.shutdown()
        _CLEAN = out
    return _CLEAN


class TestServeRecovery:
    @pytest.mark.x64
    @given(target=st.integers(0, 3))
    @settings(max_examples=3, deadline=None)
    def test_poisoned_request_isolated(self, target):
        """One NaN-poisoned request in a slot of 4 fails EXACTLY itself;
        the other three return energies matching their clean solo runs to
        <1e-10 (phantom-slot exactness: batch composition never changes
        per-problem numerics)."""
        clean_energies = _get_clean_energies()
        faults.registry.clear()  # hypothesis re-enters past the fixture
        svc = _manual_service(_get_ops(), max_retries=0)
        rids = [svc.submit(s) for s in SPECS]
        # count=inf + rid targeting: the poison follows the request through
        # every isolation retry, like persistently corrupt upstream input
        faults.registry.arm("serve.poison_request", count=math.inf,
                            problem=rids[target])
        _drain_one_slot(svc)
        faults.registry.clear()
        for i, (rid, spec) in enumerate(zip(rids, SPECS)):
            if i == target:
                with pytest.raises(RuntimeError, match="failed"):
                    svc.result(rid, timeout=5.0)
            else:
                rec = svc.result(rid, timeout=5.0)
                assert abs(rec["energy"] - clean_energies[spec]) < 1e-10
        st_ = svc.stats()
        assert st_["failed"] == 1 and st_["completed"] == 3
        svc.shutdown()

    @pytest.mark.x64
    def test_unmasked_failure_bisects(self):
        """A whole-slot failure with no mask (stand-in: LAPACK SVD dying)
        bisects; the halves rerun clean once the transient fault is gone.

        x64-marked not for tolerances but for a precondition: under f32 the
        MPO compression of the two J values yields different block
        structures, so the specs land in different batch groups and no
        multi-request slot (nothing to bisect) ever forms."""
        svc = _manual_service(_get_ops())
        rids = [svc.submit(s) for s in SPECS[:2]]
        with faults.inject("decomp.svd_fail", count=1) as f:
            _drain_one_slot(svc)
        assert f.fired == 1
        for rid in rids:
            assert svc.result(rid, timeout=5.0)["status"] == "done"
        st_ = svc.stats()
        assert st_["bisections"] == 1
        assert st_["failed"] == 0
        assert st_["davidson"]["solves"] > 0  # health surfaced in stats JSON
        svc.shutdown()

    def test_single_request_retry_budget_exhausts(self):
        svc = _manual_service(_get_ops(), max_retries=1)
        rid = svc.submit(SPECS[0])
        with faults.inject("decomp.svd_fail", count=math.inf):
            _drain_one_slot(svc)
        with pytest.raises(RuntimeError, match="failed"):
            svc.result(rid, timeout=5.0)
        st_ = svc.stats()
        assert st_["retries"] == 2  # initial charge + one budgeted re-run
        assert st_["failed"] == 1
        svc.shutdown()

    def test_worker_crash_restarts_and_recovers(self):
        svc = DMRGService(max_batch=4, ops=_get_ops(), batch_wait_s=0.01)
        faults.registry.arm("serve.worker_crash", count=1)
        rid = svc.submit(SPECS[0])
        rec = svc.result(rid, timeout=120.0)
        assert rec["status"] == "done"
        assert svc.stats()["worker_restarts"] == 1
        svc.shutdown()

    def test_cancel_pending_request(self):
        svc = _manual_service(_get_ops())
        r0 = svc.submit(SPECS[0])
        r1 = svc.submit(SPECS[1])
        assert svc.cancel(r0) is True
        assert svc.cancel(r0) is False  # already cancelled
        assert svc.poll(r0)["status"] == "cancelled"
        with pytest.raises(RuntimeError, match="cancelled"):
            svc.result(r0, timeout=1.0)
        _drain_one_slot(svc)  # r1 alone; r0 must not be solved
        assert svc.result(r1, timeout=5.0)["status"] == "done"
        st_ = svc.stats()
        assert st_["cancelled"] == 1 and st_["completed"] == 1
        svc.shutdown()

    def test_result_evicts_into_bounded_tombstones(self):
        """The delivered-result leak is fixed: result() evicts the live
        record; late poll() answers from a bounded tombstone map."""
        svc = _manual_service(_get_ops(), max_tombstones=2)
        rids = [svc.submit(s) for s in SPECS[:3]]
        while len(svc.scheduler):
            _drain_one_slot(svc)
        for rid in rids:
            svc.result(rid, timeout=5.0)
        assert svc._requests == {}  # nothing retained after delivery
        assert svc.poll(rids[-1])["status"] == "done"  # tombstone answers
        with pytest.raises(KeyError):  # oldest pushed out of the bound
            svc.poll(rids[0])
        svc.shutdown()

    def test_journal_recovery_reenqueues(self, tmp_path):
        ckdir = str(tmp_path)
        svc1 = _manual_service(_get_ops(), checkpoint_dir=ckdir)
        rids = [svc1.submit(s) for s in SPECS[:2]]
        assert os.path.exists(os.path.join(ckdir, "serve_journal.json"))
        # no shutdown: simulate the process dying with work undelivered
        svc2 = _manual_service(_get_ops(), checkpoint_dir=ckdir)
        assert len(svc2.scheduler) == 2
        for rid in rids:
            assert svc2.poll(rid)["status"] == "pending"
        assert svc2.submit(SPECS[2]) == max(rids) + 1  # rid counter resumes
        svc2.shutdown()
        svc1.shutdown()

    def test_slot_latency_fault_delays_solve(self):
        import time as _time

        svc = _manual_service(_get_ops())
        rid = svc.submit(SPECS[0])
        with faults.inject("serve.slot_latency", value=0.2):
            t0 = _time.perf_counter()
            _drain_one_slot(svc)
            dt = _time.perf_counter() - t0
        assert dt >= 0.2
        assert svc.result(rid, timeout=5.0)["status"] == "done"
        svc.shutdown()
