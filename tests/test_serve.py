"""Serving subsystem: batched multi-problem exactness vs independent single
runs (energies and singular values), scheduler grouping / power-of-two slot
padding, plan-cache thread-safety, queue backpressure, and the end-to-end
service worker (subprocess: XLA compilation with a live secondary thread is
fragile late in a big shared process on this jaxlib)."""
import os
import subprocess
import sys
import textwrap
import threading

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import run_dmrg
from repro.dist import DecompositionEngine, cache_stats
from repro.dist.plan import _SignatureLRU
from repro.serve import (
    DEVICE_LOCK,
    BatchScheduler,
    DMRGService,
    ProblemSpec,
    ServeQueueFull,
    build_problem,
    group_key,
    run_dmrg_multi,
    svd_split_multi,
)
from repro.serve.stacked import stack_tensors

from test_decomp import rand_theta


def _solve_single(spec, mpo, space):
    """Reference: one independent run over the same prebuilt operator.

    Holds DEVICE_LOCK so a live service worker never compiles concurrently
    with this run (jaxlib < 0.5 segfaults on concurrent XLA compilation).
    """
    with DEVICE_LOCK:
        return run_dmrg(
            space,
            None,
            spec.n_sites,
            bond_schedule=spec.bond_schedule,
            sweeps_per_bond=spec.sweeps_per_bond,
            davidson_iters=spec.davidson_iters,
            cutoff=spec.cutoff,
            mpo=mpo,
            algo="batched",
            jit_matvec=True,
        )


@pytest.mark.x64
class TestMultiProblemCore:
    @settings(max_examples=2, deadline=None)
    @given(
        j0=st.floats(min_value=0.6, max_value=1.4),
        h0=st.floats(min_value=0.1, max_value=0.5),
    )
    def test_batch_matches_independent_singles(self, j0, h0):
        """Property: a batch of B problems with varied (J, h) reproduces B
        independent single-problem runs to 1e-10."""
        pairs = [(j0, h0), (0.9 * j0, h0 + 0.15), (1.1 * j0, h0 + 0.3)]
        specs = [
            ProblemSpec.make(
                "heisenberg", 6, J=j, h=h, max_bond=8, davidson_iters=5
            )
            for j, h in pairs
        ]
        built = [build_problem(s) for s in specs]
        space = built[0][0]
        mpos = [m for _, m in built]
        res = run_dmrg_multi(
            space,
            6,
            mpos,
            bond_schedule=specs[0].bond_schedule,
            sweeps_per_bond=2,
            davidson_iters=5,
        )
        for b, spec in enumerate(specs):
            ref = _solve_single(spec, mpos[b], space)
            assert abs(float(res.energies[b]) - ref.energy) < 1e-10

    def test_structure_mismatch_rejected(self):
        """Problems whose MPOs differ in block structure cannot share a batch
        axis — run_dmrg_multi must refuse rather than compute garbage."""
        s_chain = ProblemSpec.make("heisenberg", 6, J=1.0, h=0.3)
        s_ladder = ProblemSpec.make("j1j2_ladder", 6, J1=1.0, J2=0.5)
        space, mpo_a = build_problem(s_chain)
        _, mpo_b = build_problem(s_ladder)
        with pytest.raises(ValueError, match="structure"):
            run_dmrg_multi(space, 6, [mpo_a, mpo_b], bond_schedule=(8,))

    def test_stacked_svals_match_per_problem_svd(self):
        """svd_split_multi singular values equal per-problem engine.svd_split
        for every problem and sector; phantom slots are exact zeros."""
        base = rand_theta(7)
        thetas = [
            type(base).random(
                base.indices, key=jax.random.PRNGKey(100 + b), charge=base.charge
            )
            for b in range(3)
        ]
        stacked = stack_tensors(thetas)
        _, _, svals_multi, errs = svd_split_multi(
            stacked, 2, max_bond=6, cutoff=1e-12
        )
        engine = DecompositionEngine()
        for b, theta in enumerate(thetas):
            _, _, svals_one, err_one = engine.svd_split(
                theta, 2, max_bond=6, cutoff=1e-12
            )
            assert abs(float(errs[b]) - err_one) < 1e-10
            for q, col in svals_multi.items():
                ref = np.asarray(svals_one.get(q, np.zeros(0)))
                got = np.asarray(col[b])
                assert got[: len(ref)] == pytest.approx(ref, abs=1e-10)
                assert np.all(np.abs(got[len(ref):]) < 1e-14)


class TestScheduler:
    def _spec(self, **kw):
        return ProblemSpec.make("heisenberg", kw.pop("n", 6), **kw)

    def test_group_key_ignores_values_catches_structure(self):
        sa = self._spec(J=0.8, h=0.3)
        sb = self._spec(J=1.2, h=0.45)
        # degenerate h=0 keeps the (zero-block) field channel: same structure,
        # same group — the sweep endpoint batches with the rest
        sc = self._spec(J=1.0, h=0.0)
        sd = self._spec(J=1.0, h=0.3, n=8)
        se = ProblemSpec.make("j1j2_ladder", 6, J1=1.0, J2=0.5)
        ka = group_key(sa, build_problem(sa)[1])
        kb = group_key(sb, build_problem(sb)[1])
        kc = group_key(sc, build_problem(sc)[1])
        kd = group_key(sd, build_problem(sd)[1])
        ke = group_key(se, build_problem(se)[1])
        assert ka == kb == kc
        assert ka != kd          # different chain length
        assert ka != ke          # different model -> different MPO structure

    def test_power_of_two_slot_padding(self):
        sched = BatchScheduler(max_batch=8)
        spec = self._spec(J=1.0, h=0.3)
        for rid in range(3):
            sched.add(("g",), rid, spec, "space", f"mpo{rid}")
        slot = sched.next_batch()
        assert slot.rids == [0, 1, 2]
        assert slot.slot_size == 4          # padded 3 -> 4
        assert slot.mpos == ["mpo0", "mpo1", "mpo2", "mpo2"]  # tail duplicate
        assert slot.fill_ratio == pytest.approx(0.75)
        assert len(sched) == 0 and sched.next_batch() is None

    def test_oldest_head_group_served_first(self):
        sched = BatchScheduler(max_batch=2)
        spec = self._spec(J=1.0)
        sched.add(("a",), 0, spec, "sp", "m0")
        sched.add(("b",), 1, spec, "sp", "m1")
        sched.add(("a",), 2, spec, "sp", "m2")
        first = sched.next_batch()
        assert first.key == ("a",) and first.rids == [0, 2]
        second = sched.next_batch()
        assert second.key == ("b",) and second.rids == [1]
        assert second.slot_size == 1


class TestPlanCacheThreadSafety:
    def test_concurrent_get_consistent_stats(self):
        """Hammer one small cache from many threads: every signature must
        resolve to a single shared plan object, and the counters must add up
        (hits + misses == lookups) with evictions actually counted."""
        cache = _SignatureLRU(maxsize=4)
        n_threads, n_iter, n_sigs = 8, 300, 12
        built = []
        build_lock = threading.Lock()
        seen = [dict() for _ in range(n_threads)]

        def worker(tid):
            for i in range(n_iter):
                sig = ("sig", (tid + i) % n_sigs)

                def build():
                    obj = object()
                    with build_lock:
                        built.append(obj)
                    return obj

                plan = cache._get(sig, build)
                seen[tid][sig] = plan

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st_ = cache.stats()
        assert st_["hits"] + st_["misses"] == n_threads * n_iter
        assert st_["misses"] == len(built)
        assert st_["size"] <= 4
        assert st_["evictions"] == st_["misses"] - st_["size"]
        assert st_["evictions"] > 0

    def test_cache_stats_shape(self):
        out = cache_stats()
        assert set(out) == {
            "plan_cache", "decomp_plan_cache", "env_plan_cache", "plan_store",
        }
        for k in ("plan_cache", "decomp_plan_cache", "env_plan_cache"):
            assert set(out[k]) == {
                "hits", "misses", "evictions", "size", "builds",
            }


class TestService:
    def test_backpressure_queue_full(self):
        svc = DMRGService(max_batch=2, max_queue=2, start=False)
        spec = ProblemSpec.make("heisenberg", 4, J=1.0, h=0.3)
        svc.submit(spec, timeout=1.0)
        svc.submit(spec, timeout=1.0)
        with pytest.raises(ServeQueueFull):
            svc.submit(spec, timeout=0.05)
        assert svc.stats()["pending"] == 2
        svc.shutdown()

    def test_unknown_request_id(self):
        svc = DMRGService(start=False)
        with pytest.raises(KeyError):
            svc.poll(99)
        with pytest.raises(KeyError):
            svc.result(99, timeout=0.01)
        svc.shutdown()

    def test_unknown_model_rejected_at_submit(self):
        svc = DMRGService(start=False)
        with pytest.raises(ValueError, match="unknown model"):
            svc.submit(ProblemSpec.make("not-a-model", 4))
        svc.shutdown()

    @pytest.mark.slow
    def test_end_to_end_correct_energies(self, tmp_path):
        """Full service path — queue, worker thread, warmed zero-retrace
        steady state, energies vs independent singles — in its OWN process,
        the way the serve CLI runs: a fresh interpreter in which only the
        caller and the service worker compile (jaxlib 0.4.x segfaulted on
        XLA compilation from a secondary thread late in a large shared
        pytest process; the installed JAX is 0.9)."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        code = textwrap.dedent(f"""\
        import os
        os.environ["JAX_ENABLE_X64"] = "1"
        import sys
        sys.path.insert(0, r"{os.path.abspath(src)}")
        from repro.core import run_dmrg
        from repro.serve import DEVICE_LOCK, DMRGService, ProblemSpec
        from repro.serve.problems import build_problem

        svc = DMRGService(max_batch=2, max_queue=8, batch_wait_s=0.05)
        specs = [
            ProblemSpec.make(
                "heisenberg", 6, J=j, h=0.3, max_bond=8, davidson_iters=5
            )
            for j in (0.9, 1.0, 1.1)
        ]
        # the documented serving pattern: warm on the calling thread so the
        # worker replays compiled code only
        svc.warmup(specs[0], sizes=(1, 2))
        rids = [svc.submit(s, timeout=5.0) for s in specs]
        recs = [svc.result(rid, timeout=600.0) for rid in rids]
        for rec, spec in zip(recs, specs):
            assert rec["status"] == "done"
            space, mpo = build_problem(spec)
            with DEVICE_LOCK:
                ref = run_dmrg(
                    space, None, spec.n_sites,
                    bond_schedule=spec.bond_schedule,
                    sweeps_per_bond=spec.sweeps_per_bond,
                    davidson_iters=spec.davidson_iters, cutoff=spec.cutoff,
                    mpo=mpo, algo="batched", jit_matvec=True,
                )
            diff = abs(rec["energy"] - ref.energy)
            assert diff < 1e-10, (rec["energy"], ref.energy)
        st = svc.stats()
        assert st["completed"] == 3 and st["failed"] == 0, st
        assert st["pending"] == 0, st
        assert st["retraces"] == 0, st       # warmed group replays only
        assert st["problems_per_sec"] > 0, st
        assert 0.0 < st["batch_fill_ratio"] <= 1.0, st
        assert set(st["plan_caches"]) >= {{
            "plan_cache", "decomp_plan_cache", "env_plan_cache", "engines"
        }}, st
        svc.shutdown()
        print("SERVE_E2E_OK")
        """)
        script = tmp_path / "serve_e2e.py"
        script.write_text(code)
        proc = subprocess.run(
            [sys.executable, str(script)],
            capture_output=True,
            text=True,
            timeout=900,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "SERVE_E2E_OK" in proc.stdout

    @pytest.mark.x64
    def test_failed_slot_bisects_and_recovers(self):
        """A slot whose problems turn out incompatible no longer fails (or
        hangs) every request in it: the unmasked failure bisects the slot
        and each half solves clean on its own."""
        svc = DMRGService(max_batch=2, start=False)
        s_chain = ProblemSpec.make("heisenberg", 6, J=1.0, h=0.3)
        s_ladder = ProblemSpec.make("j1j2_ladder", 6, J1=1.0, J2=0.5)
        space, mpo_a = build_problem(s_chain)
        _, mpo_b = build_problem(s_ladder)
        # bypass group_key on purpose to force a mixed-structure slot
        with svc._cv:
            for rid, (sp, mpo) in enumerate(
                [(s_chain, mpo_a), (s_ladder, mpo_b)]
            ):
                svc._requests[rid] = {"status": "running", "spec": sp,
                                      "submitted": 0.0, "retries": 0,
                                      "space": space, "mpo": mpo,
                                      "key": ("forced",)}
                svc.scheduler.add(("forced",), rid, sp, space, mpo)
        slot = svc.scheduler.next_batch()
        svc._run_slot(slot)
        r0 = svc.result(0, timeout=1.0)
        r1 = svc.result(1, timeout=1.0)
        assert r0["status"] == "done" and r1["status"] == "done"
        st = svc.stats()
        assert st["bisections"] == 1
        assert st["completed"] == 2
        assert st["failed"] == 0
        svc.shutdown()
