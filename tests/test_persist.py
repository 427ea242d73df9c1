"""Persistent plan + executable store (dist/persist.py): signature
canonicalization, plan roundtrips through the LRU caches (zero rebuilds,
bit-identical engine outputs), version/corruption gating, jax.export
roundtrips + custom_call refusal tombstones, prefetch warm-up, activation
scoping — and the cross-process cold-start contract (prime in process A,
process B's first sweep sees zero plan builds and a >=5x speedup)."""
import json
import os
import pickle
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import run_dmrg
from repro.core.ed import ground_energy
from repro.core.mps import neel_states, total_charge
from repro.dist import ContractionEngine, PlanCache, persist
from repro.dist.persist import (
    PERSIST_VERSION,
    PlanStore,
    canonical_signature,
    signature_digest,
)
from repro.dist.plan import (
    global_decomp_cache,
    global_env_cache,
    global_plan_cache,
    plan_signature,
)
from repro.serve.problems import MODEL_BUILDERS
from repro.tensor import OUT, BlockSparseTensor, Index

AX = ((1,), (0,))


def rand_index(rng, nq=1, max_sectors=3, max_dim=4, flow=OUT):
    ns = rng.integers(1, max_sectors + 1)
    charges = rng.choice(np.arange(-2, 3), size=(8, nq), replace=True)
    charges = [tuple(int(c) for c in q) for q in charges]
    uniq = []
    for q in charges:
        if q not in uniq:
            uniq.append(q)
    uniq = uniq[:ns]
    return Index(
        tuple((q, int(rng.integers(1, max_dim + 1))) for q in uniq), flow
    )


def rand_pair(seed, nq=1):
    rng = np.random.default_rng(seed)
    shared = rand_index(rng, nq=nq)
    ia = rand_index(rng, nq=nq)
    ib = rand_index(rng, nq=nq)
    A = BlockSparseTensor.random([ia, shared], key=jax.random.PRNGKey(seed))
    B = BlockSparseTensor.random(
        [shared.dual(), ib], key=jax.random.PRNGKey(seed + 1)
    )
    return A, B

BENCH = os.path.join(
    os.path.dirname(__file__), "..", "benchmarks", "bench_dist.py"
)


def _coldstart_child(store_dir, phase, timeout=900):
    """Run one bench_dist cold-start child (its own process) and parse it."""
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    # the children's compilation cache lives in the store directory, so a
    # "cold" child starts with an empty one whatever the checkout holds
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(str(store_dir), "xla")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(BENCH), "--child-coldstart",
         str(store_dir), phase],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_COLDSTART_JSON "):
            return json.loads(line[len("BENCH_COLDSTART_JSON "):])
    raise AssertionError(proc.stdout)


class TestCompilationCacheRule:
    @pytest.mark.parametrize("env_dir", [True, False])
    def test_one_directory(self, tmp_path, monkeypatch, env_dir):
        """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
        is the checkout's fixed ``.jax_cache``.  No other directory."""
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs")
        prev = {k: getattr(jax.config, k) for k in keys}
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            want = str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache",
            )
        try:
            assert persist.configure_compilation_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
            store = persist.activate_store(tmp_path / "store", prefetch=False)
            assert jax.config.jax_compilation_cache_dir == want
            assert not os.path.exists(os.path.join(store.root, "xla"))
        finally:
            persist.deactivate_store()
            for k, v in prev.items():
                jax.config.update(k, v)


class TestSignatures:
    def test_digest_ignores_index_names(self):
        A, B = rand_pair(3)
        renamed = BlockSparseTensor(
            tuple(Index(ix.sectors, ix.flow, "other") for ix in A.indices),
            A.blocks,
            A.charge,
        )
        assert signature_digest(plan_signature(A, B, AX)) == signature_digest(
            plan_signature(renamed, B, AX)
        )

    def test_digest_distinguishes_structure(self):
        A, B = rand_pair(0)
        C, D = rand_pair(5)
        if plan_signature(A, B, AX) == plan_signature(C, D, AX):
            pytest.skip("random structures collided")
        assert signature_digest(plan_signature(A, B, AX)) != signature_digest(
            plan_signature(C, D, AX)
        )

    def test_canonical_form_drops_names_only(self):
        ix = Index((((0,), 2), ((1,), 3)), 1, "named")
        canon = canonical_signature((ix, 7, "s"))
        assert canon == (("Ix", ix.sectors, ix.flow), 7, "s")


class TestPlanRoundtrip:
    def test_primed_cache_zero_builds_bit_identical(self, tmp_path):
        """A second cache on the same store loads instead of building, and
        the engine's outputs through the loaded plan are bit-identical."""
        A, B = rand_pair(1)
        store = PlanStore(tmp_path)
        cache = PlanCache()
        cache.store = store
        eng = ContractionEngine(backend="list", cache=cache)
        C1 = eng(A, B, AX)
        assert cache.builds == 1
        assert store.stats()["saves"] == 1

        cache2 = PlanCache()
        cache2.store = store
        eng2 = ContractionEngine(backend="list", cache=cache2)
        C2 = eng2(A, B, AX)
        assert cache2.builds == 0, "primed store must satisfy the miss"
        assert store.stats()["hits"] == 1
        assert set(C1.blocks) == set(C2.blocks)
        for k in C1.blocks:
            # same plan content -> same pair order -> identical accumulation
            np.testing.assert_array_equal(
                np.asarray(C1.blocks[k]), np.asarray(C2.blocks[k])
            )

    def test_version_mismatch_rejected_and_repaired(self, tmp_path):
        A, B = rand_pair(2)
        sig = plan_signature(A, B, AX)
        store = PlanStore(tmp_path)
        cache = PlanCache()
        cache.store = store
        cache.get(A, B, AX)
        path = store._plan_path("contraction", sig)
        with open(path, "rb") as f:
            entry = pickle.load(f)
        entry["version"] = PERSIST_VERSION + 1
        with open(path, "wb") as f:
            pickle.dump(entry, f)

        store2 = PlanStore(tmp_path)
        assert store2.load_plan("contraction", sig) is None
        assert store2.stats()["stale"] == 1
        # a cache on the stale store rebuilds and repairs the entry
        cache2 = PlanCache()
        cache2.store = store2
        cache2.get(A, B, AX)
        assert cache2.builds == 1
        store3 = PlanStore(tmp_path)
        assert store3.load_plan("contraction", sig) is not None
        assert store3.stats() ["hits"] == 1

    @pytest.mark.parametrize("payload", [b"", b"garbage", b"\x80\x04X"])
    def test_corrupt_entry_is_a_counted_miss(self, tmp_path, payload):
        A, B = rand_pair(4)
        sig = plan_signature(A, B, AX)
        store = PlanStore(tmp_path)
        path = store._plan_path("contraction", sig)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(payload)
        assert store.load_plan("contraction", sig) is None
        assert store.stats()["corrupt"] == 1

    def test_truncated_entry_rebuilt(self, tmp_path):
        """A torn write (simulated by truncation) never crashes a load; the
        next build atomically repairs the entry."""
        A, B = rand_pair(6)
        sig = plan_signature(A, B, AX)
        store = PlanStore(tmp_path)
        cache = PlanCache()
        cache.store = store
        cache.get(A, B, AX)
        path = store._plan_path("contraction", sig)
        with open(path, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])

        store2 = PlanStore(tmp_path)
        cache2 = PlanCache()
        cache2.store = store2
        cache2.get(A, B, AX)
        assert store2.stats()["corrupt"] == 1
        assert cache2.builds == 1
        assert store2.stats()["saves"] == 1  # repaired
        store3 = PlanStore(tmp_path)
        assert store3.load_plan("contraction", sig) is not None

    def test_foreign_kind_rejected(self, tmp_path):
        """An entry pickled under one kind never aliases another kind's
        lookup, even at an identical digest."""
        A, B = rand_pair(7)
        sig = plan_signature(A, B, AX)
        store = PlanStore(tmp_path)
        cache = PlanCache()
        cache.store = store
        cache.get(A, B, AX)
        src = store._plan_path("contraction", sig)
        dst = store._plan_path("decomp", sig)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        with open(src, "rb") as f:
            data = f.read()
        with open(dst, "wb") as f:
            f.write(data)
        assert store.load_plan("decomp", sig) is None
        assert store.stats()["corrupt"] == 1


class TestExports:
    def _arr(self, shape=(4, 4)):
        return jnp.arange(
            np.prod(shape), dtype=jnp.float64 if jax.config.jax_enable_x64
            else jnp.float32
        ).reshape(shape)

    def test_export_roundtrip_across_store_instances(self, tmp_path):
        x = self._arr()
        fn = lambda a: a @ a.T  # pure-XLA program, exportable

        store = PlanStore(tmp_path)
        assert store.save_export(("core", "k1"), fn, (x,))
        assert store.stats()["export_saves"] == 1

        fresh = PlanStore(tmp_path)  # empty memo: must go through disk
        loaded = fresh.load_export(("core", "k1"), (x,))
        assert loaded is not None
        assert fresh.stats()["export_hits"] == 1
        np.testing.assert_allclose(
            np.asarray(loaded(x)), np.asarray(fn(x)), atol=0
        )

    def test_export_aval_mismatch_is_a_miss(self, tmp_path):
        x = self._arr((4, 4))
        store = PlanStore(tmp_path)
        assert store.save_export(("core", "k1"), lambda a: a * 2, (x,))
        y = self._arr((8, 8))
        assert store.load_export(("core", "k1"), (y,)) is None
        assert store.stats()["export_misses"] == 1

    @pytest.mark.x64
    def test_custom_call_refused_with_tombstone(self, tmp_path):
        """LAPACK-lowered programs are refused (they do not survive a
        cross-process deserialize), a tombstone is written, and every later
        save attempt is skipped without re-exporting."""
        x = self._arr((6, 4))
        svals = lambda a: jnp.linalg.svd(a, full_matrices=False)[1]

        store = PlanStore(tmp_path)
        assert not store.save_export(("svd", "k"), svals, (x,))
        assert store.stats()["export_failures"] == 1
        names = os.listdir(os.path.join(store.root, "exports"))
        assert len(names) == 1
        with open(os.path.join(store.root, "exports", names[0]), "rb") as f:
            entry = pickle.load(f)
        assert entry["refused"] == "custom_call"
        assert "data" not in entry

        # a fresh process (instance) reads the tombstone: load is a miss,
        # save is refused without paying export + module scan again
        fresh = PlanStore(tmp_path)
        assert fresh.load_export(("svd", "k"), (x,)) is None
        assert fresh.stats()["export_misses"] == 1
        assert not fresh.save_export(("svd", "k"), svals, (x,))
        assert fresh.stats()["export_failures"] == 1

    def test_prefetch_warms_the_memo(self, tmp_path):
        x = self._arr()
        store = PlanStore(tmp_path)
        store.save_export(("core", "a"), lambda a: a + 1, (x,))
        store.save_export(("core", "b"), lambda a: a - 1, (x,))

        fresh = PlanStore(tmp_path)
        assert fresh.prefetch_exports(block=True) == 2
        assert fresh.stats()["export_prefetched"] == 2
        # both lookups resolve from the warmed memo
        fa = fresh.load_export(("core", "a"), (x,))
        fb = fresh.load_export(("core", "b"), (x,))
        assert fa is not None and fb is not None
        assert fresh.stats()["export_hits"] == 2
        np.testing.assert_allclose(np.asarray(fa(x)), np.asarray(x + 1))
        # re-prefetch schedules nothing (everything already memoized)
        assert fresh.prefetch_exports(block=True) == 0

    def test_corrupt_export_is_a_counted_miss(self, tmp_path):
        x = self._arr()
        store = PlanStore(tmp_path)
        store.save_export(("core", "a"), lambda a: a + 1, (x,))
        d = os.path.join(store.root, "exports")
        name = os.listdir(d)[0]
        with open(os.path.join(d, name), "wb") as f:
            f.write(b"torn")
        fresh = PlanStore(tmp_path)
        assert fresh.load_export(("core", "a"), (x,)) is None
        assert fresh.stats()["export_corrupt"] == 1


class TestActivation:
    def test_using_store_scopes_and_restores(self, tmp_path):
        assert persist.active_store() is None
        with persist.using_store(str(tmp_path), prefetch=False) as s1:
            assert persist.active_store() is s1
            inner = tmp_path / "inner"
            with persist.using_store(str(inner), prefetch=False) as s2:
                assert persist.active_store() is s2
            assert persist.active_store() is s1
        assert persist.active_store() is None

    def test_run_dmrg_plan_store_detaches_after_run(self, tmp_path):
        space, terms = MODEL_BUILDERS["heisenberg"](4)
        res = run_dmrg(space, terms, 4, bond_schedule=(8,),
                       sweeps_per_bond=1, davidson_iters=2, algo="list",
                       plan_store=str(tmp_path))
        assert persist.active_store() is None
        assert res.energy < 0
        store = PlanStore(tmp_path)
        assert os.path.isdir(os.path.join(store.root, "contraction"))


def _clear_global_caches():
    global_plan_cache.clear()
    global_decomp_cache.clear()
    global_env_cache.clear()


@pytest.mark.x64
class TestPrimedEqualsCold:
    """The store must be physics-transparent: a run against a primed store
    (all plans loaded, zero builds) lands on the cold run's energies."""

    @settings(max_examples=5, deadline=None)
    @given(j2=st.floats(0.0, 1.0), n=st.sampled_from([4, 6]))
    def test_primed_equals_cold_energy(self, j2, n):
        space, terms = MODEL_BUILDERS["j1j2_ladder"](n, J1=1.0, J2=j2)
        kw = dict(bond_schedule=(8,), sweeps_per_bond=2, davidson_iters=4,
                  algo="list")
        with tempfile.TemporaryDirectory(prefix="persist_prop_") as d:
            _clear_global_caches()
            cold = run_dmrg(space, terms, n, plan_store=d, **kw)
            # drop the in-memory caches: the primed run must come out of
            # the store, not out of this process's memory
            _clear_global_caches()
            primed = run_dmrg(space, terms, n, plan_store=d, **kw)
            builds = (global_plan_cache.builds + global_decomp_cache.builds
                      + global_env_cache.builds)
        _clear_global_caches()
        assert builds == 0, "primed store must satisfy every plan miss"
        assert abs(cold.energy - primed.energy) < 1e-10
        for s_cold, s_primed in zip(cold.sweep_stats, primed.sweep_stats):
            assert abs(s_cold.energy - s_primed.energy) < 1e-10


@pytest.mark.x64
class TestEDCrossCheck:
    """run_dmrg (with a plan store active, exercising the full persistence
    path) matches exact diagonalization at L=8 for both registered serve
    models — the end-to-end correctness net under the cold-start machinery."""

    @pytest.mark.parametrize("model", sorted(MODEL_BUILDERS))
    def test_ground_energy_matches_ed_l8(self, model, tmp_path):
        n = 8
        space, terms = MODEL_BUILDERS[model](n)
        q = total_charge(space, neel_states(space, n))
        e0 = ground_energy(space, terms, n, charge=q)
        res = run_dmrg(space, terms, n, bond_schedule=(8, 16, 32),
                       sweeps_per_bond=2, davidson_iters=6,
                       plan_store=str(tmp_path))
        assert abs(res.energy - e0) < 1e-8, (model, res.energy, e0)


@pytest.mark.slow
class TestConcurrentAccess:
    """Two processes hammering the same store concurrently: atomic writes
    mean readers never observe a torn entry and both writers succeed."""

    def test_two_process_store_access(self, tmp_path):
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        code = textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, r"{os.path.abspath(src)}")
        from repro.dist.persist import PlanStore

        store = PlanStore(sys.argv[1])
        seed = int(sys.argv[2])
        # all workers write the SAME signatures (maximal path contention)
        # with worker-distinct payloads: any winner is complete
        for rounds in range(20):
            for i in range(10):
                sig = ("shared", i)
                payload = ("plan-payload", seed, rounds, i, "x" * 4096)
                assert store.save_plan("contraction", sig, payload)
                got = store.load_plan("contraction", sig)
                # the other worker may have won the race, but the entry
                # must always be complete and well-formed
                assert got is not None and got[0] == "plan-payload", got
        st = store.stats()
        assert st["corrupt"] == 0 and st["stale"] == 0, st
        print("WORKER_OK", st["saves"], st["hits"])
        """)
        script = tmp_path / "store_worker.py"
        script.write_text(code)
        store_dir = tmp_path / "store"
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(store_dir), str(seed)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for seed in (1, 2)
        ]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            assert "WORKER_OK" in out
        # afterwards every entry is readable by a fresh store
        reader = PlanStore(store_dir)
        for i in range(10):
            assert reader.load_plan("contraction", ("shared", i)) is not None
        st = reader.stats()
        assert st["corrupt"] == 0 and st["hits"] == 10, st


@pytest.mark.slow
@pytest.mark.x64
class TestColdStartRegression:
    """The cold-start contract, measured across a real process boundary:
    process A primes the store (and runs the warmup compile pass); process
    B's first sweep then builds ZERO plans, replays exported cores, finds
    its executables in the compilation cache A filled, and reproduces A's
    energy to 1e-10.  Counts, not wall-clock: a CPU timing ratio says
    nothing about the chip and does not hold under parallel test workers."""

    def test_primed_process_zero_builds_and_speedup(self, tmp_path):
        cold = _coldstart_child(tmp_path, "cold")
        primed = _coldstart_child(tmp_path, "primed")
        assert primed["plan_builds"] == 0, primed
        assert abs(cold["energy"] - primed["energy"]) < 1e-10
        assert cold["store"]["saves"] > 0 and cold["store"]["export_saves"] > 0
        assert primed["store"]["hits"] > 0
        assert primed["store"]["export_hits"] > 0, primed
        assert primed["compile_cache_hits"] > 0, primed
