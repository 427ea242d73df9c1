import os
import sys

import pytest

# 64-bit for DMRG numerics; LM-model code passes explicit float32/bfloat16
# dtypes, so this does not change the transformer stack's behavior.  CI also
# runs a float32 leg (JAX_ENABLE_X64=0 in the job env wins over this
# setdefault); tests whose tolerances genuinely need float64 carry the
# ``x64`` marker and are skipped there, so the f32 leg still exercises the
# whole precision-agnostic surface (dtype handling, plan caches, kernels).
os.environ.setdefault("JAX_ENABLE_X64", "1")
# NOTE: deliberately NOT setting --xla_force_host_platform_device_count here:
# smoke tests and benches must see the single real CPU device; only
# launch/dryrun.py (run as its own process) requests 512 placeholder devices.

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Property tests import hypothesis; the container may not ship it.  Fall back
# to the deterministic stub in _hypothesis_stub.py so collection never dies
# (real hypothesis, when installed via requirements-dev.txt, always wins).
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(__file__))
    from _hypothesis_stub import install as _install_hypothesis_stub

    _install_hypothesis_stub()


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Drop jit caches between test modules to bound the process's mmap count.

    Every compiled XLA executable holds several live mmaps and the default
    ``vm.max_map_count`` is 65530; a full-suite run accumulates enough
    compiled executables to cross that ceiling, at which point the NEXT
    compilation segfaults inside jaxlib (observed deterministically once the
    suite grew past ~200 tests: /proc/<pid>/maps hits ~65k right before the
    crash).  Clearing per module keeps each module's within-module caching
    behavior (retrace-counter tests warm up and assert inside one module)
    while releasing executables no later test can reach.

    Interaction with the persistent compilation cache (dist/persist.py):
    ``jax.clear_caches()`` drops only the *in-memory* trace/executable
    caches — the on-disk cache a ``PlanStore`` activation turned on
    (``configure_compilation_cache``: ``JAX_COMPILATION_CACHE_DIR`` or the
    checkout's ``.jax_cache``) survives, by design, so post-clear
    re-compiles of already-seen programs are disk hits rather than full
    XLA compiles.  The disk entries hold no mmaps, so they don't count
    against ``vm.max_map_count``; only re-*loading* them does, and that is
    exactly the per-module budget this fixture resets.  The teardown below
    detaches any store a test module leaked without touching the cache
    config.
    """
    yield
    import gc

    import jax

    # a leaked process-wide PlanStore would redirect every later module's
    # plan-cache misses into a (possibly deleted) tmp dir; detach it first
    from repro.dist import persist

    persist.deactivate_store()
    jax.clear_caches()
    gc.collect()


def pytest_collection_modifyitems(config, items):
    """Skip ``x64``-marked tests when jax runs in float32.

    The marker tags tests whose assertions are only meaningful at float64
    precision (1e-10 energy/block equality, ED comparisons, SVD round
    trips).  Asking jax itself (rather than re-parsing the env var, whose
    truthiness rules jax owns — e.g. "off" and "no" also disable x64)
    guarantees the skip decision matches the precision the suite runs with.
    """
    import jax

    if jax.config.jax_enable_x64:
        return
    skip = pytest.mark.skip(
        reason="needs float64 numerics (JAX_ENABLE_X64=1); f32 CI leg skips"
    )
    for item in items:
        if "x64" in item.keywords:
            item.add_marker(skip)
