"""Deterministic fault injection + numerical-health errors (DESIGN.md 3.8).

At supercomputer scale (the paper's Blue Waters/Stampede2 runs) and in a
serving deployment, failures are routine: a batched GEMM can produce NaN on
a flaky node, LAPACK's SVD can fail to converge, a worker thread can die
mid-slot.  This module makes those failure modes *first-class, testable
code paths* instead of hoping they never happen:

- A registry of named **fault points** threaded through the pipeline
  (``FAULT_POINTS`` below).  Each point is a one-line hook at the real code
  site: ``fire("decomp.svd_fail")`` returns the armed fault (or ``None``).
  Disarmed, a hook is a single truthiness check of an empty dict — the
  tier-1 bench leg asserts zero retries/degradations so the hooks provably
  cost nothing when off.
- Faults are **deterministic and seedable**: armed with ``after`` (skip the
  first N reaches) and ``count`` (fire at most N times), so a test can kill
  exactly the 3rd env update of a run and nothing else.
- Arming: programmatically (``registry.arm`` / the ``inject`` context
  manager) or via the ``REPRO_FAULTS`` env var, e.g.::

      REPRO_FAULTS="decomp.svd_fail:count=1,serve.slot_latency:value=0.25"

  parsed once at first registry use — works for any entry point (tests,
  example drivers, ``python -m repro.serve``) without code changes.

Fault hooks NEVER fire under jit tracing: a NaN poisoned at trace time
would be baked into a compiled executable cached far beyond the fault's
lifetime.  Call sites that can trace guard with their existing tracing
flags.

The exception types live here too, because the injection points and the
health guards that catch their damage are two halves of one contract:

- ``FaultInjected`` — raised by "raise"-style fault points.
- ``NumericalHealthError`` — raised by the isfinite/convergence guards that
  piggyback on the pipeline's existing one-host-sync points (the Davidson
  Rayleigh-Ritz read, the post-SVD singular-value sync), so health checking
  costs ZERO extra device round-trips.  For stacked batches it carries a
  per-problem boolean mask, which the serving layer uses to fail exactly
  the poisoned request and retry the rest (``serve/service.py``).
"""
from __future__ import annotations

import dataclasses
import math
import os
import threading
from contextlib import contextmanager
from typing import Dict, Iterator, Optional


class FaultInjected(RuntimeError):
    """An armed fault point fired in "raise" mode.

    ``point`` names the fault point that fired (a ``FAULT_POINTS`` key), so
    recovery layers can report *which* injected failure they absorbed.
    """

    def __init__(self, point: str, message: Optional[str] = None):
        super().__init__(message or f"injected fault at {point!r}")
        self.point = point


class NumericalHealthError(RuntimeError):
    """A health guard at an existing host-sync point saw bad numerics.

    ``stage`` is the pipeline stage that detected the damage ("davidson",
    "svd", ...) — usually downstream of where the damage occurred, since
    checks ride the existing sync points rather than adding new ones.
    ``problems`` is ``None`` for single-problem runs; for stacked batches it
    is a boolean numpy array ``[B]``, True where that problem's values were
    non-finite — healthy problems in the same batch are NOT flagged, which
    is what lets the serving layer isolate the poisoned request.
    """

    def __init__(self, message: str, stage: str = "", problems=None):
        super().__init__(message)
        self.stage = stage
        self.problems = problems


#: Every named injection point, with where its hook lives.  Arming an
#: unknown name raises immediately (a typo would otherwise silently never
#: fire and the test would pass vacuously).
FAULT_POINTS: Dict[str, str] = {
    # NaN-poison one bucket output of a batched-GEMM contraction
    # (dist/batch.py execute_batched; skipped under tracing).
    "batch.gemm_nan": "dist/batch.py:execute_batched",
    # Forced failure of the planned batched SVD core, standing in
    # for LAPACK *gesdd non-convergence (dist/decomp.py svd_split, and the
    # stacked svd_split_multi in serve/multicore.py).
    "decomp.svd_fail": "dist/decomp.py:DecompositionEngine.svd_split",
    # Exception out of the fused environment-update core
    # (dist/envcore.py EnvironmentEngine._update).
    "env.exception": "dist/envcore.py:EnvironmentEngine._update",
    # Force a Davidson solve to report non-convergence: the residual break
    # is suppressed, the solve runs its full budget and returns
    # converged=False (core/davidson.py).
    "davidson.no_converge": "core/davidson.py:davidson",
    # Kill the sweep loop after a site update — simulates a mid-sweep crash
    # for checkpoint/resume tests (core/sweep.py DMRGEngine.sweep).
    "sweep.kill": "core/sweep.py:DMRGEngine.sweep",
    # Crash the serving worker thread between slots (outside the per-slot
    # recovery), exercising the watchdog restart (serve/service.py).
    "serve.worker_crash": "serve/service.py:_worker_loop",
    # Artificial latency added to one slot solve (``value`` = seconds).
    "serve.slot_latency": "serve/service.py:_run_slot",
    # NaN-poison the MPO of one request in a slot before solving
    # (``problem`` = the request id, so the poison follows the request
    # through bisection retries), exercising per-problem health masks and
    # slot bisection (serve/service.py).
    "serve.poison_request": "serve/service.py:_run_slot",
}


@dataclasses.dataclass
class ArmedFault:
    """One armed injection: deterministic fire window + payload knobs."""

    point: str
    after: int = 0          # skip the first ``after`` reaches
    count: float = 1        # then fire this many times (math.inf = forever)
    value: float = 0.0      # payload: latency seconds, poison value, ...
    problem: int = 0        # batch position, for per-problem faults
    fired: int = 0          # times this fault actually fired
    seen: int = 0           # times the hook was reached while armed


class FaultRegistry:
    """Thread-safe registry of armed faults; the module ships one instance.

    The fast path is ``fire()`` on an empty registry: a single truthiness
    check of ``self._armed`` with no lock (reading a dict's emptiness is
    atomic under the GIL, and arming is rare + test-only), so production
    code pays nothing for carrying the hooks.
    """

    def __init__(self):
        self._armed: Dict[str, ArmedFault] = {}
        self._lock = threading.Lock()
        self._fired_total: Dict[str, int] = {}
        self._env_parsed = False

    # ------------------------------------------------------------------ arm
    def arm(
        self,
        point: str,
        *,
        after: int = 0,
        count: float = 1,
        value: float = 0.0,
        problem: int = 0,
    ) -> ArmedFault:
        if point not in FAULT_POINTS:
            raise KeyError(
                f"unknown fault point {point!r}; known: {sorted(FAULT_POINTS)}"
            )
        f = ArmedFault(point, after=after, count=count, value=value,
                       problem=problem)
        with self._lock:
            self._armed[point] = f
        return f

    def disarm(self, point: str) -> None:
        with self._lock:
            self._armed.pop(point, None)

    def clear(self) -> None:
        with self._lock:
            self._armed.clear()

    # ----------------------------------------------------------------- fire
    def fire(self, point: str) -> Optional[ArmedFault]:
        """The hook call sites use: None when disarmed / outside the window.

        Deterministic: the ``after``/``count`` window is consumed in hook
        reach order, which the single-threaded sweep and the worker's
        slot loop make reproducible.
        """
        if not self._armed:  # fast path: nothing armed, no lock
            return None
        with self._lock:
            f = self._armed.get(point)
            if f is None:
                return None
            f.seen += 1
            if f.seen <= f.after:
                return None
            if f.fired >= f.count:
                return None
            f.fired += 1
            self._fired_total[point] = self._fired_total.get(point, 0) + 1
            return f

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict:
        with self._lock:
            return {
                "armed": sorted(self._armed),
                "fired": dict(self._fired_total),
            }

    # ---------------------------------------------------------------- env
    def arm_from_env(self, spec: Optional[str] = None) -> None:
        """Arm from a ``REPRO_FAULTS``-style spec string.

        Grammar: comma-separated points, each optionally followed by
        colon-separated ``key=value`` knobs (keys: after, count, value,
        problem; ``count=inf`` fires forever)::

            decomp.svd_fail:count=1:after=2,serve.slot_latency:value=0.25
        """
        spec = os.environ.get("REPRO_FAULTS", "") if spec is None else spec
        for part in filter(None, (p.strip() for p in spec.split(","))):
            name, *kvs = part.split(":")
            kw: Dict[str, float] = {}
            for kv in kvs:
                k, _, v = kv.partition("=")
                if k not in ("after", "count", "value", "problem"):
                    raise ValueError(
                        f"bad REPRO_FAULTS knob {kv!r} in {part!r}"
                    )
                kw[k] = math.inf if v == "inf" else float(v)
            self.arm(
                name,
                after=int(kw.get("after", 0)),
                count=kw.get("count", 1),
                value=kw.get("value", 0.0),
                problem=int(kw.get("problem", 0)),
            )


#: The process-wide registry every hook consults.
registry = FaultRegistry()


def fire(point: str) -> Optional[ArmedFault]:
    """Module-level hook shim (``faults.fire("...")`` at each call site)."""
    return registry.fire(point)


@contextmanager
def inject(point: str, **kw) -> Iterator[ArmedFault]:
    """Arm one fault for the duration of a ``with`` block, then disarm.

    The yielded ``ArmedFault`` exposes ``fired`` so tests can assert the
    fault actually triggered (a hook that silently moved would otherwise
    make the test pass without injecting anything).
    """
    f = registry.arm(point, **kw)
    try:
        yield f
    finally:
        registry.disarm(point)


# Arm anything requested through the environment once, at import: import
# order guarantees this runs before any hook can fire, and an empty/unset
# REPRO_FAULTS is a no-op.
if os.environ.get("REPRO_FAULTS"):
    registry.arm_from_env()
