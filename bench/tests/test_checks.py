"""The comparison that decides ``correct``: the float32 control fails it,
and so does a run whose timed path is broken underneath.

Small sizes on the CPU (``cells.SMALL``), the real limits of the
configuration files.  On the chip the control runs at the cells' own size
through ``bench/control.py``.
"""

import pytest

from bench import run
from bench.meter import CompileMeter

from .cells import run_small, small_cell

CELLS = ["j1j2-cyl4.sweep"]


def _set_up(workload, seed=2**31 + 7):
    cfg, mix, kind, _ = small_cell(workload)
    ctx = run.Context(CompileMeter())
    st = kind.setup(cfg, mix, seed, ctx)
    from bench.meter import Spans

    kind.window(st, 2.0, Spans(), ctx.meter)
    return cfg, kind, st


@pytest.mark.parametrize("workload", CELLS)
def test_program_passes_and_control_fails(workload):
    cfg, kind, st = _set_up(workload)
    limits = cfg["limits"]
    sound = kind.check(st)
    control = kind.control(st)
    assert set(sound) == set(control) == set(limits)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control


def _scale_matvec(monkeypatch):
    from repro.dist.engine import ContractionEngine

    real = ContractionEngine.matvec_fn

    def matvec_fn(self, *a, **kw):
        mv = real(self, *a, **kw)
        return lambda x: mv(x).scale(1.0 + 1e-6)

    monkeypatch.setattr(ContractionEngine, "matvec_fn", matvec_fn)


def _scale_split(monkeypatch):
    from repro.dist.engine import ContractionEngine

    real = ContractionEngine.svd_split

    def svd_split(self, *a, **kw):
        U, V, s, err = real(self, *a, **kw)
        return U.scale(1.0 + 1e-6), V, s, err

    monkeypatch.setattr(ContractionEngine, "svd_split", svd_split)


def _pair_keeps_state(monkeypatch):
    from repro.core.sweep import DMRGEngine

    real = DMRGEngine._optimize_pair_fast

    def optimize(self, j, *a, **kw):
        before = self.mps.tensors[j], self.mps.tensors[j + 1]
        out = real(self, j, *a, **kw)
        self.mps.tensors[j], self.mps.tensors[j + 1] = before
        return out

    monkeypatch.setattr(DMRGEngine, "_optimize_pair_fast", optimize)


def _davidson_returns_input(monkeypatch):
    """The eigensolver hands back the vector it was given."""
    from repro.core import sweep

    real = sweep.davidson

    def davidson(matvec, x0, n_iter=2, **kw):
        return real(matvec, x0, n_iter=0, **kw)

    monkeypatch.setattr(sweep, "davidson", davidson)


def _davidson_wrong_eigenvector(monkeypatch):
    """The eigensolver converges to the highest eigenvector, not the
    lowest."""
    from repro.core import sweep

    real = sweep.davidson

    def davidson(matvec, x0, **kw):
        lam, x, info = real(lambda v: matvec(v).scale(-1.0), x0, **kw)
        return -lam, x, info

    monkeypatch.setattr(sweep, "davidson", davidson)


def _half_the_pairs(monkeypatch):
    """Every other pair update of a sweep is left out."""
    from repro.core.davidson import DavidsonInfo
    from repro.core.sweep import DMRGEngine

    real = DMRGEngine._optimize_pair_fast

    def optimize(self, j, *a, **kw):
        if j % 2:
            return 0.0, 0.0, 0.0, DavidsonInfo()
        return real(self, j, *a, **kw)

    monkeypatch.setattr(DMRGEngine, "_optimize_pair_fast", optimize)


@pytest.fixture(scope="module")
def sound_runs():
    return {w: run_small(w) for w in CELLS}


@pytest.mark.parametrize("workload,fault", [
    ("j1j2-cyl4.sweep", _scale_matvec),
    ("j1j2-cyl4.sweep", _scale_split),
    ("j1j2-cyl4.sweep", _pair_keeps_state),
    ("j1j2-cyl4.sweep", _davidson_returns_input),
    ("j1j2-cyl4.sweep", _davidson_wrong_eigenvector),
    ("j1j2-cyl4.sweep", _half_the_pairs),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch,
                                          sound_runs):
    assert sound_runs[workload]["correct"] is True, \
        sound_runs[workload]["checks"]
    res = run_small(workload, broken=lambda: fault(monkeypatch))
    assert res["correct"] is False, res["checks"]
