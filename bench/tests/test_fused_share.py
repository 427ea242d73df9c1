"""The reader of ``davidson_fused_share`` on hand-made records: the
pair updates of ``test_span_metrics._records``, with ``davidson.fused``
spans added under the solves of some of them."""
import sys

import pytest

from bench import catalog
from bench.tests.test_span_metrics import MS, _records

NAME = "davidson_fused_share.sweep"


def _with_fused(n_roots, fused, finished=True):
    """``_records(n_roots)``, with two ``davidson.fused`` spans of 0.1 ms
    under the solve of each update i for which ``fused(i)``."""
    recs = _records(n_roots, finished)
    solves = [k for k, r in enumerate(recs) if r[0] == "davidson.solve"]
    for i, k in enumerate(solves):
        if fused(i):
            start = recs[k][2]
            recs += [("davidson.fused", k, start, start + MS // 10),
                     ("davidson.fused", k, start + MS // 5, start + MS // 4)]
    return recs


@pytest.fixture
def fed(monkeypatch):
    from repro import obs

    def feed(recs):
        monkeypatch.setattr(obs, "records", lambda: list(recs))
    return feed


@pytest.mark.parametrize("fused,share", [
    (lambda i: False, 0.0),
    (lambda i: True, 100.0),
    (lambda i: i % 4 == 0, 25.0),
], ids=["none", "all", "a_quarter"])
def test_share_on_hand_made_records(fed, fused, share):
    fed(_with_fused(20, fused, finished=False))
    assert catalog.metric_reader(NAME)({}) == pytest.approx(share)


def test_none_under_twenty_updates(fed):
    fed(_with_fused(19, lambda i: True))
    assert catalog.metric_reader(NAME)({}) is None


def test_none_without_spans_in_the_program(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert catalog.metric_reader(NAME)({}) is None


def test_declared_for_the_sweep_cell():
    m = {m["name"]: m for m in catalog.load_benchmark()["per_layer"]}[NAME]
    assert m["workloads"] == ["j1j2-cyl4.sweep"]
    assert m["moves"] == "pair_updates_per_s"
    assert (m["unit"], m["better"], m["source"], m["layer"]) == \
        ("%", "higher", "program_span", "Davidson")
