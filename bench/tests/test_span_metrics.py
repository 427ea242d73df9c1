"""The readers of the program's spans (``repro.obs``) on hand-made records:
the value computed by hand, and None with fewer than 20 pair updates or a
program without spans."""
import sys

import pytest

from bench import catalog

MS = 1_000_000  # ns


def _records(n_roots, finished=True):
    """``n_roots`` pair updates.  Update i: ``davidson.solve`` (i + 1) ms
    holding a matvec and two reads of 0.1 ms, and a third read on odd i;
    ``split`` 2 ms holding a 0.3 ms read; ``env.update`` 0.5 ms; glue
    spans of 1 + 0.25 + 0.25 + 0.1 + 0.4 = 2 ms.  A span outside any pair
    update comes first."""
    recs = [("env.update", None, 0, 7 * MS)]
    t = 10 * MS

    def add(name, parent, dur):
        nonlocal t
        recs.append((name, parent, t, t + dur))
        t += dur
        return len(recs) - 1

    for i in range(n_roots):
        root = len(recs)
        recs.append(["sweep.pair", None, t, None])
        for name, dur in (("sweep.theta", MS), ("sweep.pad", MS // 4),
                          ("sweep.operator", MS // 4)):
            add(name, root, dur)
        solve = len(recs)
        start = t
        recs.append(None)
        add("davidson.matvec", solve, MS // 10)
        for _ in range(3 if i % 2 else 2):
            add("davidson.read", solve, MS // 10)
        t = start + (i + 1) * MS
        recs[solve] = ("davidson.solve", root, start, t)
        add("sweep.unpad", root, MS // 10)
        split = len(recs)
        start = t
        recs.append(None)
        add("split.core", split, MS // 2)
        add("split.read", split, 3 * MS // 10)
        t = start + 2 * MS
        recs[split] = ("split", root, start, t)
        add("sweep.place", root, 4 * MS // 10)
        add("env.update", root, MS // 2)
        recs[root][3] = t + MS // 10
        t += MS
        recs[root] = tuple(recs[root])
    if not finished:
        recs.append(("sweep.pair", None, t, None))
    return recs


EXPECTED = {
    "davidson_host_ms.sweep": 10.5,       # median of 1..20 ms
    "split_host_ms.sweep": 2.0,
    "env_host_ms.sweep": 0.5,
    "glue_host_ms.sweep": 2.0,
    "read_wait_ms.sweep": 0.55,          # ten updates at 0.5, ten at 0.6
    "host_reads_per_update.sweep": 3.5,  # 3 and 4 reads, alternating
}


@pytest.fixture
def fed(monkeypatch):
    from repro import obs

    def feed(recs):
        monkeypatch.setattr(obs, "records", lambda: list(recs))
    return feed


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_records(name, fed):
    read = catalog.metric_reader(name)
    fed(_records(20, finished=False))
    assert read({}) == pytest.approx(EXPECTED[name])
    fed(_records(19))
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_spans_in_the_program(name, monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert catalog.metric_reader(name)({}) is None


def test_each_span_metric_is_declared_for_the_sweep_cell():
    bench = catalog.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = declared[name]
        assert m["workloads"] == ["j1j2-cyl4.sweep"]
        assert m["moves"] == "pair_updates_per_s"
