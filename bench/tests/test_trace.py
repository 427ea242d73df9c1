"""The trace reduction, on hand-made events and on a recorded trace."""
import os
import shutil

import pytest

from bench import trace


def _events():
    # two devices; times in ns; the window is [100, 1100)
    ops0 = [("dot.1", 50, 150), ("fusion.2", 140, 300), ("dot.1", 500, 600),
            ("copy.3", 1050, 1200)]
    ops1 = [("dot.1", 200, 400), ("fusion.2", 900, 1000)]
    host = [("python", "bench.window", 100, 1100),
            ("python", "bench.pair_update", 100, 700),
            ("python", "bench.pair_update", 700, 1100),
            ("python", "device_get", 320, 480),
            ("python", "svd_callback", 650, 1040)]
    mods = [("jit_a", 40, 310), ("jit_b", 490, 610)]
    return {"devices": {"/device:TPU:0": {"ops": ops0, "modules": mods},
                        "/device:TPU:1": {"ops": ops1, "modules": []}},
            "host": host}


def test_union_and_gaps():
    merged = trace.union([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert merged == [(0, 4), (5, 10)]
    assert trace.gaps(merged, -1, 12) == [(-1, 0), (4, 5), (10, 12)]
    assert trace.clip([(0, 4), (5, 10)], 2, 6) == [(2, 4), (5, 6)]


def test_reduce_hand_made():
    ev = _events()
    lo, hi = trace.window_of(ev["host"], "bench.window")
    assert (lo, hi) == (100, 1100)
    r = trace.reduce(ev, (lo, hi))
    # device 0 busy: [100,300) + [500,600) + [1050,1100) = 350 ns
    # device 1 busy: [200,400) + [900,1000) = 300 ns
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(325e-9)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["jit_a/dot.1"] == pytest.approx(50e-9)
    assert ops["jit_a/fusion.2"] == pytest.approx(160e-9)
    assert ops["jit_b/dot.1"] == pytest.approx(100e-9)
    assert ops["dot.1"] == pytest.approx(200e-9)   # device 1: no module
    # longest gaps: device 1 [400, 900), middle 650, then device 0
    # [600, 1050), middle 825; both under a pair update and the callback
    assert [s for _, s in r["idle_gaps"][:4]] == pytest.approx(
        [500e-9, 450e-9, 200e-9, 100e-9])
    assert r["idle_gaps"][0][0] == "bench.pair_update / svd_callback"
    assert r["idle_gaps"][1][0] == "bench.pair_update / svd_callback"
    # device 0 [300, 500), middle 400: inside the device_get
    assert r["idle_gaps"][2][0] == "bench.pair_update / device_get"


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_reduce_recorded_trace(tmp_path):
    """A trace recorded on a TPU v5e by ``record_trace.py``: four jitted
    products, each followed by a 50 ms host pause, inside ``bench.window``."""
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    shutil.copy(DATA, run_dir / "host.xplane.pb")
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert ev["devices"]["/device:TPU:0"]["ops"]
    r = trace.reduce_events(ev)
    assert r["devices"] == 1
    assert 0.2 <= r["window_s"] < 1.0
    assert 0.0 < r["busy_s"] < 0.5 * r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    # the four pauses are the longest gaps, each inside a pair update
    gaps = r["idle_gaps"][:4]
    assert all(s >= 0.04 for _, s in gaps)
    assert all(label.startswith("bench.pair_update") for label, _ in gaps)
