"""chip_smoke.py off the chip: it refuses to run without a TPU or outside
the checkout, and its phases pass on the CPU — the ED check and service at
the sizes the chip runs, the deployment and four-chip phases on smaller
lattices (four virtual devices for the mesh), and the deadline that cuts
the deployment."""
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run(args, cwd, env_extra=None, drop_pythonpath=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    if drop_pythonpath:
        env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable] + args, cwd=cwd, env=env, capture_output=True,
        text=True, timeout=600,
    )


def _contract_line(stdout):
    return any(l.startswith('{"ok"') for l in stdout.splitlines())


class TestRefusal:
    def test_exits_nonzero_without_tpu(self):
        proc = _run([SCRIPT], cwd=ROOT)
        assert proc.returncode != 0
        assert not _contract_line(proc.stdout)
        assert '"platform": "cpu"' in proc.stdout  # the device phase ran

    def test_exits_nonzero_outside_checkout(self, tmp_path):
        shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        proc = _run(["chip_smoke.py"], cwd=tmp_path, drop_pythonpath=True)
        assert proc.returncode == 2
        assert "no checkout here" in proc.stderr
        # refused before JAX was asked for a device
        assert '"phase": "device"' not in proc.stdout
        assert not _contract_line(proc.stdout)


@pytest.mark.x64
class TestPhasesTiny:
    def test_one_chip_phases(self):
        """The default run's ED check and service at the chip's own sizes,
        and the deployment phase on a 4x2 cylinder."""
        meter = chip_smoke.CompileMeter()
        ed = chip_smoke.ed_phase(meter)
        dep = chip_smoke.deployment_phase(
            meter, lx=4, ly=2, schedule=(8, 16), deadline=float("inf")
        )
        # one sweep per bond; the first grows the bond from a product state
        assert len(dep.sweep_stats) == 2
        assert dep.sweep_stats[-1].max_bond == 16
        st, singles = chip_smoke.service_phase(meter)
        assert st["slots"] == 1 and st["batch_fill_ratio"] == 1.0
        chip_smoke.recovery_phase(
            [("ed", ed), ("deployment", dep)]
            + [(f"single{i}", r) for i, r in enumerate(singles)],
            st,
        )

    def test_recovery_ledger_flags_a_degraded_run(self):
        from repro.core.dmrg import DMRGResult

        dirty = {"retries": {"env": 1}, "degradations": {"env_seed": 1},
                 "decomp": {"retries": 0, "degradations": {"svd_exact": 0}}}
        res = DMRGResult(energy=0.0, mps=None, sweep_stats=[],
                         engine_stats=dirty)
        with pytest.raises(RuntimeError, match="env_seed"):
            chip_smoke.recovery_phase([("run", res)], None)

    def test_four_chip_phase_on_virtual_devices(self):
        code = textwrap.dedent(f"""\
            import sys
            sys.path.insert(0, {ROOT!r})
            import chip_smoke
            meter = chip_smoke.CompileMeter()
            chip_smoke.four_chip_phase(meter, lx=2, ly=2, schedule=(4,),
                                       deadline=float("inf"))
            print("FOUR_CHIP_OK")
            """)
        proc = _run(
            ["-c", code], cwd=ROOT,
            env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                       "JAX_ENABLE_X64": "1"},
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "FOUR_CHIP_OK" in proc.stdout
        assert '"env_device_set_sizes": [4]' in proc.stdout


class TestDeadline:
    def test_deployment_skipped_without_setup_time(self, capsys):
        assert chip_smoke.deployment_phase(
            None, deadline=chip_smoke.elapsed() + 1.0) is None
        out = capsys.readouterr().out
        assert '"completed_sweeps": 0' in out and "skipped" in out

    @pytest.mark.parametrize("where", ["before", "inside"])
    def test_sweeps_cut_at_deadline(self, where):
        class Engine:
            swept = []

            def sweep(self, max_bond, on_site):
                time.sleep(1.5)
                on_site(None)
                self.swept.append(max_bond)
                return max_bond

        if where == "before":
            deadline = chip_smoke.elapsed() - 1.0
        else:
            deadline = chip_smoke.elapsed() + 1.0
        stats, cut = chip_smoke.sweep_until(Engine(), [4, 8], deadline)
        assert stats == [] and Engine.swept == []
        assert cut.startswith("deadline") and f"{where} the m=4 sweep" in cut

    def test_sweeps_run_to_the_end_of_the_schedule(self):
        class Engine:
            def sweep(self, max_bond, on_site):
                on_site(None)
                return max_bond

        done = []
        stats, cut = chip_smoke.sweep_until(
            Engine(), [4, 8], float("inf"), lambda m, s, h: done.append(m))
        assert stats == [4, 8] and done == [4, 8] and cut is None
