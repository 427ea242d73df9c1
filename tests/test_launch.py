"""Launch-layer tests: sharding resolution, cost parser, dry-run smoke on a
small in-process mesh (8 host devices via subprocess to avoid polluting the
test process's device count)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.hlo_costs import total_costs

REPO = Path(__file__).resolve().parents[1]


class TestShardingRules:
    def _mesh(self):
        from repro.launch.mesh import make_mesh
        return make_mesh((1, 1), ("data", "model"))

    def test_divisibility_fallback(self):
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import spec_for
        mesh = self._mesh()
        # everything divides a 1x1 mesh
        assert spec_for((60, 2048, 1408), ("expert", "embed", "expert_ff"),
                        mesh) == P("model", "data", None)

    def test_axis_used_once(self):
        from repro.launch.sharding import spec_for
        mesh = self._mesh()
        spec = spec_for((64, 64), ("heads", "ff"), mesh)
        used = [s for s in spec if s is not None]
        assert len(set(used)) == len(used)


class TestHloCosts:
    def test_while_trip_multiplication(self):
        hlo = textwrap.dedent("""\
        HloModule test
        %body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %a = f32[8,8]{1,0} get-tuple-element(%p), index=1
          %dot.1 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %c = s32[] constant(1)
          %i = s32[] get-tuple-element(%p), index=0
          %ip = s32[] add(%i, %c)
          ROOT %t = (s32[], f32[8,8]) tuple(%ip, %dot.1)
        }
        %cond.1 (p: (s32[], f32[8,8])) -> pred[] {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %n = s32[] constant(7)
          ROOT %lt = pred[] compare(%i, %n), direction=LT
        }
        ENTRY %main (x: f32[8,8]) -> f32[8,8] {
          %x = f32[8,8]{1,0} parameter(0)
          %z = s32[] constant(0)
          %t0 = (s32[], f32[8,8]) tuple(%z, %x)
          %w = (s32[], f32[8,8]) while(%t0), condition=%cond.1, body=%body.1
          ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
        }
        """)
        t = total_costs(hlo)
        # dot flops = 2*8*8*8 = 1024, x 7 trips
        assert t["flops"] == pytest.approx(1024 * 7)

    def test_collective_wire_model(self):
        hlo = textwrap.dedent("""\
        ENTRY %main (x: f32[64]) -> f32[64] {
          %x = f32[64]{0} parameter(0)
          ROOT %ar = f32[64]{0} all-reduce(%x), replica_groups=[2,4]<=[8], to_apply=%sum
        }
        """)
        t = total_costs(hlo)
        # 2 * 256B * (4-1)/4 = 384
        assert t["coll"]["all-reduce"] == pytest.approx(384.0)


@pytest.mark.slow
class TestDryRunSmoke:
    """Full dry-run machinery on an 8-device host mesh (subprocess)."""

    def test_small_mesh_cell(self, tmp_path):
        code = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, r"%s")
        import jax, json
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = (
            lambda multi_pod=False: mesh_mod.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model"))
        )
        from repro.launch.dryrun import run_cell
        from pathlib import Path
        import dataclasses
        from repro.configs import base as cb
        cfg = cb.get_config("granite_3_2b").smoke()
        cfg = dataclasses.replace(cfg, name="granite_tiny")
        cb.register(cfg)
        for mp in (False, True):
            rec = run_cell("granite_tiny", "train_4k", mp, Path(r"%s"), force=True)
            assert rec["status"] == "ok", rec
            assert rec["flops_per_chip"] > 0
            assert rec["collective"]["total"] > 0
        print("SMOKE_OK")
        """) % (REPO / "src", tmp_path)
        # patch SHAPES to something tiny inside the subprocess
        code = code.replace(
            'from repro.launch.dryrun import run_cell',
            'import repro.configs.base as b;'
            'b.SHAPES["train_4k"] = dict(seq_len=64, global_batch=8, kind="train");'
            'from repro.launch.dryrun import run_cell')
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=600)
        assert "SMOKE_OK" in r.stdout, r.stderr[-2000:]


def test_import_raises_no_deprecation_warning():
    """Every module of the package imports cleanly on the installed JAX,
    with deprecation warnings turned into errors."""
    code = textwrap.dedent("""\
        import importlib, pkgutil, repro
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            if not m.name.endswith("__main__"):
                importlib.import_module(m.name)
        """)
    proc = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c", code],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
