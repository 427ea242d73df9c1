"""Compiles for a described TPU v5e, no chip attached: the block-GEMM Pallas
kernel at real bucket shapes, its float64 refusal, the jitted two-site
matvec core at a real block structure, and the row-chunked segment-sum of a
bucket too wide for one scatter.

The shapes come from the J1-J2 8x6 cylinder (48 sites, compressed MPO bond
20) at m=64: ``tests/data/tpu_compile_8x6.json`` holds the padded operand
structure of one Davidson solve and the two largest (P, M, K, N) buckets of
its contraction plans.  Nothing here runs on a device: these are the TPU
compiler's refusals caught at no chip time.  The matvec compile takes tens
of seconds, not the kernel's one or two: emulated float64 makes every GEMM
of the core expensive to compile (the same core in float32 takes ~3 s).
The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro.dist import ContractionEngine, PlanCache
from repro.kernels.block_gemm import ops
from repro.kernels.block_gemm.ops import _kernel_covered
from repro.tensor import BlockSparseTensor, Index

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_compile_8x6.json")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def structure():
    with open(DATA) as f:
        return json.load(f)


def _gemm_args(shape, dtype, sharding):
    p, m, k, n = shape
    return (
        jax.ShapeDtypeStruct((p, m, k), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((p, k, n), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((p,), jnp.int32, sharding=sharding),
    )


def _compile_gemm(shape, dtype, sharding):
    lhs, rhs, oi = _gemm_args(shape, dtype, sharding)
    return _kernel_covered.lower(
        lhs, rhs, oi, shape[0], bm=128, bn=128, bk=128, interpret=False
    ).compile()


def _abstract_tensor(desc, sharding, dtype=jnp.float64):
    indices = [
        Index(tuple((tuple(q), d) for q, d in secs), flow)
        for secs, flow in desc["indices"]
    ]
    t = BlockSparseTensor(indices, {}, tuple(desc["charge"]))
    t.blocks = {
        tuple(k): jax.ShapeDtypeStruct(
            t.block_shape(tuple(k)), dtype, sharding=sharding
        )
        for k in desc["keys"]
    }
    return t


@pytest.mark.parametrize("which", [0, 1])
def test_block_gemm_kernel_float32(one_chip, structure, which):
    shape = structure["bucket_shapes"][which]
    text = _compile_gemm(shape, jnp.float32, one_chip).as_text()
    assert "tpu_custom_call" in text


def test_block_gemm_refuses_float64(one_chip, structure):
    with pytest.raises(TypeError, match="float64"):
        _compile_gemm(structure["bucket_shapes"][0], jnp.float64, one_chip)


def test_matvec_core_compiles(one_chip, structure):
    ops = {
        k: _abstract_tensor(structure["matvec"][k], one_chip)
        for k in ("A", "W0", "W1", "B", "x")
    }
    eng = ContractionEngine(backend="batched", cache=PlanCache())

    def matvec(A, W0, W1, B, x):
        mats = eng._fixed_operand_mats(A, W0, W1, B)
        return eng.two_site_matvec(A, W0, W1, B, x, mats=mats)

    compiled = jax.jit(matvec).lower(
        ops["A"], ops["W0"], ops["W1"], ops["B"], ops["x"]
    ).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert out and all(o.dtype == jnp.float64 for o in out)


def test_wide_bucket_segment_sum_fits_vmem(one_chip):
    """A float64 bucket of the 8x6 matvec at m~2048 ([26, 131072, 8] x
    [26, 8, 8] into 15 outputs): its one-piece segment-sum scatter asks for
    20 MiB of scoped VMEM against the v5e's 16 MiB and is refused; the row
    chunks that ``block_sparse_matmul`` cuts it into compile."""
    p, m, k, n, o = 26, 131072, 8, 8, 15
    oi = np.sort(np.concatenate(
        [np.arange(o), np.random.RandomState(0).randint(0, o, p - o)]
    )).astype(np.int32)
    assert m * n > ops.SCATTER_WINDOW
    lhs = jax.ShapeDtypeStruct((p, m, k), jnp.float64, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((p, k, n), jnp.float64, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: ops.block_sparse_matmul(a, b, oi, o, use_kernel=False)
    ).lower(lhs, rhs).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (o, m, n) and out.dtype == jnp.float64
