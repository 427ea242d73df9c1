"""Decomposition engine: plan-cached, shape-bucketed batched truncated SVD.

The blockwise truncated SVD across a bond (paper Fig. 1e, Sec. IV-A) is the
second cost center of the DMRG pipeline next to contractions — Menczer et
al. (arXiv:2407.07411) show it becomes the scaling bottleneck once the
contractions are batched onto accelerators.  The seed ``svd_split`` rebuilt
each charge-sector matrix with one ``.at[].set()`` dispatch per block, ran
one ``jnp.linalg.svd`` per sector sequentially, and synced the singular
values of every sector to host separately.  This module mirrors the
plan/execute split of the contraction engine for that stage:

1. A ``DecompositionPlan`` (``dist/plan.py``, cached by structural
   signature) precomputes the sector grouping, row/column layouts and a
   gather index table per *shape bucket* — all sectors whose matrices pad to
   the same power-of-two ``(Rp, Cp)`` — from ``Index`` metadata alone.
2. ``DecompositionEngine.svd_split`` executes the plan as ONE jit-compiled
   core per bucketed structure: a single gather assembles each bucket's
   stacked ``[S, Rp, Cp]`` sector matrices straight from the flattened theta
   blocks (no per-block ``.at[].set()``), each bucket runs as one batched
   SVD (``bucket_svd``: host LAPACK for float64, the device's own SVD
   below that), padding singular values are masked to exact zero in
   padded space, and the absorb scaling happens on device.  Only the
   (small) concatenated singular-value vector is synced to host — one sync
   per call instead of one per sector — where the global truncation picks
   the retained bond.
3. For sectors where ``min(R, C)`` far exceeds the requested ``max_bond``, a
   randomized-SVD path (sketch + power iteration, Halko et al. 2011)
   computes only the top ``max_bond + oversample`` triplets; ``method="auto"``
   enables it per bucket through a flop cost model.

Backend-equality guarantee: with the default exact method, the split matches
the seed ``svd_split_unplanned`` to <1e-10 up to the per-singular-vector
sign gauge — the products U·V (and therefore all DMRG energies and reduced
density matrices), the singular values, the retained bond sectors and the
truncation error agree unconditionally; individual U/V blocks may differ by
a column/row sign because LAPACK's sign choice is not specified.  Exact
ties in singular values at the truncation threshold are broken
deterministically by (sector charge order, position), keeping the total
retained bond ≤ ``max_bond`` — the seed path can exceed ``max_bond`` on
exact ties.  The randomized method is approximate by construction and is
never chosen unless explicitly requested ("randomized") or cost-justified
under ``method="auto"``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..tensor.blocksparse import BlockSparseTensor
from ..tensor.qn import IN, Index, OUT, qzero
from . import faults, persist
from .batch import is_tracing as _is_tracing
from .faults import FaultInjected, NumericalHealthError
from .plan import (
    DecompPlanCache,
    DecompositionPlan,
    global_decomp_cache,
    svd_flop_estimate,
)

# per-plan cap on cached compiled cores (the batched-SVD core per
# (absorb, methods, sketch) and one slice core per kept-count tuple): the
# kept counts drift while a run converges, so without a bound every
# truncation pattern ever seen would pin an executable (and the engine that
# compiled it, via the closure) for the life of the globally cached plan.
# FIFO eviction; an evicted core is simply recompiled on next use.
_EXEC_CACHE_MAX = 32


def _cache_exec(plan: DecompositionPlan, key, core):
    plan._exec[key] = core
    while len(plan._exec) > _EXEC_CACHE_MAX:
        plan._exec.pop(next(iter(plan._exec)))


_host_svd_lock = threading.Lock()
_host_svd = {"calls": 0, "seconds": 0.0}


def host_svd_stats() -> Dict:
    """Process-wide ledger of the float64 host LAPACK callback: ``calls``
    and ``seconds`` of host wall-clock spent inside it (the decomposition
    stage's host-resident share; the rest of a ``split`` span is device
    gather/absorb work and the truncation sync)."""
    with _host_svd_lock:
        return dict(_host_svd)


def _host_lapack_svd(x: np.ndarray):
    """numpy's LAPACK gesdd on a stack of matrices; NaN where it fails.

    Non-convergence (or non-finite input) fills the outputs with NaN, the
    same contract as XLA's own LAPACK lowering, so the numerical-health
    guard at the truncation sync sees it instead of a callback exception.
    It runs on the runtime's callback thread, so it is a profiler annotation
    (``split.lapack``) and not a ``repro.obs`` span.
    """
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("split.lapack"):
        x = np.asarray(x)
        try:
            u, s, vh = np.linalg.svd(x, full_matrices=False)
        except np.linalg.LinAlgError:
            k = min(x.shape[-2:])
            u = np.full(x.shape[:-1] + (k,), np.nan, x.dtype)
            s = np.full(x.shape[:-2] + (k,), np.nan, np.finfo(x.dtype).dtype)
            vh = np.full(x.shape[:-2] + (k, x.shape[-1]), np.nan, x.dtype)
        out = (u.astype(x.dtype), s.astype(np.finfo(x.dtype).dtype),
               vh.astype(x.dtype))
    with _host_svd_lock:
        _host_svd["calls"] += 1
        _host_svd["seconds"] += time.perf_counter() - t0
    return out


def bucket_svd(mats: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Thin SVD of stacked ``[..., R, C]`` matrices inside a traced core.

    64-bit inputs factorize on host LAPACK through ``jax.pure_callback``:
    a TPU has no float64 unit, and XLA's emulated float64 SVD (QDWH polar
    decomposition + eigh) takes ~75 s to compile for one [8, 256, 256]
    bucket and over 10 minutes at 1024x1024 on a v5e, once per bucket
    shape.  The callback compiles in milliseconds and is the same gesdd
    the CPU backend lowers ``jnp.linalg.svd`` to, so every platform runs
    one float64 path.  32-bit inputs stay on the device's own SVD.
    """
    if jnp.finfo(mats.dtype).bits < 64:
        return jnp.linalg.svd(mats, full_matrices=False)
    *lead, r, c = mats.shape
    k = min(r, c)
    real = jnp.finfo(mats.dtype).dtype
    shapes = (
        jax.ShapeDtypeStruct((*lead, r, k), mats.dtype),
        jax.ShapeDtypeStruct((*lead, k), real),
        jax.ShapeDtypeStruct((*lead, k, c), mats.dtype),
    )
    return jax.pure_callback(
        _host_lapack_svd, shapes, mats, vmap_method="broadcast_all"
    )


def _randomized_svd(
    mats: jax.Array, sketch: int, power_iters: int, seed: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched randomized range-finder SVD (Halko/Martinsson/Tropp 2011).

    Returns the approximate top-``sketch`` triplets of every stacked matrix:
    project onto a random sketch, orthonormalize, refine with QR-stabilized
    power iterations, then SVD the small projected matrix.  Accuracy decays
    with the singular-value tail beyond the sketch — callers must keep
    ``sketch`` comfortably above the retained bond (the engine uses
    ``max_bond + rsvd_oversample``).
    """
    cp = mats.shape[-1]
    key = jax.random.PRNGKey(seed)
    G = jnp.asarray(
        jax.random.normal(key, (cp, sketch), jnp.float64 if mats.dtype in (jnp.float64, jnp.complex128) else jnp.float32),
        mats.dtype,
    )
    Q, _ = jnp.linalg.qr(mats @ G)                                 # [S, rp, l]
    mats_h = jnp.swapaxes(jnp.conj(mats), -1, -2)
    for _ in range(power_iters):
        Z, _ = jnp.linalg.qr(mats_h @ Q)                           # [S, cp, l]
        Q, _ = jnp.linalg.qr(mats @ Z)
    B = jnp.swapaxes(jnp.conj(Q), -1, -2) @ mats                   # [S, l, cp]
    Ub, s, Vh = bucket_svd(B)
    return Q @ Ub, s, Vh


def _rsvd_flops(rp: int, cp: int, sketch: int, power_iters: int) -> float:
    """Flop estimate for one randomized SVD: sketch + power-iteration GEMMs
    (2·rp·cp·l each), QR factorizations (~2·dim·l²) and the small SVD."""
    gemms = (2.0 + 2.0 * power_iters) * 2.0 * rp * cp * sketch
    qrs = (1.0 + 2.0 * power_iters) * 2.0 * (rp + cp) * sketch**2
    return gemms + qrs + svd_flop_estimate(sketch, cp)


# The traced bodies and the host truncation live at module level (not as
# engine methods) so the multi-problem solver (repro/serve/multicore.py) can
# wrap the *same* code in ``jax.vmap`` and in per-problem host loops —
# per-problem SVD/truncation semantics then cannot diverge from the
# single-problem engine by construction.
def svd_core_body(
    plan: DecompositionPlan,
    absorb: str,
    methods: Tuple[str, ...],
    sketch: int,
    rsvd_power_iters: int = 2,
    rsvd_seed: int = 0,
):
    """Assembly + batched SVD + masking + absorb, one traceable function.

    Input: theta's block arrays in ``plan.block_order``.  Output: per bucket
    ``(U, s, Vh)`` with padding singular values masked to exact zero and the
    absorb scaling applied to U ("left") or Vh ("right"), plus the
    concatenated singular values of all buckets (the only array the caller
    syncs to host).  The gather tables fold into the trace as constants, so
    a compiled executable is keyed purely by the bucketed block structure.
    """

    def body(blocks):
        flat = jnp.pad(jnp.concatenate([b.reshape(-1) for b in blocks]), (0, 1))
        out, s_parts = [], []
        for bi, bucket in enumerate(plan.buckets):
            mats = flat[bucket.gather]
            if methods[bi] == "rsvd":
                U, s, Vh = _randomized_svd(
                    mats, sketch, rsvd_power_iters, rsvd_seed + bi
                )
            else:
                U, s, Vh = bucket_svd(mats)
            # padding rows/cols contribute ~eps junk values; zero them so
            # the host truncation only ever sees the K=min(R,C) real ones
            mask = jnp.arange(s.shape[-1])[None, :] < bucket.k_true[:, None]
            s = jnp.where(mask, s, jnp.zeros((), s.dtype))
            if absorb == "left":
                U = U * s[:, None, :].astype(U.dtype)
            elif absorb == "right":
                Vh = Vh * s[:, :, None].astype(Vh.dtype)
            out.append((U, s, Vh))
            s_parts.append(s.reshape(-1))
        return tuple(out), jnp.concatenate(s_parts)

    return body


def slice_core_body(plan: DecompositionPlan, m_q: Tuple[int, ...]):
    """Slice every retained U column / V row / singular value, traceable.

    ``m_q`` (retained count per sector) is static — it keys the compiled
    executable.  Returns flat tuples of U blocks, V blocks and per-sector
    singular values in plan order, skipping sectors with ``m_q == 0``.
    """

    def body(bucket_out):
        u_out, v_out, s_out = [], [], []
        for si, sec in enumerate(plan.sectors):
            m = m_q[si]
            if m == 0:
                continue
            U, s, Vh = bucket_out[sec.bucket]
            Uq, Vq = U[sec.slot], Vh[sec.slot]
            s_out.append(s[sec.slot, :m])
            for rk, rd, ro in zip(sec.row_keys, sec.rdims, sec.roffs):
                shp = tuple(
                    ix.sector_dim(sk) for ix, sk in zip(plan.row_ix, rk)
                ) + (m,)
                u_out.append(Uq[ro : ro + rd, :m].reshape(shp))
            for ck, cd, co in zip(sec.col_keys, sec.cdims, sec.coffs):
                shp = (m,) + tuple(
                    ix.sector_dim(sk) for ix, sk in zip(plan.col_ix, ck)
                )
                v_out.append(Vq[:m, co : co + cd].reshape(shp))
        return tuple(u_out), tuple(v_out), tuple(s_out)

    return body


def host_truncate(
    plan: DecompositionPlan,
    s_host: np.ndarray,
    k_out,
    max_bond: int,
    cutoff: float,
) -> Tuple[np.ndarray, float]:
    """Global truncation on the host-synced singular values of one problem.

    ``s_host`` is the concatenated (masked) singular-value vector a
    ``svd_core_body`` call produced; ``k_out`` the per-bucket value counts.
    Returns ``(m_q, trunc_err)``: retained count per plan sector (ties broken
    deterministically by (sector, position)) and the tail sum of squares.
    """
    sec_vals: list = [None] * plan.num_sectors
    off = 0
    for b, bucket in enumerate(plan.buckets):
        kb = k_out[b]
        for slot, si in enumerate(bucket.sectors):
            avail = min(plan.sectors[si].K, kb)
            sec_vals[si] = s_host[off + slot * kb : off + slot * kb + avail]
        off += len(bucket.sectors) * kb

    vals = np.concatenate(sec_vals)
    sec_id = np.concatenate(
        [np.full(len(v), si, np.int64) for si, v in enumerate(sec_vals)]
    )
    pos_id = np.concatenate([np.arange(len(v)) for v in sec_vals])
    order = np.lexsort((pos_id, sec_id, -vals))
    smax = float(vals[order[0]]) if len(order) else 1.0
    n_keep = int(min(int(max_bond), int(np.sum(vals > cutoff * smax))))
    n_keep = max(n_keep, 1)
    kept = order[:n_keep]
    m_q = np.zeros(plan.num_sectors, np.int64)
    np.add.at(m_q, sec_id[kept], 1)
    # direct tail sum, like the seed: exactly 0.0 when nothing is truncated
    # (a total-minus-kept difference would leave ~eps noise of either sign
    # from summing the same multiset in two orders)
    trunc_err = float(np.sum(vals[order[n_keep:]] ** 2))
    return m_q, trunc_err


class DecompositionEngine:
    """Executes cached DecompositionPlans as bucketed batched SVDs.

    Parameters
    ----------
    cache: ``DecompPlanCache`` (defaults to the global one, shared with any
        other engine — plans and their compiled cores are reused).
    method: "svd" (exact batched SVD, the default and the only path with the
        <1e-10 seed-equality guarantee), "randomized" (randomized SVD on
        every bucket where the sketch is smaller than the full rank), or
        "auto" (per-bucket flop cost model chooses between the two).
    jit: compile the assembly+SVD core once per bucketed structure (default);
        ``False`` runs it eagerly, for debugging.
    rsvd_oversample / rsvd_power_iters / rsvd_seed: randomized-path knobs —
        sketch size is ``max_bond + rsvd_oversample``, power iterations
        sharpen the spectrum estimate, and the seed fixes the sketch matrix
        so repeated calls are deterministic.

    ``stats()`` reports cumulative counters; see its docstring for units.
    """

    def __init__(
        self,
        cache: Optional[DecompPlanCache] = None,
        method: str = "svd",
        *,
        jit: bool = True,
        rsvd_oversample: int = 8,
        rsvd_power_iters: int = 2,
        rsvd_min_gain: float = 1.0,
        rsvd_seed: int = 0,
    ):
        assert method in ("svd", "randomized", "auto")
        self.cache = cache if cache is not None else global_decomp_cache
        self.method = method
        self.jit = jit
        self.rsvd_oversample = rsvd_oversample
        self.rsvd_power_iters = rsvd_power_iters
        self.rsvd_min_gain = rsvd_min_gain
        self.rsvd_seed = rsvd_seed
        self.svd_calls = 0
        self.svd_flops = 0.0
        self.jit_retraces = 0
        self.sectors_processed = 0
        self.buckets_processed = 0
        self.rsvd_buckets = 0
        # degradation ladder ledger (DESIGN.md 3.8): ``retries`` counts
        # splits whose first attempt failed; ``degradations`` counts which
        # ladder rung recovered them.  Both stay zero on a healthy run —
        # the bench gate asserts it.
        self.retries = 0
        self.degradations = {"svd_exact": 0, "svd_unplanned": 0}

    # ------------------------------------------------------------ cost model
    def _bucket_methods(
        self, plan: DecompositionPlan, max_bond: int
    ) -> Tuple[Tuple[str, ...], int]:
        """Per-bucket "svd"/"rsvd" choice and the sketch size.

        The randomized path is meaningful only when the sketch is strictly
        below the bucket's full rank ``min(Rp, Cp)``; under "auto" it must
        also win the flop comparison by ``rsvd_min_gain``x.
        """
        sketch = max_bond + self.rsvd_oversample
        if self.method == "svd":
            return ("svd",) * plan.num_buckets, sketch
        methods = []
        for b in plan.buckets:
            if sketch >= b.kp:
                methods.append("svd")
            elif self.method == "randomized":
                methods.append("rsvd")
            else:  # auto: flop cost model
                full = svd_flop_estimate(b.rp, b.cp)
                rand = _rsvd_flops(b.rp, b.cp, sketch, self.rsvd_power_iters)
                methods.append("rsvd" if rand * self.rsvd_min_gain < full else "svd")
        return tuple(methods), sketch

    def _call_flops(
        self, plan: DecompositionPlan, methods: Tuple[str, ...], sketch: int
    ) -> float:
        total = 0.0
        for b, m in zip(plan.buckets, methods):
            per = (
                _rsvd_flops(b.rp, b.cp, sketch, self.rsvd_power_iters)
                if m == "rsvd"
                else svd_flop_estimate(b.rp, b.cp)
            )
            total += len(b.sectors) * per
        return total

    # ------------------------------------------------------------- jit core
    def _build_core(
        self, plan: DecompositionPlan, absorb: str, methods: Tuple[str, ...], sketch: int
    ):
        """Compile (or wrap eagerly) the shared ``svd_core_body``.

        One compiled executable per bucketed structure — the same
        compile-once trick as ``pad_block_sparse``.
        """
        engine = self
        body = svd_core_body(
            plan, absorb, methods, sketch, self.rsvd_power_iters, self.rsvd_seed
        )
        if not self.jit:
            return body

        def svd_core(blocks):
            engine.jit_retraces += 1  # body runs only when jax (re)traces
            return body(blocks)

        return jax.jit(svd_core)

    def _build_slice_core(self, plan: DecompositionPlan, m_q: Tuple[int, ...]):
        """Compile (or wrap eagerly) the shared ``slice_core_body``.

        The retained counts ``m_q`` are static (they key the compiled
        executable): during convergence they drift and retrace like the
        bucketed matvec, but at structural steady state the truncation
        pattern stabilizes and the whole output assembly — dozens of block
        slices per split — replays as one compiled program instead of one
        dispatch per block.
        """
        engine = self
        body = slice_core_body(plan, m_q)
        if not self.jit:
            return body

        def slice_core(bucket_out):
            engine.jit_retraces += 1
            return body(bucket_out)

        return jax.jit(slice_core)

    # ----------------------------------------------------------------- entry
    def svd_split(
        self,
        theta: BlockSparseTensor,
        n_row_modes: int,
        max_bond: int,
        cutoff: float = 1e-12,
        absorb: str = "right",
    ):
        """Planned blockwise truncated SVD; drop-in for the seed signature.

        Returns ``(U, V, svals_by_sector, trunc_err)`` exactly like
        ``tensor.blocksparse.svd_split_unplanned``; see the module docstring
        for the equality guarantee and tie-break semantics.  ``trunc_err``
        (a host float) is the sum of the squared discarded singular values —
        equal to the squared Frobenius reconstruction error
        ``||theta - U·V||²`` when ``absorb`` is "left" or "right".

        Robustness (DESIGN.md 3.8): a failed attempt — an injected
        ``decomp.svd_fail`` or non-finite singular values at the host sync
        (LAPACK non-convergence comes back as NaN) — retries down the
        documented ladder: randomized → exact batched SVD
        → the seed per-sector loop (``svd_split_unplanned``).  Each rung is
        counted in ``stats()['retries']`` / ``['degradations']``; if the
        final rung still yields non-finite values the input itself is
        poisoned and ``NumericalHealthError`` propagates to the caller.  Any
        other exception — a compile or lowering error — propagates as is.
        """
        if _is_tracing(theta):
            raise TypeError(
                "svd_split needs concrete blocks: the global truncation syncs "
                "singular values to host, so it cannot run under jit tracing"
            )
        with obs.span("split"):
            plan = self.cache.get(theta, n_row_modes)
            methods, sketch = self._bucket_methods(plan, int(max_bond))
            try:
                f = faults.fire("decomp.svd_fail")
                if f is not None:
                    raise FaultInjected("decomp.svd_fail",
                                        "batched SVD did not converge")
                return self._execute_planned(
                    plan, theta, max_bond, cutoff, absorb, methods, sketch
                )
            except (FaultInjected, NumericalHealthError):
                self.retries += 1
                if "rsvd" in methods:
                    # ladder rung 1: drop the randomized sketch, retry exact
                    self.degradations["svd_exact"] += 1
                    try:
                        return self._execute_planned(
                            plan, theta, max_bond, cutoff, absorb,
                            ("svd",) * plan.num_buckets, sketch,
                        )
                    except (FaultInjected, NumericalHealthError):
                        pass
                # ladder rung 2 (final): the seed per-sector loop
                self.degradations["svd_unplanned"] += 1
                from ..tensor.blocksparse import svd_split_unplanned

                U_t, V_t, svals, trunc_err = svd_split_unplanned(
                    theta, n_row_modes, max_bond, cutoff=cutoff, absorb=absorb
                )
                s_all = np.concatenate(
                    [np.asarray(jax.device_get(s)).ravel()
                     for s in svals.values()]
                ) if svals else np.zeros(0)
                if not np.isfinite(s_all).all():
                    raise NumericalHealthError(
                        "non-finite singular values even on the seed path: "
                        "the decomposition input is poisoned",
                        stage="svd",
                    )
                return U_t, V_t, svals, trunc_err

    def _execute_planned(
        self, plan, theta, max_bond, cutoff, absorb, methods, sketch
    ):
        """One planned attempt: core exec + the single sync + slicing."""
        key = (
            absorb if absorb in ("left", "right") else "none",
            methods,
            sketch if "rsvd" in methods else 0,
            self.jit,
            self.rsvd_power_iters,
            self.rsvd_seed,
        )
        with obs.span("split.core"):
            blocks_in = tuple(theta.blocks[k] for k in plan.block_order)
            core = self._svd_core(plan, key, methods, sketch, blocks_in)
            bucket_out, s_cat = core(blocks_in)

        self.svd_calls += 1
        self.svd_flops += self._call_flops(plan, methods, sketch)
        self.sectors_processed += plan.num_sectors
        self.buckets_processed += plan.num_buckets
        self.rsvd_buckets += sum(1 for m in methods if m == "rsvd")

        # ---- the one host sync: all singular values, already masked.  The
        # numerical-health guard rides this existing sync (zero extra device
        # round-trips): non-finite values here mean the SVD input or the
        # decomposition itself went bad, and must not reach the MPS.
        with obs.span("split.read"):
            s_host = np.asarray(jax.device_get(s_cat))
        if not np.isfinite(s_host).all():
            raise NumericalHealthError(
                "non-finite singular values at the truncation sync",
                stage="svd",
            )
        with obs.span("split.slice"):
            return self._truncate_and_slice(
                plan, key, theta, bucket_out, s_host, max_bond, cutoff
            )

    def _svd_core(self, plan, key, methods, sketch, blocks_in):
        """The plan's cached assembly + SVD core for ``key``, built (or
        loaded from the export store) on first use."""
        core = plan._exec.get(key)
        if core is None:
            # export round-trip (dist/persist.py): a primed store replays the
            # core's StableHLO instead of re-tracing the Python body; a cold
            # run with a store attached exports what it builds (best-effort —
            # any failure just re-traces).  Only the jitted path exports.
            store = persist.active_store() if self.jit else None
            ekey = ("svd_core", plan.signature, key)
            if store is not None:
                core = store.load_export(ekey, (blocks_in,))
            if core is None:
                core = self._build_core(plan, key[0], methods, sketch)
                if store is not None:
                    store.save_export(
                        ekey,
                        svd_core_body(
                            plan, key[0], methods, sketch,
                            self.rsvd_power_iters, self.rsvd_seed,
                        ),
                        (blocks_in,),
                    )
            _cache_exec(plan, key, core)
        return core

    def _truncate_and_slice(
        self, plan, key, theta, bucket_out, s_host, max_bond, cutoff
    ):
        """Global truncation on the synced singular values, then the
        retained U columns / V rows as output blocks."""
        k_out = [int(out[1].shape[-1]) for out in bucket_out]
        # global truncation, deterministic tie-break (sector, position)
        m_q, trunc_err = host_truncate(plan, s_host, k_out, max_bond, cutoff)

        # ---- slice the retained columns/rows into output blocks: one
        # compiled call keyed by the kept-count tuple (stable at steady state)
        m_tuple = tuple(int(x) for x in m_q)
        slice_key = ("slice", key, m_tuple)
        slice_core = plan._exec.get(slice_key)
        if slice_core is None:
            store = persist.active_store() if self.jit else None
            ekey = ("svd_slice", plan.signature, key, m_tuple)
            if store is not None:
                slice_core = store.load_export(ekey, (bucket_out,))
            if slice_core is None:
                slice_core = self._build_slice_core(plan, m_tuple)
                if store is not None:
                    store.save_export(
                        ekey, slice_core_body(plan, m_tuple), (bucket_out,)
                    )
            _cache_exec(plan, slice_key, slice_core)
        u_flat, v_flat, s_flat = slice_core(bucket_out)

        new_sectors, u_blocks, v_blocks, svals = [], {}, {}, {}
        ui = vi = si_out = 0
        for si, sec in enumerate(plan.sectors):
            m = m_tuple[si]
            if m == 0:
                continue
            svals[sec.q] = s_flat[si_out]
            si_out += 1
            new_sectors.append((sec.q, m))
            for rk in sec.row_keys:
                u_blocks[(sec.q, rk)] = u_flat[ui]
                ui += 1
            for ck in sec.col_keys:
                v_blocks[(sec.q, ck)] = v_flat[vi]
                vi += 1

        bond_u = Index(tuple(new_sectors), IN, "bond")
        bond_v = Index(tuple(new_sectors), OUT, "bond")
        sector_index = {q: i for i, (q, _) in enumerate(new_sectors)}
        U_t = BlockSparseTensor(
            list(plan.row_ix) + [bond_u],
            {rk + (sector_index[q],): b for (q, rk), b in u_blocks.items()},
            qzero(theta.indices[0].nq),
        )
        V_t = BlockSparseTensor(
            [bond_v] + list(plan.col_ix),
            {(sector_index[q],) + ck: b for (q, ck), b in v_blocks.items()},
            theta.charge,
        )
        return U_t, V_t, svals, trunc_err

    # ------------------------------------------------------------- reporting
    def stats(self) -> Dict:
        """Cumulative decomposition-stage counters.

        - ``plan_cache``: hits/misses/size of the DecompPlanCache.
        - ``svd_calls``: number of ``svd_split`` executions.
        - ``svd_flops``: estimated flops of the executed decompositions
          (LAPACK-gesdd-style counts for exact buckets, sketch+power-GEMM
          counts for randomized ones) — a cost-model estimate, not a
          hardware counter.
        - ``jit_retraces``: times the compiled cores (batched-SVD core and
          output-slice core) were (re)traced; at structural steady state
          this stops growing (compile-once).  Cores are cached on the plan
          and shared across engines using the same cache, so a trace is
          attributed to the engine that first compiled it.
        - ``sectors`` / ``buckets``: cumulative charge sectors decomposed
          and shape buckets executed (buckets ≤ sectors; the gap is the
          batching win).
        - ``rsvd_buckets``: buckets routed to the randomized path.
        - ``retries`` / ``degradations``: failed first attempts and the
          ladder rung that recovered them ("svd_exact": randomized dropped
          for exact, "svd_unplanned": fell back to the seed per-sector
          loop).  Zero on a healthy run (the bench gate asserts this).
        """
        return {
            "plan_cache": self.cache.stats(),
            "svd_calls": self.svd_calls,
            "svd_flops": self.svd_flops,
            "jit_retraces": self.jit_retraces,
            "sectors": self.sectors_processed,
            "buckets": self.buckets_processed,
            "rsvd_buckets": self.rsvd_buckets,
            "retries": self.retries,
            "degradations": dict(self.degradations),
        }


# Default engine behind ``tensor.blocksparse.svd_split`` (module-level so the
# plan cache and compiled cores persist across calls); sweep-owned
# ContractionEngines carry their own DecompositionEngine for per-run stats.
default_decomp_engine = DecompositionEngine()


def svd_split_planned(
    theta: BlockSparseTensor,
    n_row_modes: int,
    max_bond: int,
    cutoff: float = 1e-12,
    absorb: str = "right",
    engine: Optional[DecompositionEngine] = None,
):
    """Functional entry to the planned split (module docstring has the
    guarantees); uses the shared ``default_decomp_engine`` unless given one."""
    return (engine or default_decomp_engine).svd_split(
        theta, n_row_modes, max_bond, cutoff=cutoff, absorb=absorb
    )
