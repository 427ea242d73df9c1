"""Persistent plan + executable store: cold starts at warm-cache speed.

A fresh worker process pays the full plan/trace/compile pipeline on its
first sweep — ~20x a steady-state sweep (benchmarks/bench_dist.json) even
though every artifact it builds is a pure function of block *structure* the
previous worker already derived.  This module persists all three layers of
that pipeline across processes:

1. **Plan tables** (``ContractionPlan`` / ``DecompositionPlan`` /
   ``EnvironmentPlan``): pure Index/numpy metadata, already keyed by
   structural signature in the ``_SignatureLRU`` caches (dist/plan.py).
   ``PlanStore`` maps a canonicalized signature digest to a pickled,
   version-gated entry on disk; the LRU caches consult it on miss and write
   back on build, so a primed store means zero plan builds.
2. **Compiled executables** via the JAX persistent compilation cache:
   ``configure_compilation_cache`` turns it on in the one directory the
   process uses (``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
   ``<checkout>/.jax_cache``), with the entry-size/compile-time floors
   dropped so the many small DMRG cores all qualify.  XLA then skips
   *compilation* of any program it has seen, in any process.
3. **Traced cores** via ``jax.export``: the padded bucket cores (batched
   SVD core, output-slice core, fused env core) are exported to StableHLO
   keyed by (plan signature, core params, operand avals, jax fingerprint).
   A fresh process deserializes and wraps ``exported.call`` in ``jax.jit``
   — skipping the Python re-trace of the core body entirely (layer 2 then
   skips the XLA compile).  Export is strictly best-effort: any failure to
   export, serialize or deserialize is counted and falls back to a plain
   re-trace, never an error.

Store layout (``PlanStore(root)``)::

    root/
      contraction/<digest>.pkl   one entry per canonical plan signature
      decomp/<digest>.pkl
      env/<digest>.pkl
      exports/<digest>.pkl       serialized jax.export artifacts, or
                                 refusal tombstones for unexportable cores

Every entry is written with the ``core/checkpoint.py`` idiom — mkstemp in
the target directory, write, flush, fsync, ``os.replace`` — so concurrent
writers (two workers priming the same store) race atomically: last writer
wins with a complete file, readers never observe a torn entry.

Version + signature gating: each entry records ``PERSIST_VERSION`` and its
canonical signature; a load checks both (and the jax fingerprint, for
exports) and treats any mismatch — or any unpickling error from a
truncated/corrupt file — as a miss, counted in ``stats()``, never a crash.
The store trusts its own directory (entries are pickles): point it only at
paths you would trust a checkpoint from.

Signature canonicalization: ``Index.__eq__``/``__hash__`` exclude the
``name`` field, so two structurally-identical tensors with differently
named indices share one in-memory cache slot.  The on-disk digest must
honor the same contract, so ``canonical_signature`` recursively rewrites
every ``Index`` to its ``(sectors, flow)`` pair before hashing — names can
never fragment (or alias) the store.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import tempfile
import threading
from typing import Any, Dict, Optional, Tuple

from ..tensor.qn import Index
from . import plan as _plan_mod
from .plan import (
    global_decomp_cache,
    global_env_cache,
    global_plan_cache,
)

# Bump on ANY change to plan dataclass layout, signature canonicalization or
# entry schema: old stores are then rejected wholesale (counted as ``stale``)
# and rebuilt, never misread.
PERSIST_VERSION = 1

# subdirectory per plan kind; the kind string is also stored in each entry
# and checked on load, so a digest collision across kinds cannot alias
PLAN_KINDS = ("contraction", "decomp", "env")


def canonical_signature(sig: Any) -> Any:
    """Rewrite a structural signature into its name-free canonical form.

    Recursively maps ``Index -> ("Ix", sectors, flow)`` (dropping ``name``,
    which Index equality already excludes) and preserves tuple structure;
    ints, strings and charges pass through.  Two signatures compare equal
    under the in-memory caches iff their canonical forms are equal, so the
    canonical form is what the store digests and verifies.
    """
    if isinstance(sig, Index):
        return ("Ix", sig.sectors, sig.flow)
    if isinstance(sig, tuple):
        return tuple(canonical_signature(x) for x in sig)
    return sig


def signature_digest(sig: Any) -> str:
    """Stable hex digest of a signature's canonical form (store filename)."""
    canon = canonical_signature(sig)
    return hashlib.sha256(repr(canon).encode()).hexdigest()


def _atomic_write_bytes(path: str, payload: bytes) -> None:
    """The core/checkpoint.py idiom: tmp file in the target dir + rename."""
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def jax_fingerprint() -> Tuple[str, bool, str]:
    """Environment key for exported executables: (jax version, x64, backend).

    An exported StableHLO artifact bakes in dtypes (x64) and lowering
    choices that may shift across jax releases or backends, so exports are
    only replayed in an identical environment; plans (pure numpy) need no
    fingerprint.
    """
    import jax

    return (jax.__version__, bool(jax.config.jax_enable_x64), jax.default_backend())


def _aval_fingerprint(args: Any) -> Any:
    """(shape, dtype) per flattened leaf of the example args.

    Leaves only, no treedef: exports replay only on exact aval match, and
    the caller's structural key already pins the container structure.  (A
    mapped *tree* would reconstruct custom pytree nodes — e.g.
    BlockSparseTensor — whose repr embeds a memory address, making the
    digest process-unstable.)
    """
    import jax

    return tuple(
        (tuple(x.shape), str(x.dtype))
        for x in jax.tree_util.tree_leaves(args)
    )


_pytree_serialization_ready = False


def _ensure_pytree_serialization() -> bool:
    """Register BlockSparseTensor for jax.export treedef serialization.

    Exported artifacts whose in/out trees contain custom pytree nodes can
    only be serialized once the node type is registered; the aux data
    (indices, charge, block keys) is pure metadata, so pickle round-trips
    it.  Idempotent; returns False (export path disabled) if this jax
    version lacks the registration API.
    """
    global _pytree_serialization_ready
    if _pytree_serialization_ready:
        return True
    try:
        from jax import export as jax_export

        from ..tensor.blocksparse import BlockSparseTensor

        jax_export.register_pytree_node_serialization(
            BlockSparseTensor,
            serialized_name="repro.tensor.BlockSparseTensor",
            serialize_auxdata=lambda aux: pickle.dumps(
                aux, protocol=pickle.HIGHEST_PROTOCOL
            ),
            deserialize_auxdata=pickle.loads,
        )
    except ValueError:
        pass  # already registered (e.g. two stores in one process)
    except Exception:
        return False
    _pytree_serialization_ready = True
    return True


class PlanStore:
    """Versioned on-disk store for plan tables and exported cores.

    Thread-safe (one lock guards the counters; file operations are atomic
    on their own) and multi-process-safe (atomic writes, tolerant reads).
    All counters are cumulative per store *instance*; ``stats()`` snapshots
    them.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(os.fspath(root))
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        # plan-entry counters
        self.hits = 0          # entry found, version + signature verified
        self.misses = 0        # no entry on disk
        self.saves = 0         # entries written
        self.corrupt = 0       # unreadable / truncated / wrong-kind entries
        self.stale = 0         # version-mismatch rejections
        # export counters
        self.export_hits = 0
        self.export_misses = 0
        self.export_saves = 0
        self.export_failures = 0   # export/serialize attempts that failed
        self.export_corrupt = 0    # unreadable or mismatched export entries
        self.export_prefetched = 0  # artifacts scheduled by prefetch_exports
        # in-process memo over export entries, keyed by entry path:
        # value is ("fn", full_key, callable) | ("refused", full_key, None),
        # or a Future resolving to one (prefetch_exports).  Serves repeat
        # lookups and refusal tombstones without touching disk again.
        self._memo: Dict[str, Any] = {}

    # ---------------------------------------------------------------- layout
    def _plan_path(self, kind: str, sig: Any) -> str:
        assert kind in PLAN_KINDS, kind
        return os.path.join(self.root, kind, signature_digest(sig) + ".pkl")

    def _export_path(self, key: Any) -> str:
        return os.path.join(self.root, "exports", signature_digest(key) + ".pkl")

    # ----------------------------------------------------------- plan entries
    def load_plan(self, kind: str, sig: Any):
        """Fetch the plan stored for ``sig``, or None (miss/corrupt/stale).

        Never raises on a bad entry: truncated pickles, foreign payloads,
        version or signature mismatches all count and return None — the
        caller rebuilds and (on save) atomically repairs the entry.
        """
        path = self._plan_path(kind, sig)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
        except FileNotFoundError:
            with self._lock:
                self.misses += 1
            return None
        except Exception:
            with self._lock:
                self.corrupt += 1
            return None
        if not isinstance(entry, dict) or entry.get("version") != PERSIST_VERSION:
            with self._lock:
                self.stale += 1
            return None
        if (
            entry.get("kind") != kind
            or entry.get("signature") != canonical_signature(sig)
            or "plan" not in entry
        ):
            with self._lock:
                self.corrupt += 1
            return None
        with self._lock:
            self.hits += 1
        return entry["plan"]

    def save_plan(self, kind: str, sig: Any, plan: Any) -> bool:
        """Atomically persist ``plan`` under ``sig``; False on any IO error.

        Contraction plans get their lazy layouts materialized first (see
        ``ContractionPlan.materialize``): the priming process derives them
        once, loaders never do.
        """
        if hasattr(plan, "materialize"):
            with contextlib.suppress(Exception):
                plan.materialize()
        entry = {
            "version": PERSIST_VERSION,
            "kind": kind,
            "signature": canonical_signature(sig),
            "plan": plan,
        }
        try:
            _atomic_write_bytes(
                self._plan_path(kind, sig),
                pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL),
            )
        except Exception:
            return False
        with self._lock:
            self.saves += 1
        return True

    # --------------------------------------------------------------- exports
    def load_export(self, key: Any, example_args: Any):
        """Deserialize the exported core stored under ``key``, jit-wrapped.

        ``key`` is any picklable structure identifying the core (plan
        signature + core kind + static params); the jax fingerprint and the
        example-arg avals are folded in, so a hit is only possible in an
        identical environment with identical operand shapes.  Returns a
        callable or None; never raises.
        """
        if not _ensure_pytree_serialization():
            with self._lock:
                self.export_misses += 1
            return None
        full_key = (canonical_signature(key), jax_fingerprint(),
                    _aval_fingerprint(example_args))
        path = self._export_path(full_key)
        memo = self._resolve_memo(path)
        if memo is not None and memo[1] == full_key:
            tag, _, fn = memo
            with self._lock:
                if tag == "fn":
                    self.export_hits += 1
                else:  # refusal tombstone: behaves as a miss, but
                    # save_export will skip the doomed re-export
                    self.export_misses += 1
            return fn if tag == "fn" else None
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
        except FileNotFoundError:
            with self._lock:
                self.export_misses += 1
            return None
        except Exception:
            with self._lock:
                self.export_corrupt += 1
            return None
        try:
            if (
                not isinstance(entry, dict)
                or entry.get("version") != PERSIST_VERSION
                or entry.get("key") != full_key
            ):
                raise ValueError("export entry mismatch")
            if entry.get("refused"):
                self._memo[path] = ("refused", full_key, None)
                with self._lock:
                    self.export_misses += 1
                return None
            import jax
            from jax import export as jax_export

            exported = jax_export.deserialize(entry["data"])
            fn = jax.jit(exported.call)
        except Exception:
            with self._lock:
                self.export_corrupt += 1
            return None
        self._memo[path] = ("fn", full_key, fn)
        with self._lock:
            self.export_hits += 1
        return fn

    def _resolve_memo(self, path: str):
        """The memo entry for ``path`` as a resolved tuple, or None.

        Blocks on an in-flight prefetch Future: waiting on the background
        deserialize+compile is still cheaper than redoing it inline.
        """
        m = self._memo.get(path)
        if m is None:
            return None
        if hasattr(m, "result"):
            try:
                m = m.result()
            except Exception:
                m = None
            self._memo[path] = m  # collapse the Future (even to None)
        return m

    def save_export(self, key: Any, fn, example_args: Any) -> bool:
        """Best-effort: export ``fn`` at ``example_args``' avals and persist.

        ``fn`` must be a plain traceable callable (it is jit-wrapped here);
        failures — unexportable programs, serialization errors, IO — are
        counted, never raised.

        Programs containing ``stablehlo.custom_call`` (LAPACK SVD/QR on
        CPU, PRNG kernels) are refused even when jax's own export accepts
        them: on this jax generation a *batched* LAPACK custom call
        deserialized in a fresh process segfaults at execution, so only
        pure-XLA programs (GEMM/gather/reshape cores — the matvec, slice
        and env cores) round-trip.  Refusals count as ``export_failures``;
        the caller re-traces and the persistent compilation cache still
        skips the XLA compile.
        """
        if not _ensure_pytree_serialization():
            with self._lock:
                self.export_failures += 1
            return False
        full_key = (canonical_signature(key), jax_fingerprint(),
                    _aval_fingerprint(example_args))
        path = self._export_path(full_key)
        memo = self._resolve_memo(path)
        if memo is not None and memo[0] == "refused" and memo[1] == full_key:
            # a prior process already proved this core unexportable — the
            # tombstone spares every later process the export + module scan
            with self._lock:
                self.export_failures += 1
            return False
        try:
            import jax
            from jax import export as jax_export

            exported = jax_export.export(jax.jit(fn))(*example_args)
            if "stablehlo.custom_call" in exported.mlir_module():
                entry = {
                    "version": PERSIST_VERSION,
                    "key": full_key,
                    "refused": "custom_call",
                }
                with contextlib.suppress(Exception):
                    _atomic_write_bytes(
                        path,
                        pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL),
                    )
                self._memo[path] = ("refused", full_key, None)
                raise ValueError("custom_call programs do not round-trip")
            entry = {
                "version": PERSIST_VERSION,
                "key": full_key,
                "data": bytes(exported.serialize()),
            }
            _atomic_write_bytes(
                path,
                pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL),
            )
        except Exception:
            with self._lock:
                self.export_failures += 1
            return False
        with self._lock:
            self.export_saves += 1
        return True

    # ------------------------------------------------------------- prefetch
    def prefetch_exports(
        self, *, compile: bool = False, max_workers: int = 4,
        block: bool = False,
    ) -> int:
        """Warm the export memo from disk on background threads.

        Walks ``exports/`` and schedules every entry for deserialization —
        and, with ``compile=True``, AOT compilation at the artifact's own
        recorded avals (``Exported.in_avals``) — on a small thread pool.
        ``load_export`` then finds a ready (or in-flight) callable instead
        of paying deserialize + trace + compile inline, so a fresh worker's
        first sweep overlaps artifact loading with actual solving.

        ``compile=True`` is the warmup half of the cold-start contract: the
        AOT compiles populate the persistent compilation cache with the
        *wrapped-module* executables (distinct cache entries from the
        priming run's own programs), which is exactly what a later worker's
        inline first-use compiles hit.  It is NOT the default because a
        cache-cold compile pass takes minutes of background CPU, and the
        pool's worker threads are joined at interpreter shutdown — fine for
        the blocking warmup driver or a long-lived server, a trap for a
        short-lived CLI process.

        Returns the number of artifacts scheduled (0 if the export layer is
        unavailable); ``block=True`` waits for completion — used by warmup,
        where the point is filling caches, not overlapping work.
        """
        d = os.path.join(self.root, "exports")
        try:
            names = sorted(n for n in os.listdir(d) if n.endswith(".pkl"))
        except FileNotFoundError:
            return 0
        if not names or not _ensure_pytree_serialization():
            return 0
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="plan-store-prefetch"
        )
        n = 0
        for name in names:
            path = os.path.join(d, name)
            if path in self._memo:
                continue
            self._memo[path] = pool.submit(
                self._load_export_entry, path, compile
            )
            n += 1
        pool.shutdown(wait=block)
        with self._lock:
            self.export_prefetched += n
        return n

    def _load_export_entry(self, path: str, compile: bool):
        """Read one export entry: ("fn"|"refused", full_key, callable|None).

        Runs on prefetch threads; returns None on any corrupt, stale or
        foreign-environment entry (``load_export`` then falls back to its
        own tolerant disk path for accurate counters).
        """
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if (
                not isinstance(entry, dict)
                or entry.get("version") != PERSIST_VERSION
                or not isinstance(entry.get("key"), tuple)
                or entry["key"][1] != jax_fingerprint()
            ):
                return None
            if entry.get("refused"):
                return ("refused", entry["key"], None)
            import jax
            import jax.tree_util as jtu
            from jax import export as jax_export

            exported = jax_export.deserialize(entry["data"])
            fn = jax.jit(exported.call)
            if compile:
                sds = [
                    jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in exported.in_avals
                ]
                args, kwargs = jtu.tree_unflatten(exported.in_tree, sds)
                fn = fn.lower(*args, **kwargs).compile()
            return ("fn", entry["key"], fn)
        except Exception:
            return None

    # ------------------------------------------------------------- reporting
    def stats(self) -> Dict[str, Any]:
        """Cumulative store counters.

        ``hits``/``misses``/``saves`` are plan-entry loads that verified /
        found nothing / writes; ``corrupt`` counts unreadable or mismatched
        entries and ``stale`` version-gated rejections (both behave as
        misses).  The ``export_*`` family is the same ledger for
        ``jax.export`` artifacts, plus ``export_failures`` for cores that
        could not be exported in the first place (they fall back to a plain
        re-trace).
        """
        with self._lock:
            return {
                "root": self.root,
                "hits": self.hits,
                "misses": self.misses,
                "saves": self.saves,
                "corrupt": self.corrupt,
                "stale": self.stale,
                "export_hits": self.export_hits,
                "export_misses": self.export_misses,
                "export_saves": self.export_saves,
                "export_failures": self.export_failures,
                "export_corrupt": self.export_corrupt,
                "export_prefetched": self.export_prefetched,
            }


# ------------------------------------------------------------- activation
_active_store: Optional[PlanStore] = None


# the checkout root (src/repro/dist/persist.py -> three levels up from src)
CHECKOUT_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def configure_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and no other
    is set.  Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
    (git-ignored) — a fixed path, so every process of this checkout shares
    one cache.  Either
    way the min-entry-size and min-compile-time floors are dropped so the
    many small DMRG cores all qualify; with jax's defaults (1 second of
    compile time) exactly the executables whose *count* makes cold starts
    slow would be skipped.  Idempotent; safe to call after jax is
    initialized.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILATION_CACHE_DIR
        os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def activate_store(
    store, *, compile_cache: bool = True, prefetch=True
) -> PlanStore:
    """Attach ``store`` (a PlanStore or a path) as the process-wide store.

    Wires it into the three global ``_SignatureLRU`` caches (consulted on
    every miss, written on every build), publishes it to the engines'
    export lookups (``active_store``), and — unless ``compile_cache=False``
    — turns on the JAX persistent compilation cache
    (``configure_compilation_cache``).  ``prefetch`` (default on) kicks off the
    background export warm-up (``prefetch_exports``) so first-use lookups
    find ready artifacts; ``prefetch="compile"`` additionally AOT-compiles
    each artifact in the background — the long-lived-worker mode
    (``DMRGService``) that lands a warmed-up worker's first sweep within
    ~2x of steady state.  It is a no-op on a store with no exports, and
    ``prefetch=False`` keeps activation fully synchronous (tests asserting
    exact disk-read sequencing).  Returns the (possibly constructed) store.
    """
    global _active_store
    if not isinstance(store, PlanStore):
        store = PlanStore(store)
    _active_store = store
    _plan_mod._ACTIVE_STORE = store
    if compile_cache:
        configure_compilation_cache()
    if prefetch:
        store.prefetch_exports(compile=prefetch == "compile")
    return store


def deactivate_store() -> None:
    """Detach the active store (the compilation cache stays configured)."""
    global _active_store
    _active_store = None
    _plan_mod._ACTIVE_STORE = None


def active_store() -> Optional[PlanStore]:
    """The process-wide store engines consult for export round-trips."""
    return _active_store


@contextlib.contextmanager
def using_store(store, *, compile_cache: bool = True, prefetch: bool = True):
    """Scoped ``activate_store``: restores the previous store on exit."""
    prev = _active_store
    s = activate_store(store, compile_cache=compile_cache, prefetch=prefetch)
    try:
        yield s
    finally:
        if prev is None:
            deactivate_store()
        else:
            activate_store(prev, compile_cache=False, prefetch=False)


def store_stats() -> Optional[Dict[str, Any]]:
    """``stats()`` of the active store, or None when none is attached
    (the shape ``repro.dist.cache_stats`` folds in)."""
    return None if _active_store is None else _active_store.stats()


def resolve_store(store) -> Optional[PlanStore]:
    """None | path | PlanStore -> Optional[PlanStore] (drivers' arg coercion)."""
    if store is None or isinstance(store, PlanStore):
        return store
    return PlanStore(store)
