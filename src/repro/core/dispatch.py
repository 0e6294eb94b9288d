"""SpGEMM engine registry, plan/execute dispatch, and batched execution.

The paper's central observation (Table III / Fig. 8) is that no single
SpGEMM strategy wins everywhere: scalar hash accumulation, vectorized
Expand-Sort-Compress, and the SparseZipper merge path trade off by density,
per-row work, and work skew. This module turns the five free functions in
``core/spgemm.py`` into a serving-grade engine layer, split into a
**selection** phase and an **execution** phase:

  * a **registry** of named engines with declared capabilities (jittable,
    returns-stats, batchable, dtype support) — new engines plug in via
    :func:`register_engine`;
  * :func:`plan` — ``plan(A, B, engine="auto")`` resolves everything
    data-dependent about a multiply *before* it runs: the engine (from
    cheap structural features through an overridable heuristic table, a
    cached prior selection, or one-shot measurement with
    ``autotune=True``), the resolved engine kwargs, and the static
    capacities that key the jit cache.  Plans are frozen, hashable, and
    reusable across calls with matching operand structure;
  * :func:`execute` — runs a plan against concrete operands.
    ``spgemm(A, B, ...)`` is exactly ``execute(plan(A, B, ...), A, B)``;
  * an **autotune cache** persisted to disk and keyed by shape/nnz bucket,
    so repeated shapes (the serving steady state) skip re-selection, plus
    an in-process plan memo keyed on operand identity so repeat calls on
    the same matrices skip planning entirely;
  * :func:`plan_batched` / :func:`execute_batched` — the same split for a
    whole :class:`BatchedCSR` batch under one compilation: ``esc`` via a
    vmapped core, ``spz`` via a lock-step driver that packs rows from
    every batch lane into shared fixed-capacity stream groups.
    ``distributed/spgemm_shard.py`` layers work-balanced multi-device
    lane sharding on top of these plans.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import inspect
import json
import math
import os
import tempfile
import threading
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

# NB: ``repro.core.__init__`` binds the engines module under the alias
# ``spgemm_engines`` *before* importing this module, then re-exports
# ``dispatch.spgemm`` under the package-level name ``spgemm`` — so the
# alias (not ``from repro.core import spgemm``) is the stable way to
# reach the module once the package is initialized.
from repro.core import spgemm as sg
from repro.core import trace
from repro.core.formats import (BatchedCSR, CSR, batch_csr, csr_from_coo,
                                csr_to_numpy, validate_operands)
from repro.kernels import backend as kb
from repro.runtime import faultinject as fi

try:  # best-effort file locking for the autotune-cache flush
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX platforms
    fcntl = None


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """A registered SpGEMM engine and its declared capabilities.

    ``fn(A, B, **kw)`` returns a CSR, or ``(CSR, stats)`` when
    ``returns_stats``. ``jittable`` engines lower to one XLA computation
    with static capacities; ``batchable`` engines additionally support the
    single-compilation :func:`spgemm_batched` path; ``backend_aware``
    engines take a ``backend=`` kernel-backend kwarg (resolved once at
    plan time from the registry in ``kernels/backend.py``)."""

    name: str
    fn: Callable
    jittable: bool = False
    returns_stats: bool = False
    batchable: bool = False
    measure: bool = True  # candidate for autotune measurement
    backend_aware: bool = False
    dtypes: tuple = ("float32",)
    description: str = ""


_REGISTRY: dict[str, EngineSpec] = {}


def register_engine(name: str, fn: Callable, **caps) -> EngineSpec:
    """Register (or replace) an engine under ``name``; see EngineSpec."""
    spec = EngineSpec(name=name, fn=fn, **caps)
    _REGISTRY[name] = spec
    return spec


def get_engine(name: str) -> EngineSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def available_engines() -> dict[str, EngineSpec]:
    """Snapshot of the registry (name -> spec)."""
    return dict(_REGISTRY)


register_engine("scl-array", sg.spgemm_scl_array,
                description="scalar row loop, dense accumulator row (oracle)")
register_engine("scl-hash", sg.spgemm_scl_hash,
                description="scalar row loop, hash-style unique/accumulate")
register_engine("esc", sg.spgemm_esc, jittable=True, batchable=True,
                description="vectorized Expand-Sort-Compress (vec-radix)")
register_engine("spz", lambda A, B, **kw: sg.spgemm_spz(A, B, **kw),
                jittable=True, returns_stats=True, batchable=True,
                backend_aware=True,
                description="SparseZipper chunked stream sort + zip-merge "
                            "(device-resident fused driver by default)")
register_engine("spz-fused",
                lambda A, B, **kw: sg.spgemm_spz(A, B, driver="fused", **kw),
                jittable=True, returns_stats=True, batchable=True,
                measure=False,  # byte-identical to "spz": don't time it twice
                backend_aware=True,
                description="spz with the device-resident pipeline pinned: "
                            "expand/sort/zip-merge tree under one jit per "
                            "(N, L, R) bucket")
register_engine("spz-host",
                lambda A, B, **kw: sg.spgemm_spz(A, B, driver="host", **kw),
                returns_stats=True, batchable=True, measure=False,
                backend_aware=True,
                description="spz with the lock-step host driver (one kernel "
                            "issue per chunk; stats-faithful Fig. 9-11 path; "
                            "never wins a measurement, so autotune skips it)")
register_engine("spz-rsort",
                lambda A, B, **kw: sg.spgemm_spz(A, B, rsort=True, **kw),
                jittable=True, returns_stats=True, batchable=True,
                backend_aware=True,
                description="spz with rows pre-sorted by per-row work")


# ---------------------------------------------------------------------------
# features + heuristic table
# ---------------------------------------------------------------------------

class _OperandMemo:
    """Bounded memo keyed on operand identity + a request discriminator.

    Serving repeats the same matrix objects call after call, and
    ``BENCH_dispatch.json`` shows the selection work (``work_stats``
    recompute, cache lookups) dominating auto-dispatch (``select_us``).
    The key is the operands' buffer ``id()`` + shape + nnz + ``extra``
    (the feature group, or the full plan request); entries pin the index
    buffers so an id cannot be recycled while its entry lives, and an
    ``is`` check on hit guards against lookups racing a rebuild.  One
    instance memoizes feature dicts, another whole ExecutionPlans.
    Access is lock-guarded: async serving plans concurrent flushes from
    executor threads against these module-level memos."""

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self._mu = threading.Lock()
        self._entries: collections.OrderedDict = collections.OrderedDict()

    @staticmethod
    def _key(A: CSR, B: CSR, extra):
        return (id(A.indices), id(B.indices), A.shape, B.shape,
                int(np.asarray(A.indptr)[-1]), int(np.asarray(B.indptr)[-1]),
                extra)

    def get(self, A: CSR, B: CSR, extra) -> Optional[Any]:
        key = self._key(A, B, extra)
        with self._mu:
            hit = self._entries.get(key)
            if hit is not None and hit[1] is A.indices \
                    and hit[2] is B.indices:
                self._entries.move_to_end(key)
                self.hits += 1
                return hit[0]
            self.misses += 1
            return None

    def put(self, A: CSR, B: CSR, extra, value) -> None:
        with self._mu:
            self._entries[self._key(A, B, extra)] = (value, A.indices,
                                                     B.indices)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
        self.hits = self.misses = 0


_feature_cache = _OperandMemo()
_plan_memo = _OperandMemo()


def clear_feature_cache() -> None:
    """Drop memoized features and plans (benchmarks measure cold selection)."""
    _feature_cache.clear()
    _plan_memo.clear()


def extract_features(A: CSR, B: CSR, group: int = 16) -> dict:
    """Cheap structural features driving engine choice (Table III columns).

    Memoized on the operands' buffer identity/shape/nnz so repeat calls
    on the same matrices (the serving steady state) skip the recompute."""
    feats = _feature_cache.get(A, B, group)
    if feats is None:
        feats = sg.work_stats(A, B, group=group)
        _feature_cache.put(A, B, group, feats)
    return dict(feats)  # callers may mutate their copy, not the cache


@dataclasses.dataclass(frozen=True)
class HeuristicRule:
    """First matching rule wins; ``predicate`` maps a feature dict to bool."""

    name: str
    predicate: Callable[[dict], bool]
    engine: str


# Ordered density-regime table (paper §V-B intuition):
#   tiny total work      -> scalar hash: vectorized setup cost dominates;
#   dense / heavy rows   -> esc: expansion+radix amortizes, one XLA graph;
#   high work skew       -> spz-rsort: work-sorted rows fix lock-step
#                           imbalance (Fig. 9);
#   everything else      -> spz merge path (duplicates drop out early).
DEFAULT_HEURISTICS: tuple[HeuristicRule, ...] = (
    HeuristicRule("tiny-work", lambda f: f["total_work"] < 2048
                  and f["density"] < 2e-3, "scl-hash"),
    HeuristicRule("dense", lambda f: f["density"] >= 1.5e-2
                  or f["avg_work_per_row"] >= 128.0, "esc"),
    HeuristicRule("skewed", lambda f: f["work_var_per_group"] >= 1.0,
                  "spz-rsort"),
    HeuristicRule("default", lambda f: True, "spz"),
)


def choose_engine(feats: dict,
                  rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
                  ) -> tuple[str, str]:
    """Return (engine_name, rule_name) for a feature dict."""
    for rule in rules:
        if rule.predicate(feats):
            return rule.engine, rule.name
    raise ValueError("no heuristic rule matched (missing default rule?)")


# ---------------------------------------------------------------------------
# persistent autotune cache
# ---------------------------------------------------------------------------

def _nnz_bucket(m: CSR) -> int:
    """log2 bucket of true nnz — shapes in the same bucket share a plan."""
    return int(np.asarray(m.indptr)[-1]).bit_length()


def cache_key(A: CSR, B: CSR, backend: Optional[str] = None) -> str:
    """Shape/nnz bucket key, extended with the *requested* kernel backend
    so an explicitly pinned backend autotunes its own bucket (a "pallas"
    measurement must never serve an "xla" request, and vice versa).
    ``"auto"`` requests keep the bare key — the default bucket, whose
    entries may record the backend an autotune sweep picked."""
    key = (f"{A.n_rows}x{A.n_cols}@{_nnz_bucket(A)}"
           f"*{B.n_rows}x{B.n_cols}@{_nnz_bucket(B)}")
    return key if backend in (None, "auto") else f"{key}|bk={backend}"


# quarantine records ride in the same JSON file under a reserved key
# prefix (shape keys are "<rows>x<cols>@..." strings, so no collision)
_QUAR_PREFIX = "!quarantine:"

# the cache file's schema record (same reserved "!" namespace).  v1 files
# (no record) held winner-only selection entries and TTL-less quarantine
# records; v2 adds per-candidate timing vectors + feature dicts on
# autotune entries and per-combo quarantine timestamps/strike counts.
# Old entries are MIGRATED forward on load, never dropped: a winner-only
# v1 entry is a perfectly good v2 entry without a timing vector.
_SCHEMA_KEY = "!schema"
SCHEMA_VERSION = 2


def combo_str(engine: str, backend: Optional[str]) -> str:
    """The canonical "engine|backend" id shared by quarantine records,
    timing vectors, and the dispatch model's candidate space ("" for a
    backend-less engine)."""
    return f"{engine}|{backend or ''}"


def split_combo(combo: str) -> tuple[str, Optional[str]]:
    engine, _, backend = combo.partition("|")
    return engine, (backend or None)

# returned by AutotuneCache._lock_file when a live holder kept the lock
# past the bounded acquire window (distinct from None = "no locking")
_LOCK_TIMEOUT = object()


class AutotuneCache:
    """Disk-backed map cache_key -> {engine, source[, backend]}.

    ``source`` records how the entry was made: "heuristic" entries are
    upgraded in place by a later ``autotune=True`` call; "autotune" entries
    are sticky.  ``backend`` (optional) records the winning kernel backend
    for backend-aware engines.  Default path: ``$REPRO_AUTOTUNE_CACHE`` or
    ``~/.cache/repro/spgemm_autotune.json``.

    Robustness (shared by concurrent serving processes): a corrupt or
    truncated file is moved aside to ``<path>.corrupt`` and the cache
    starts empty instead of crashing; writes go to a unique tempfile and
    are published with an atomic rename, so readers never observe a
    partial file; and every flush re-reads and merges the current
    on-disk entries (an "autotune" entry from another process is never
    downgraded by this process's "heuristic" one) under a best-effort
    ``fcntl`` file lock (``<path>.lock``) that serializes the
    read-merge-write critical section across processes — on platforms
    without ``fcntl`` the lock is a no-op and the merge falls back to
    the previous shrunk-loss-window behaviour, where a dropped entry
    only costs a re-measurement, never correctness.  The lock acquire
    is *bounded* (``lock_timeout_s``, default 0.5s or
    ``$REPRO_AUTOTUNE_LOCK_TIMEOUT_S``): a hung — not dead — lock
    holder costs a skipped flush, never a stalled serving process.

    Cross-process propagation protocol (the multi-process serving
    substrate): **push on quarantine** — ``quarantine()`` flushes
    immediately, so a combo poisoned by one worker process lands on
    disk right away, not at process exit; **pull on plan miss** —
    ``plan()``/``plan_batched()`` call :meth:`refresh` before giving up
    on a cache miss, so a fresh bucket picks up selections and poison
    other processes pushed since this process loaded the file.  Net
    effect: a kernel crash observed in one process is routed around by
    every process within one flush interval."""

    def __init__(self, path: Optional[str] = None, *,
                 lock_timeout_s: Optional[float] = None,
                 quarantine_ttl_s: Optional[float] = None,
                 clock: Callable[[], float] = time.time):
        self.path = path or os.environ.get(
            "REPRO_AUTOTUNE_CACHE",
            os.path.join(os.path.expanduser("~"), ".cache", "repro",
                         "spgemm_autotune.json"))
        self._entries: Optional[dict] = None
        # bumped whenever a memoized plan may have been invalidated
        # (autotune upgrades, clears, pulled quarantines) — keyed into
        # the plan memo
        self.version = 0
        # serializes in-process access (async flush threads share one
        # cache object); the fcntl file lock covers cross-process
        self._mu = threading.RLock()
        if lock_timeout_s is None:
            lock_timeout_s = float(os.environ.get(
                "REPRO_AUTOTUNE_LOCK_TIMEOUT_S", "0.5"))
        self.lock_timeout_s = lock_timeout_s
        if quarantine_ttl_s is None:
            quarantine_ttl_s = float(os.environ.get(
                "REPRO_QUARANTINE_TTL_S", "3600"))
        self.quarantine_ttl_s = quarantine_ttl_s
        self.clock = clock
        # (st_mtime_ns, st_size, st_ino) of the last disk state we
        # parsed — lets refresh() skip the JSON re-parse when nothing
        # was flushed since (the plan-miss pull runs per miss)
        self._disk_stat: Optional[tuple] = None
        # schema version of the file as loaded (pre-migration), for
        # inspection tools; None until the file is first read
        self.loaded_schema_version: Optional[int] = None

    def _migrate(self, data: dict) -> dict:
        """Normalize entries from any prior schema version in place.

        Migration is strictly additive — a version bump must never
        discard winner entries another (older) process wrote:
          * selection entries (winner-only v1 or timing-vectored v2)
            pass through unchanged — absent ``timings``/``features``
            just means "no replayable measurement for this bucket";
          * v1 quarantine records carry no per-combo timestamps; they
            are stamped *now* so a combo poisoned before TTLs existed
            gets one full TTL from this load instead of being poisoned
            forever (the exact failure the TTL exists to fix)."""
        now = float(self.clock())
        for k, v in data.items():
            if not k.startswith(_QUAR_PREFIX):
                continue
            ts = v.setdefault("ts", {})
            for combo in v.get("combos", ()):
                ts.setdefault(combo, now)
        return data

    def _read_disk(self) -> Optional[dict]:
        """Parse + migrate the on-disk file; {} when missing, None when
        corrupt.  Records the file's stat identity for refresh()."""
        try:
            with open(self.path) as f:
                st = os.fstat(f.fileno())
                data = json.load(f)
        except FileNotFoundError:
            self._disk_stat = None
            return {}
        except (OSError, ValueError):
            return None
        if not isinstance(data, dict):
            return None
        self._disk_stat = (st.st_mtime_ns, st.st_size, st.st_ino)
        schema = data.pop(_SCHEMA_KEY, None)
        self.loaded_schema_version = int(schema.get("version", 1)) \
            if isinstance(schema, dict) else 1
        return self._migrate(
            {k: v for k, v in data.items() if isinstance(v, dict)})

    def _load(self) -> dict:
        if self._entries is None:
            disk = self._read_disk()
            if disk is None:
                # corrupted/truncated: preserve the evidence, start empty
                try:
                    os.replace(self.path, self.path + ".corrupt")
                except OSError:
                    pass
                disk = {}
            self._entries = disk
        return self._entries

    def get(self, key: str) -> Optional[dict]:
        with self._mu:
            return self._load().get(key)

    def put(self, key: str, engine: str, source: str,
            backend: Optional[str] = None, *,
            timings: Optional[dict] = None,
            features: Optional[dict] = None) -> None:
        """Record a selection; autotune sweeps additionally log the FULL
        per-candidate timing vector (``timings``: combo string ->
        seconds) and the feature dict that drove it — the replayable
        dataset the learned dispatch model trains on."""
        with self._mu:
            entry: dict[str, Any] = {"engine": engine, "source": source}
            if backend is not None:
                entry["backend"] = backend
            if timings:
                entry["timings"] = {k: float(v) for k, v in timings.items()}
            if features:
                entry["features"] = {k: (float(v) if isinstance(v, float)
                                         else int(v))
                                     for k, v in features.items()}
            self._load()[key] = entry
            if source == "autotune":
                self.version += 1
            self._flush()

    def entries(self) -> dict:
        """Snapshot of every record (selections + ``!quarantine:`` keys)
        — the offline-training dataset export and the inspection surface
        for ``tools/dump_autotune.py``."""
        with self._mu:
            return {k: dict(v) for k, v in self._load().items()}

    # -- quarantine: poisoned (engine, backend) combos per shape bucket --

    @staticmethod
    def _combo(engine: str, backend: Optional[str]) -> str:
        return combo_str(engine, backend)

    def _quarantine_ttl(self, q: dict, combo: str) -> float:
        """Effective TTL for a combo: the base TTL doubled per strike
        (a combo that keeps crashing on re-probe earns exponentially
        longer quarantines, capped at 16x) — the re-probe budget."""
        strikes = int(q.get("strikes", {}).get(combo, 1))
        return self.quarantine_ttl_s * min(2.0 ** (strikes - 1), 16.0)

    def _quarantine_active(self, q: dict, combo: str) -> bool:
        """Whether a combo is currently poisoned (listed and unexpired).

        An expired combo is *re-admitted*: dropped from the active list
        (its strike count survives, so a re-crash re-quarantines it for
        longer) lazily here rather than by a sweeper.  The removal is
        in-memory only — the next flush persists it; until then other
        processes run their own expiry clocks."""
        if combo not in q.get("combos", ()):
            return False
        ts = q.get("ts", {}).get(combo)
        if ts is None:  # unmigrated record mid-merge: stamp, stay active
            q.setdefault("ts", {})[combo] = float(self.clock())
            return True
        if float(self.clock()) - float(ts) < self._quarantine_ttl(q, combo):
            return True
        q["combos"] = [c for c in q["combos"] if c != combo]
        q.get("ts", {}).pop(combo, None)
        return False

    def quarantine(self, key: str, engine: str,
                   backend: Optional[str] = None,
                   reason: str = "") -> None:
        """Mark (engine, backend) poisoned for this shape bucket.

        A kernel that crashes (or returns garbage) for a bucket must not
        be re-selected on the next plan: quarantined combos are skipped
        by cache hits, heuristic selection, and autotune sweeps.  With
        ``backend=None`` the engine is poisoned for every backend.

        Poison is NOT forever: each combo carries a timestamp and the
        quarantine expires after ``quarantine_ttl_s`` (doubled per
        repeat offense), so a transiently-crashing combo — an OOM spike,
        a half-installed kernel build — is re-probed instead of being
        routed around for the life of the cache file."""
        with self._mu:
            entries = self._load()
            qk = _QUAR_PREFIX + key
            q = entries.setdefault(qk, {"combos": []})
            combo = self._combo(engine, backend)
            if combo not in q["combos"]:
                q["combos"].append(combo)
            q.setdefault("ts", {})[combo] = float(self.clock())
            strikes = q.setdefault("strikes", {})
            strikes[combo] = int(strikes.get(combo, 0)) + 1
            if reason:
                q.setdefault("reasons", {})[combo] = reason
            # a selection entry routing to the poisoned combo is dropped
            # so the next plan re-selects among healthy candidates
            sel = entries.get(key)
            if sel is not None and sel.get("engine") == engine and \
                    backend in (None, sel.get("backend")):
                entries.pop(key)
            self.version += 1  # invalidate memoized plans
            self._flush()

    def is_quarantined(self, key: str, engine: str,
                       backend: Optional[str] = None) -> bool:
        with self._mu:
            q = self._load().get(_QUAR_PREFIX + key)
            if not q:
                return False
            return (self._quarantine_active(q, self._combo(engine, backend))
                    or self._quarantine_active(q, self._combo(engine, None)))

    def quarantined(self, key: str) -> list[tuple[str, Optional[str]]]:
        """The (engine, backend) combos actively quarantined for a
        bucket (expired combos are re-admitted, not listed)."""
        with self._mu:
            q = self._load().get(_QUAR_PREFIX + key, {})
            return [(c.split("|", 1)[0], c.split("|", 1)[1] or None)
                    for c in list(q.get("combos", ()))
                    if self._quarantine_active(q, c)]

    def _lock_file(self):
        """Open + exclusively lock ``<path>.lock``.

        Returns the locked file object, ``None`` when locking is
        unavailable (no ``fcntl``, open failure — the unlocked merge
        proceeds), or the :data:`_LOCK_TIMEOUT` sentinel when a live
        holder kept the lock past ``lock_timeout_s`` — the caller skips
        the flush entirely rather than stalling the serving process
        behind a hung peer.  flock serializes the flush's
        read-merge-write across processes (and across cache objects in
        one process — each open is its own file description).  Purely
        best-effort: any failure degrades to a skipped or unlocked
        merge, never to a failed multiply."""
        if fcntl is None:
            return None
        try:
            f = open(self.path + ".lock", "a")
        except OSError:
            return None
        deadline = time.monotonic() + max(0.0, self.lock_timeout_s)
        while True:
            try:
                fcntl.flock(f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
                return f
            except OSError:
                if time.monotonic() >= deadline:
                    try:
                        f.close()
                    except OSError:
                        pass
                    return _LOCK_TIMEOUT
                time.sleep(0.01)

    def _merge_from(self, disk: dict) -> bool:
        """Merge on-disk entries into memory; True when anything changed.

        Entries concurrent processes flushed since we loaded are kept;
        their measured plans beat our heuristics (quarantine records
        merge by union — a combo poisoned by any process stays
        poisoned).  After the merge, selections routing to poisoned
        combos are swept: the merge may have resurrected a selection
        this process just quarantined (its stale disk entry merged back
        in), or pulled in a selection another process has since
        poisoned."""
        changed = False
        for k, v in disk.items():
            ours = self._entries.get(k)
            if k.startswith(_QUAR_PREFIX):
                if ours is None:
                    self._entries[k] = v
                    changed = True
                else:
                    for c in v.get("combos", ()):
                        if c not in ours["combos"]:
                            ours["combos"].append(c)
                            changed = True
                    # timestamps merge by max (the most recent poisoning
                    # wins the TTL clock), strike counts by max
                    for fld in ("ts", "strikes"):
                        theirs = v.get(fld, {})
                        mine = ours.setdefault(fld, {})
                        for c, val in theirs.items():
                            if float(val) > float(mine.get(c, -math.inf)):
                                mine[c] = val
                                changed = True
                continue
            if ours is None or (v.get("source") == "autotune"
                                and ours.get("source") != "autotune"):
                if ours != v:
                    self._entries[k] = v
                    changed = True
            elif ours.get("source") == v.get("source"):
                # same-rank entries: union in the dataset fields a peer
                # recorded that we lack (its sweep logged timings, ours
                # was a bare winner) — measurements are never discarded
                for fld in ("timings", "features"):
                    if fld in v and fld not in ours:
                        ours[fld] = v[fld]
                        changed = True
                # ... including per-combo timing points a peer's sweep
                # measured for candidates ours skipped (quarantine or
                # backend availability differ across processes)
                theirs_t = v.get("timings")
                ours_t = ours.get("timings")
                if theirs_t and ours_t:
                    for c, t in theirs_t.items():
                        if c not in ours_t:
                            ours_t[c] = t
                            changed = True
        for qk, q in list(self._entries.items()):
            if not qk.startswith(_QUAR_PREFIX):
                continue
            sk = qk[len(_QUAR_PREFIX):]
            sel = self._entries.get(sk)
            if sel is None:
                continue
            eng = sel.get("engine", "")
            if (self._quarantine_active(q, self._combo(eng,
                                                       sel.get("backend")))
                    or self._quarantine_active(q, self._combo(eng, None))):
                self._entries.pop(sk, None)
                changed = True
        return changed

    def refresh(self) -> bool:
        """Pull entries other processes flushed since our last read.

        The "pull" half of the cross-process propagation protocol:
        called on a plan-cache miss (and available to supervisors on
        worker-loss events), it merges the current on-disk state into
        memory without writing anything back.  Bumps :attr:`version`
        when the merge changed anything, so memoized plans built on the
        stale view are invalidated.  Returns whether anything changed."""
        with self._mu:
            if self._entries is None:
                self._load()
                return True
            # stat short-circuit: the pull runs on EVERY plan-cache miss
            # (model-based selection makes misses the common case for
            # fresh buckets), so an unchanged file must cost a stat, not
            # a JSON parse
            try:
                st = os.stat(self.path)
                if self._disk_stat == (st.st_mtime_ns, st.st_size,
                                       st.st_ino):
                    return False
            except OSError:
                pass
            disk = self._read_disk()
            if not disk:
                return False
            changed = self._merge_from(disk)
            if changed:
                self.version += 1
            return changed

    def _flush(self, *, merge: bool = True) -> None:
        with self._mu:
            self._flush_locked(merge=merge)

    def _flush_locked(self, *, merge: bool = True) -> None:
        # merge=False writes the in-memory view verbatim — maintenance
        # rewrites (compact --drop-timings) that must NOT re-union the
        # on-disk dataset fields they just stripped
        tmp = None
        lock = None
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            lock = self._lock_file()
            if lock is _LOCK_TIMEOUT:
                # a hung (not dead) holder: skip this flush — the
                # entries stay in memory and the next flush retries;
                # a skipped write costs a re-measurement, a stall
                # costs the serving process
                lock = None
                return
            fi.fire("autotune.flush", path=self.path)
            if merge:
                self._merge_from(self._read_disk() or {})
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(self.path) or ".",
                prefix=os.path.basename(self.path) + ".tmp.")
            payload = {_SCHEMA_KEY: {"version": SCHEMA_VERSION},
                       **self._entries}
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=0, sort_keys=True)
            os.replace(tmp, self.path)
            try:
                st = os.stat(self.path)
                self._disk_stat = (st.st_mtime_ns, st.st_size, st.st_ino)
            except OSError:
                self._disk_stat = None
        except Exception:
            # cache is an optimization; never fail the multiply over it
            # (OSError, a scribbled-on file, or an injected write fault)
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        finally:
            if lock is not None:
                try:
                    fcntl.flock(lock.fileno(), fcntl.LOCK_UN)
                    lock.close()
                except OSError:
                    pass

    def clear(self) -> None:
        """Drop all entries, in memory and on disk (no merge-back)."""
        with self._mu:
            self._entries = {}
            self._disk_stat = None
            self.version += 1
            try:
                os.unlink(self.path)
            except OSError:
                pass

    def __len__(self) -> int:
        with self._mu:
            return len(self._load())


_default_cache: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache()
    return _default_cache


# ---------------------------------------------------------------------------
# learned cost-model selection (models/dispatch_model.py artifacts)
# ---------------------------------------------------------------------------

# the model artifact lives NEXT TO the cache file it was trained from:
# the cache is the dataset, the model is its fitted view, and serving
# processes that share the cache path automatically share the model
MODEL_SUFFIX = ".model.json"


def model_path_for(cache: AutotuneCache) -> str:
    """Default on-disk path of the dispatch model trained from ``cache``."""
    return cache.path + MODEL_SUFFIX


_model_mu = threading.Lock()
# path -> (mtime_ns, model-or-None): a retrained artifact (new mtime) is
# picked up on the next plan without a restart; a corrupt one caches as
# None so selection does not re-parse it per plan
_model_memo: dict[str, tuple[int, Any]] = {}


def _artifact_mtime_ns(path: str) -> Optional[int]:
    try:
        return os.stat(path).st_mtime_ns
    except OSError:
        return None


def resolve_model(model, cache: AutotuneCache):
    """Resolve plan()'s ``model`` request to a DispatchModel or None.

    ``"auto"`` loads (and memoizes, keyed on file mtime) the artifact
    next to the cache file — absent or unreadable artifacts resolve to
    None and selection falls through to measurement/heuristics; a
    DispatchModel instance is used as-is; False/None disables."""
    if model in (False, None):
        return None
    if model != "auto":  # an explicit DispatchModel (tests, notebooks)
        return model
    path = model_path_for(cache)
    mtime = _artifact_mtime_ns(path)
    if mtime is None:
        return None
    with _model_mu:
        hit = _model_memo.get(path)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    from repro.models import dispatch_model as dm
    try:
        loaded = dm.DispatchModel.load(path)
    except Exception:
        # a corrupt/foreign artifact must never fail a plan
        loaded = None
    with _model_mu:
        _model_memo[path] = (mtime, loaded)
    return loaded


def _model_token(model, cache: AutotuneCache) -> Optional[tuple]:
    """Hashable identity of the model a plan would consult — keyed into
    the plan memo so a retrained artifact invalidates memoized plans."""
    if model in (False, None):
        return None
    if model != "auto":
        return ("obj", id(model))
    return ("file", _artifact_mtime_ns(model_path_for(cache)))


def _model_candidates(key: str, backend: str,
                      cache: AutotuneCache) -> set:
    """Combo strings ("engine|backend") legal for this request: every
    measurable registry candidate minus quarantined combos.  A pinned
    backend restricts backend-aware engines to it, exactly like the
    autotune sweep's candidate list.

    One ``quarantined()`` snapshot instead of per-combo
    ``is_quarantined`` checks: this runs on the plan hot path and each
    check is a lock round-trip."""
    poisoned = {combo_str(e, b) for e, b in cache.quarantined(key)}
    allowed = set()
    for name, bk_name in _measure_candidates(backend):
        c = combo_str(name, bk_name)
        # an engine-wide quarantine (backend=None) poisons every backend
        if c in poisoned or combo_str(name, None) in poisoned:
            continue
        allowed.add(c)
    return allowed


def _model_select(model, feats: dict, key: str, backend: str,
                  cache: AutotuneCache):
    """One model-based selection attempt; None when the model abstains
    (no healthy candidate it knows, or a prediction failure)."""
    if model is None:
        return None
    try:
        return model.select(feats,
                            allowed=_model_candidates(key, backend, cache))
    except Exception:
        return None  # a broken model must never fail a plan


def _measure(spec: EngineSpec, A: CSR, B: CSR, repeat: int = 1,
             backend: Optional[str] = None) -> float:
    kw = {"backend": backend} if backend is not None else {}
    best = math.inf
    for _ in range(repeat):
        fi.fire("dispatch.measure", engine=spec.name, backend=backend)
        t0 = time.perf_counter()
        out = spec.fn(A, B, **kw)
        if spec.returns_stats:
            out = out[0]
        jax.block_until_ready(out.data)
        best = min(best, time.perf_counter() - t0)
    return best


_measure_cands_memo: dict[tuple, list] = {}


def _measure_candidates(backend: str) -> list[tuple[str, Optional[str]]]:
    """(engine, backend) pairs autotune times.  With ``backend="auto"``
    the backend becomes part of the search space: every backend-aware
    engine is measured once per kernel backend measurable on this host
    (``kb.measurable_backends()`` — off-TPU that excludes the
    interpret-mode pallas tier), so a TPU shape bucket can settle on
    e.g. ``spz-fused/pallas`` over ``spz-fused/xla``.  A pinned backend
    is measured as-is.

    Memoized on the (engine, backend) registry contents — this also
    runs per model-assisted plan, where rebuilding the backend list
    would be measurable overhead; registering an engine or backend
    invalidates naturally through the fingerprint key."""
    fp = (backend,
          tuple((n, s.measure, s.backend_aware)
                for n, s in _REGISTRY.items()),
          tuple(sorted((b.name, b.measure, b.needs_tpu_for_perf)
                       for b in kb.available_backends().values())))
    hit = _measure_cands_memo.get(fp)
    if hit is not None:
        return hit
    cands: list[tuple[str, Optional[str]]] = []
    for name, spec in _REGISTRY.items():
        if not spec.measure:
            continue
        if not spec.backend_aware:
            cands.append((name, None))
        elif backend == "auto":
            cands.extend((name, bk.name)
                         for bk in kb.measurable_backends())
        else:
            cands.append((name, kb.resolve_backend(backend).name))
    if len(_measure_cands_memo) > 32:  # registry churn: bound staleness
        _measure_cands_memo.clear()
    _measure_cands_memo[fp] = cands
    return cands


# ---------------------------------------------------------------------------
# plan / execute dispatch
# ---------------------------------------------------------------------------

def _filter_kwargs(fn: Callable, kw: dict) -> dict:
    """Keep only kwargs ``fn`` accepts (everything, if it takes **kw).

    Auto-selection may route to any engine, so engine-specific kwargs
    (e.g. spz's ``R``) must not crash a plan that picked a different
    engine; explicitly named engines still get strict kwargs.  Runs once
    at *plan* time — execution never re-inspects signatures."""
    try:
        params = inspect.signature(fn).parameters.values()
    except (TypeError, ValueError):
        return kw
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        return kw
    names = {p.name for p in params}
    return {k: v for k, v in kw.items() if k in names}


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything selection decides about a multiply, frozen and hashable.

    A plan captures the engine choice, the kwargs resolved against that
    engine's signature, and the static-capacity facts (shapes, nnz work
    bucket, batch lane count) that determine which compiled XLA
    computation execution lands on — ``jit_key`` is that identity, so
    two plans with equal ``jit_key`` reuse one compilation.  Plans are
    inspectable (the serving layer logs ``engine``/``source`` per
    flush), reusable across calls whose operands match the planned
    structure, and cacheable by hash."""

    engine: str                 # resolved engine (post fallback remap)
    batched: bool               # single CSR pair vs BatchedCSR lanes
    a_shape: tuple
    b_shape: tuple
    kwargs: tuple               # sorted (name, value) pairs, plan-resolved
    work_bucket: tuple          # (nnz bucket A, nnz bucket B) — jit-relevant
    cache_key: str              # autotune-cache key the selection used
    source: str    # "explicit" | "heuristic" | "cache" | "autotune" | "model"
    rule: Optional[str] = None  # heuristic rule that fired (source="heuristic")
    batch: Optional[int] = None  # lane capacity (batched plans only)
    backend: Optional[str] = None  # resolved kernel backend (aware engines)

    @property
    def kwargs_dict(self) -> dict:
        return dict(self.kwargs)

    @property
    def jit_key(self) -> tuple:
        """Static identity of the compiled computation this plan routes
        to: engine + kernel backend + operand structure + resolved
        static capacities."""
        return (self.engine, self.backend, self.batched, self.batch,
                self.a_shape, self.b_shape, self.work_bucket, self.kwargs)


def _sorted_kwargs(kw: dict) -> tuple:
    return tuple(sorted(kw.items()))


def _plan_backend_name(engine: str, backend: str) -> Optional[str]:
    """The backend name a plan for ``engine`` would resolve ``backend``
    to — for quarantine checks *before* the plan is built.  None for
    non-backend-aware engines or unknown requests."""
    spec = _REGISTRY.get(engine)
    if spec is None or not spec.backend_aware:
        return None
    try:
        return kb.resolve_backend(backend).name
    except ValueError:
        return None


def _dequarantine(selected: str, key: str, backend: str,
                  cache: "AutotuneCache") -> tuple[str, bool]:
    """If the selected engine is quarantined for this bucket, walk the
    degradation order to the first healthy engine.  Returns
    (engine, was_remapped)."""
    if not cache.is_quarantined(key, selected,
                                _plan_backend_name(selected, backend)):
        return selected, False
    for eng, _ in DEGRADE_CHAIN:
        if eng != selected and not cache.is_quarantined(
                key, eng, _plan_backend_name(eng, backend)):
            return eng, True
    return selected, False  # everything poisoned: keep the original pick


def _resolve_plan_backend(spec: EngineSpec, backend: str,
                          cached: Optional[str], kw: dict, *,
                          strict: bool = True) -> tuple[Optional[str], dict]:
    """Fold the kernel backend into an engine's plan-time kwargs.

    Backend-aware engines get ``kwargs["backend"] = <resolved name>``
    (cache/autotune outcome beats the "auto" default; an explicit pin
    always wins); other engines carry no backend.  Requesting a pinned
    backend for an explicitly named engine that cannot use one is a
    planning error; under auto selection (``strict=False``) the pin is
    simply irrelevant to a non-aware winner and is dropped.

    A ``cached`` backend name comes from the shared on-disk cache and is
    NOT trusted blindly: an unknown name (version skew, hand-edited
    file) or one that only performs on TPU (an entry recorded on a TPU
    host, replayed on a CPU serving host, would otherwise route every
    multiply through Pallas interpret mode) falls back to the "auto"
    default — a cache hit must never raise or degrade execution."""
    if not spec.backend_aware:
        if backend != "auto" and strict:
            raise ValueError(
                f"engine {spec.name!r} does not take a kernel backend "
                f"(requested {backend!r})")
        return None, kw
    name = None
    if backend == "auto" and cached is not None:
        try:
            bk_c = kb.resolve_backend(cached)
            if kb.on_tpu() or not bk_c.needs_tpu_for_perf:
                name = bk_c.name
        except ValueError:
            pass
    if name is None:
        name = kb.resolve_backend(backend).name
    kw = dict(kw)
    kw["backend"] = name
    return name, kw


def plan(A: CSR, B: CSR, engine: str = "auto", *,
         backend: str = "auto",
         autotune: bool = False,
         cache: Optional[AutotuneCache] = None,
         rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
         model: Any = "auto",
         **kw) -> ExecutionPlan:
    """Select an engine and resolve kwargs for ``A @ B`` without running it.

    engine:  a registered name, or "auto" to select by cached plan /
             heuristic features / measurement.
    backend: kernel-backend request for the stream primitives — a name
             registered in ``kernels/backend.py`` ("xla", "pallas",
             "ref") or "auto".  Resolved HERE, once: the chosen backend
             rides in the plan's kwargs/``jit_key`` and suffixes the
             autotune-cache key, so a pinned backend autotunes its own
             bucket and with "auto" the backend joins the autotune
             search space (e.g. ``spz-fused/xla`` vs
             ``spz-fused/pallas`` per shape bucket).
    autotune: with engine="auto", time every registered engine (and, for
             backend-aware engines, every measurable backend) on this
             input once and cache the winner for the shape/nnz bucket.
    cache:   AutotuneCache override (default: process-wide disk cache).
             Non-default ``rules`` bypass the cache entirely — a cached
             plan from other rules must not shadow the caller's table,
             nor may a custom-rule choice poison the shared cache.
    model:   learned-selection request.  "auto" (default) consults the
             trained dispatch model artifact next to the cache file, if
             one exists; a DispatchModel instance uses it directly;
             False/None disables learned selection.  The model sits
             between cache-hit and measurement in the ladder: a
             confident prediction plans immediately (``source="model"``)
             at ~µs cost, a low-confidence one falls through to
             measurement (``autotune=True``) or heuristics.

    Repeat plans on the *same matrix objects* (the serving steady state)
    are memoized on operand identity and skip selection entirely."""
    if A.n_cols != B.n_rows:
        raise ValueError(f"inner dims differ: {A.shape} @ {B.shape}")
    kb.resolve_backend(backend)  # validate the request up front
    use_cache = rules is DEFAULT_HEURISTICS
    if cache is None:  # NB: `or` would drop an *empty* caller cache
        cache = default_cache()
    memo_extra = None
    if engine == "auto" and use_cache and cache is default_cache():
        try:
            memo_extra = ("plan", backend, autotune, cache.version,
                          _model_token(model, cache), _sorted_kwargs(kw))
            hit = _plan_memo.get(A, B, memo_extra)
            if hit is not None:
                return hit
        except TypeError:  # unhashable kwarg value: skip the memo
            memo_extra = None
    # structural screen sits behind the memo: repeat plans on validated
    # operands (the serving steady state) skip the O(nnz) host checks
    validate_operands(A, B)
    key = cache_key(A, B, backend=backend)
    selected, source, rule, sel_bk = engine, "explicit", None, None
    if engine == "auto":
        hit = cache.get(key) if use_cache else None
        if hit is None and use_cache:
            # pull-on-plan-miss: another process may have measured (or
            # poisoned) this bucket since we loaded the file — one
            # cheap disk read here beats re-measuring or re-crashing
            cache.refresh()
            hit = cache.get(key)
        if hit is not None and cache.is_quarantined(
                key, hit["engine"], hit.get("backend")):
            hit = None  # a poisoned prior selection must not be replayed
        if hit is not None and (hit["source"] == "autotune" or not autotune):
            selected, source = hit["engine"], "cache"
            sel_bk = hit.get("backend")
        else:
            # learned-model step of the ladder: cache miss → ask the
            # trained cost model for an argmin over predicted runtimes.
            # A confident prediction plans right here at ~µs cost; a
            # low-confidence one (or no artifact) falls through to
            # measurement / heuristics exactly as before.
            sel = None
            if use_cache:
                mdl = resolve_model(model, cache)
                sel = _model_select(mdl, extract_features(A, B), key,
                                    backend, cache)
                if sel is not None and not sel.confident:
                    sel = None
            if sel is not None:
                selected, sel_bk, source = sel.engine, sel.backend, "model"
            elif autotune:
                timings: dict[tuple, float] = {}
                for name, bk_name in _measure_candidates(backend):
                    if cache.is_quarantined(key, name, bk_name):
                        continue
                    try:
                        timings[(name, bk_name)] = _measure(
                            get_engine(name), A, B, backend=bk_name)
                    except Exception as e:
                        # a candidate that dies mid-sweep is quarantined
                        # and the sweep continues — one crashing kernel
                        # must not abort measurement of the healthy
                        # candidates
                        cache.quarantine(key, name, bk_name,
                                         reason=f"{type(e).__name__}: {e}")
                if timings:
                    (selected, sel_bk), source = \
                        min(timings, key=timings.get), "autotune"
                    # the winner is the cached plan; the full timing
                    # vector + features are the training dataset the
                    # dispatch model is fitted from offline
                    cache.put(key, selected, "autotune", backend=sel_bk,
                              timings={combo_str(n, b): t
                                       for (n, b), t in timings.items()},
                              features=extract_features(A, B))
                else:  # nothing measurable survived: heuristic fallback
                    selected, rule = choose_engine(extract_features(A, B),
                                                   rules)
                    selected, _ = _dequarantine(selected, key, backend,
                                                cache)
                    source = "heuristic"
            else:
                selected, rule = choose_engine(extract_features(A, B), rules)
                source = "heuristic"
                if use_cache:
                    remapped, was_q = _dequarantine(selected, key, backend,
                                                    cache)
                    if was_q:
                        selected, rule = remapped, "quarantine-fallback"
                    cache.put(key, selected, "heuristic")
    spec = get_engine(selected)
    resolved = _filter_kwargs(spec.fn, kw) if engine == "auto" else kw
    plan_bk, resolved = _resolve_plan_backend(spec, backend, sel_bk,
                                              resolved,
                                              strict=engine != "auto")
    p = ExecutionPlan(engine=selected, batched=False,
                      a_shape=A.shape, b_shape=B.shape,
                      kwargs=_sorted_kwargs(resolved),
                      work_bucket=(_nnz_bucket(A), _nnz_bucket(B)),
                      cache_key=key, source=source, rule=rule,
                      backend=plan_bk)
    if memo_extra is not None:
        _plan_memo.put(A, B, memo_extra, p)
    return p


def execute(p: ExecutionPlan, A: CSR, B: CSR, *,
            return_stats: bool = False):
    """Run a plan against concrete operands.

    The operands must match the planned structure (shapes; the nnz
    bucket may drift within the plan's padding capacities).  A plan made
    once can be executed against every request with matching structure —
    the selection cost is paid at plan time only."""
    if p.batched:
        raise ValueError("batched plan passed to execute(); "
                         "use execute_batched()")
    if A.shape != p.a_shape or B.shape != p.b_shape:
        raise ValueError(
            f"plan/operand mismatch: planned {p.a_shape} @ {p.b_shape}, "
            f"got {A.shape} @ {B.shape}")
    spec = get_engine(p.engine)
    fi.fire("dispatch.execute", engine=p.engine, backend=p.backend)
    with trace.span(trace.ENGINE, engine=p.engine, backend=str(p.backend),
                    lanes=1):
        out = spec.fn(A, B, **p.kwargs_dict)
    out, stats = out if spec.returns_stats else (out, None)
    out = fi.corrupt("dispatch.execute", out,
                     engine=p.engine, backend=p.backend)
    return (out, stats) if return_stats else out


# ---------------------------------------------------------------------------
# failure policies: deadline + retry + graceful degradation
# ---------------------------------------------------------------------------

# The degradation ladder (the serving analogue of the RISC-V SpGEMM
# fallback-to-scalar path): planned engine/backend first, then the
# device-resident zipper pipeline pinned to the XLA kernel tier, then
# the dense-accumulator reference oracle — slower every step, but each
# step removes a class of failure (autotuned exotic kernels, Pallas
# lowering, vectorized streaming) until only plain per-row accumulation
# remains.
DEGRADE_CHAIN: tuple[tuple[str, Optional[str]], ...] = (
    ("spz-fused", "xla"),
    ("esc", None),
    ("scl-array", None),
)


class CorruptOutput(RuntimeError):
    """An engine returned structurally invalid output (non-finite values
    or out-of-range indices) without raising — e.g. a kernel that
    silently produced garbage.  The resilience layer treats this exactly
    like a crash: retry, then degrade."""


class DeadlineExceeded(RuntimeError):
    """A resilient execution ran past its per-request deadline."""


class ExhaustedFallbacks(RuntimeError):
    """Every tier of the degradation ladder failed; ``report`` carries
    the per-attempt error trail."""

    def __init__(self, message: str, report: "ExecutionReport"):
        self.report = report
        super().__init__(message)


def check_result(out: CSR) -> None:
    """Structural screen of an engine's output: non-finite payloads or
    out-of-range column indices raise :class:`CorruptOutput` so the
    degradation ladder treats silent garbage as a failed attempt rather
    than serving it."""
    indptr = np.asarray(out.indptr)
    nnz = int(indptr[-1])
    if nnz == 0:
        return
    data = np.asarray(out.data)[:nnz]
    if not np.isfinite(data).all():
        raise CorruptOutput(f"non-finite values in output ({nnz} nnz)")
    idx = np.asarray(out.indices)[:nnz]
    if int(idx.min()) < 0 or int(idx.max()) >= out.n_cols:
        raise CorruptOutput(
            f"output column index out of range [0, {out.n_cols})")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Failure policy for the resilient execute path.

    max_attempts:   attempts per tier (first try included).
    backoff_base_s / backoff_factor: deterministic exponential backoff
                    between same-tier retries (no jitter — chaos tests
                    assert exact schedules).
    deadline_s:     total budget measured on ``clock`` from the first
                    attempt; None disables the deadline.
    fallback:       (engine, backend) tiers walked after the planned
                    tier exhausts its retries (``DEGRADE_CHAIN``).
    verify_output:  run :func:`check_result` on every result so silent
                    garbage counts as a failure.
    sleep / clock:  injectable for deterministic tests."""

    max_attempts: int = 3
    backoff_base_s: float = 0.005
    backoff_factor: float = 4.0
    deadline_s: Optional[float] = None
    fallback: tuple = DEGRADE_CHAIN
    verify_output: bool = True
    sleep: Callable[[float], None] = time.sleep
    clock: Callable[[], float] = time.monotonic

    def backoff_s(self, retry: int) -> float:
        """Backoff before retry number ``retry`` (1-based)."""
        return self.backoff_base_s * self.backoff_factor ** (retry - 1)


@dataclasses.dataclass
class ExecutionReport:
    """What actually served a resilient execution: the tier, the attempt
    count, and the error trail that got it there."""

    tier: int                    # 0 = the planned engine/backend
    engine: str
    backend: Optional[str]
    attempts: int                # total attempts across all tiers
    errors: list = dataclasses.field(default_factory=list)
    quarantined: list = dataclasses.field(default_factory=list)

    @property
    def degraded(self) -> bool:
        return self.tier > 0

    @property
    def tier_label(self) -> str:
        if self.tier == 0:
            return "planned"
        bk = f"/{self.backend}" if self.backend else ""
        return f"degraded:{self.engine}{bk}"


def fallback_plan(p: ExecutionPlan, engine: str,
                  backend: Optional[str]) -> ExecutionPlan:
    """Re-target a plan at a degradation tier: same operand structure,
    fallback engine/backend, kwargs re-filtered against the new engine's
    signature."""
    spec = get_engine(engine)
    kw = {k: v for k, v in p.kwargs_dict.items() if k != "backend"}
    kw = _filter_kwargs(spec.fn, kw)
    bk = None
    if spec.backend_aware:
        bk = kb.resolve_backend(backend or "auto").name
        kw["backend"] = bk
    return dataclasses.replace(p, engine=engine, backend=bk,
                               kwargs=_sorted_kwargs(kw),
                               source="fallback", rule=None)


def execute_resilient(p: ExecutionPlan, A: CSR, B: CSR, *,
                      policy: Optional[RetryPolicy] = None,
                      cache: Optional[AutotuneCache] = None,
                      return_stats: bool = False):
    """Run a plan under the failure policy: bounded same-tier retries
    with exponential backoff, a per-request deadline, and graceful
    degradation down :data:`DEGRADE_CHAIN`.

    Returns ``(result, report)`` (or ``((result, stats), report)`` with
    ``return_stats``); the report records which tier actually served.
    A tier that exhausts its retries has its (engine, backend, bucket)
    combo quarantined in the autotune cache so the next plan for this
    bucket does not re-select the crashing kernel.  Raises
    :class:`ExhaustedFallbacks` when every tier fails, or
    :class:`DeadlineExceeded` when the budget runs out first."""
    policy = policy or RetryPolicy()
    if cache is None:
        cache = default_cache()
    start = policy.clock()
    tiers: list[tuple[str, Optional[str]]] = [(p.engine, p.backend)]
    for eng, bk in policy.fallback:
        if (eng, bk) != tiers[0]:
            tiers.append((eng, bk))
    report = ExecutionReport(tier=0, engine=p.engine, backend=p.backend,
                             attempts=0)

    def out_of_time() -> bool:
        return (policy.deadline_s is not None
                and policy.clock() - start >= policy.deadline_s)

    for tier_i, (eng, bk) in enumerate(tiers):
        tp = p if tier_i == 0 else fallback_plan(p, eng, bk)
        report.tier, report.engine, report.backend = tier_i, eng, tp.backend
        for attempt in range(1, policy.max_attempts + 1):
            if out_of_time():
                raise DeadlineExceeded(
                    f"deadline {policy.deadline_s}s exceeded after "
                    f"{report.attempts} attempts "
                    f"(errors: {report.errors})")
            report.attempts += 1
            try:
                out = execute(tp, A, B, return_stats=return_stats)
                if policy.verify_output:
                    check_result(out[0] if return_stats else out)
                return out, report
            except Exception as e:
                report.errors.append(
                    f"{tp.engine}/{tp.backend or '-'}#{attempt}: "
                    f"{type(e).__name__}: {e}")
                if attempt < policy.max_attempts and not out_of_time():
                    policy.sleep(policy.backoff_s(attempt))
        # tier exhausted: poison this combo for the bucket so replanning
        # does not walk straight back into the crashing kernel
        cache.quarantine(p.cache_key, eng, tp.backend,
                         reason=report.errors[-1])
        report.quarantined.append((eng, tp.backend))
    raise ExhaustedFallbacks(
        f"all {len(tiers)} tiers failed after {report.attempts} attempts "
        f"(errors: {report.errors})", report)


def spgemm(A: CSR, B: CSR, engine: str = "auto", *,
           backend: str = "auto",
           autotune: bool = False,
           cache: Optional[AutotuneCache] = None,
           rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
           model: Any = "auto",
           return_stats: bool = False,
           **kw):
    """Multiply two padded CSR matrices through the engine registry.

    Exactly ``execute(plan(A, B, ...), A, B)`` — see :func:`plan` for
    the selection knobs (including the plan-time kernel-backend
    resolution) and :func:`execute` for the run semantics."""
    p = plan(A, B, engine, backend=backend, autotune=autotune, cache=cache,
             rules=rules, model=model, **kw)
    return execute(p, A, B, return_stats=return_stats)


def explain(A: CSR, B: CSR,
            rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS, *,
            backend: str = "auto",
            cache: Optional[AutotuneCache] = None,
            model: Any = "auto") -> dict:
    """Dry-run selection: features + the rule and engine 'auto' would pick
    (ignoring any cached *engine* plan) — for benchmarks and debugging.

    The dict also surfaces the kernel-backend leg of the decision, which
    an ``ExecutionPlan`` resolves but selection output previously hid:

    ``backend``
        the kernel backend a plan for this (engine, request) would run —
        an autotuned backend recorded for the bucket (e.g. the
        ``spz-fused/pallas`` vs ``/xla`` winner) beats the "auto"
        default, exactly as in :func:`plan`; ``None`` for engines that
        take no kernel backend.
    ``rule``
        the heuristic rule that picked the engine.
    ``model``
        the learned-dispatch view of the same request, when a trained
        model resolves: predicted winner, calibrated confidence, whether
        that clears the confidence floor (i.e. whether ``plan()`` would
        take the prediction), per-candidate predicted costs in seconds,
        and the artifact version.  ``None`` when no model is available.
    """
    feats = extract_features(A, B)
    engine, rule = choose_engine(feats, rules)
    key = cache_key(A, B, backend=backend)
    if cache is None:
        cache = default_cache()
    hit = cache.get(key)
    cached_bk = hit.get("backend") if hit else None
    plan_bk, _ = _resolve_plan_backend(get_engine(engine), backend,
                                       cached_bk, {}, strict=False)
    mdl = resolve_model(model, cache)
    sel = _model_select(mdl, feats, key, backend, cache)
    model_info = None
    if sel is not None:
        model_info = {"engine": sel.engine, "backend": sel.backend,
                      "confidence": sel.confidence,
                      "confident": sel.confident,
                      "costs": dict(sel.costs),
                      "version": getattr(mdl, "version", None)}
    return {"engine": engine, "rule": rule, "backend": plan_bk,
            "features": feats, "cache_key": key, "model": model_info}


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------

# vmapped unjitted ESC core, jitted once over the whole batch: every lane
# shares the static (cap_products, n_rows, n_cols) plan.
_esc_batched_core = jax.jit(
    jax.vmap(sg.esc_core_impl,
             in_axes=(0, 0, 0, 0, 0, 0, None, None, None)),
    static_argnums=(6, 7, 8))


def _pow2_at_least(n: int) -> int:
    return 1 << max(4, int(n - 1).bit_length())


def _esc_batched(A: BatchedCSR, B: BatchedCSR,
                 cap_products: Optional[int] = None) -> list:
    """One-compilation ESC over a batch: shared power-of-two product
    capacity so ragged batches of similar size reuse the same XLA plan."""
    fi.fire("kernel.batched", engine="esc", lanes=A.batch)
    if cap_products is None:
        works = [int(sg.row_work(a, B[i]).sum()) for i, a in A.lanes()]
        cap_products = _pow2_at_least(max(works + [1]))
    r, c, v, valid, _ = _esc_batched_core(
        A.indptr, A.indices, A.data, B.indptr, B.indices, B.data,
        cap_products, A.n_rows, B.n_cols)
    r, c, v, valid = map(np.asarray, (r, c, v, valid))
    lane_ok = np.asarray(A.valid) & np.asarray(B.valid)
    return [csr_from_coo(r[i][valid[i]], c[i][valid[i]], v[i][valid[i]],
                         (A.n_rows, B.n_cols)) if lane_ok[i] else None
            for i in range(A.batch)]


def _spz_batched(A: BatchedCSR, B: BatchedCSR, *, R: int = 16,
                 S: Optional[int] = None, rsort: bool = False,
                 backend="auto", driver: str = "fused") -> list:
    """Batched SparseZipper driver: rows from *every* valid lane are packed
    into shared lock-step groups of S streams.  The default "fused" driver
    feeds each group through the device-resident expand/sort/merge-tree
    pipeline straight from the stacked BatchedCSR arrays (per-stream lane
    ids index the batch axis); ``driver="host"`` keeps the original
    chunk-at-a-time lock-step loop."""
    S = S or 32 * R
    if driver not in ("fused", "host"):
        raise ValueError(f"unknown spz driver {driver!r}; use 'fused'|'host'")
    fi.fire("kernel.batched", engine="spz", driver=driver, lanes=A.batch)
    bk = kb.resolve_backend(backend)  # unknown names raise, listing all
    stats = sg.SpzStats()
    lane_ok = np.asarray(A.valid) & np.asarray(B.valid)
    valid_lanes = [i for i in range(A.batch) if lane_ok[i]]
    with trace.span(trace.SPZ_PREP,
                    rows=len(valid_lanes) * A.n_rows) as span:
        items = [(i, int(r)) for i in valid_lanes for r in range(A.n_rows)]
        # only the host driver walks per-lane numpy copies; the fused
        # driver reads the stacked device arrays directly
        lanes = ({i: (csr_to_numpy(A[i]), csr_to_numpy(B[i]))
                  for i in valid_lanes} if driver == "host" else None)
        work = None
        if rsort or driver == "fused":
            work = {i: sg.row_work(A[i], B[i]) for i in valid_lanes}
            span.set_metadata(products=int(sum(w.sum()
                                               for w in work.values())))
        if rsort:
            items.sort(key=lambda it: int(work[it[0]][it[1]]))
        if driver == "fused":
            mats = sg.fused_operands(A.indptr, A.indices, A.data,
                                     B.indptr, B.indices, B.data)
        else:
            out_k = {it: np.empty(0, np.int32) for it in items}
            out_v = {it: np.empty(0, np.float32) for it in items}
    with trace.span(trace.SPZ_GROUPS, groups=-(-len(items) // S)):
        if driver == "fused":
            runs: list = []
            for g0 in range(0, len(items), S):
                group = items[g0:g0 + S]
                plens = np.array([work[ln][r] for ln, r in group], np.int64)
                sg.fused_process_group(group, plens, mats, R, bk, stats,
                                       runs)
        else:
            for g0 in range(0, len(items), S):
                group = items[g0:g0 + S]
                products = []
                for lane, row in group:
                    (a_indptr, a_idx, a_val), (b_indptr, b_idx, b_val) = \
                        lanes[lane]
                    products.extend(sg.expand_group(
                        [row], a_indptr, a_idx, a_val, b_indptr, b_idx,
                        b_val))
                parts = sg.sort_phase(products, R, len(group), bk, stats,
                                      cap_s=S)
                final = sg.merge_tree_host(parts, R, bk, stats, cap_s=S)
                if final is not None:
                    Kf, Vf, lf = final
                    for s, it in enumerate(group):
                        out_k[it] = Kf[s, :lf[s]]
                        out_v[it] = Vf[s, :lf[s]]
    if driver == "fused":
        outs = sg._runs_to_csrs(runs, (A.n_rows, B.n_cols), A.batch)
        return [c if lane_ok[i] else None for i, c in enumerate(outs)]
    with trace.span(trace.SPZ_ASSEMBLE) as span:
        results, nnz_out = [], 0
        for i in range(A.batch):
            if not lane_ok[i]:
                results.append(None)
                continue
            rr, cc, vv = [], [], []
            for row in range(A.n_rows):
                k, v = out_k[(i, row)], out_v[(i, row)]
                nz = v != 0.0
                rr.append(np.full(int(nz.sum()), row, np.int64))
                cc.append(k[nz])
                vv.append(v[nz])
            cols = np.concatenate(cc) if cc else []
            nnz_out += len(cols)
            results.append(csr_from_coo(
                np.concatenate(rr) if rr else [], cols,
                np.concatenate(vv) if vv else [], (A.n_rows, B.n_cols)))
        span.set_metadata(nnz_out=nnz_out)
    return results


# auto selection for batches maps any single-matrix choice onto the nearest
# batchable engine (the scalar engines have no single-compilation path)
_BATCH_FALLBACK = {"scl-array": "esc", "scl-hash": "esc"}

# batched drivers per engine — every batchable registry entry routes here
_BATCH_DRIVERS: dict[str, Callable] = {
    "esc": _esc_batched,
    "spz": _spz_batched,
    "spz-fused": functools.partial(_spz_batched, driver="fused"),
    "spz-host": functools.partial(_spz_batched, driver="host"),
    "spz-rsort": functools.partial(_spz_batched, rsort=True),
}


def get_batch_driver(name: str) -> Callable:
    """The batched driver callable for a (batchable) engine name — used by
    the lane-sharding layer to run one device group at a time."""
    try:
        return _BATCH_DRIVERS[name]
    except KeyError:
        raise ValueError(f"engine {name!r} has no batched driver") from None


def check_batch(A: BatchedCSR, B: BatchedCSR) -> np.ndarray:
    if A.batch != B.batch or A.n_cols != B.n_rows:
        raise ValueError(f"batch mismatch: {A.batch}x{A.shape} @ "
                         f"{B.batch}x{B.shape}")
    lane_ok = np.asarray(A.valid) & np.asarray(B.valid)
    if not lane_ok.any():
        raise ValueError("no valid lanes in batch")
    return lane_ok


def plan_batched(A: BatchedCSR, B: BatchedCSR, engine: str = "auto", *,
                 backend: str = "auto",
                 cache: Optional[AutotuneCache] = None,
                 rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
                 model: Any = "auto",
                 lane_work_hint: Optional[Sequence[int]] = None,
                 **kw) -> ExecutionPlan:
    """Select a batchable engine and resolve static capacities for a batch.

    engine: "esc", "spz", "spz-rsort", or "auto" (features of the
    heaviest valid lane pick the engine — consulting and feeding the
    same autotune cache as the single-matrix path, keyed on that lane —
    then map onto a batchable one).  The resolved plan carries the
    shared product capacity (esc) or stream geometry (spz) so identical
    request structures reuse one compilation.

    backend: kernel-backend request, resolved at plan time exactly like
    the single-pair :func:`plan` (the spz batch drivers are
    backend-aware; the cache key carries the request).

    lane_work_hint: per-lane total row_work, if the caller already
    computed it (the sharding layer does, for lane balancing) — skips
    the recompute when sizing the esc product capacity."""
    check_batch(A, B)
    kb.resolve_backend(backend)  # validate the request up front
    i_heavy = max((i for i, _ in A.lanes()),
                  key=lambda i: int(np.asarray(A[i].indptr)[-1]))
    key = cache_key(A[i_heavy], B[i_heavy], backend=backend)
    selected, source, rule, sel_bk = engine, "explicit", None, None
    if engine == "auto":
        use_cache = rules is DEFAULT_HEURISTICS
        if cache is None:
            cache = default_cache()
        hit = cache.get(key) if use_cache else None
        if hit is None and use_cache:
            # pull-on-plan-miss (see plan()): pick up selections and
            # quarantines flushed by sibling worker processes
            cache.refresh()
            hit = cache.get(key)
        if hit is not None and cache.is_quarantined(
                key, hit["engine"], hit.get("backend")):
            hit = None  # a poisoned prior selection must not be replayed
        if hit is not None:
            selected, source = hit["engine"], "cache"
            sel_bk = hit.get("backend")
        else:
            # same model step as plan(): a confident learned prediction
            # (on the heaviest lane's features) beats the rules table;
            # the selection flows through _BATCH_FALLBACK below exactly
            # like every other source
            sel = None
            if use_cache:
                mdl = resolve_model(model, cache)
                sel = _model_select(
                    mdl, extract_features(A[i_heavy], B[i_heavy]), key,
                    backend, cache)
                if sel is not None and not sel.confident:
                    sel = None
            if sel is not None:
                selected, sel_bk, source = sel.engine, sel.backend, "model"
            else:
                selected, rule = choose_engine(
                    extract_features(A[i_heavy], B[i_heavy]), rules)
                source = "heuristic"
                if use_cache:
                    remapped_q, was_q = _dequarantine(
                        _BATCH_FALLBACK.get(selected, selected), key,
                        backend, cache)
                    if was_q:
                        selected, rule = remapped_q, "quarantine-fallback"
                    cache.put(key, selected, "heuristic")
    remapped = _BATCH_FALLBACK.get(selected, selected)
    spec = get_engine(remapped)
    if not spec.batchable or remapped not in _BATCH_DRIVERS:
        raise ValueError(f"engine {remapped!r} has no batched path")
    driver = _BATCH_DRIVERS[remapped]
    # auto selection / fallback remap may land on any driver: drop kwargs
    # it can't take (explicitly named engines keep strict kwargs)
    if engine == "auto" or remapped != engine:
        kw = _filter_kwargs(driver, kw)
    if remapped == "esc" and kw.get("cap_products") is None:
        # shared power-of-two product capacity, resolved at plan time so
        # the plan's jit_key fully determines the compiled computation
        works = ([int(w) for w in lane_work_hint]
                 if lane_work_hint is not None else
                 [int(sg.row_work(a, B[i]).sum()) for i, a in A.lanes()])
        kw["cap_products"] = _pow2_at_least(max(works + [1]))
    plan_bk, kw = _resolve_plan_backend(spec, backend, sel_bk, kw,
                                        strict=engine != "auto")
    return ExecutionPlan(engine=remapped, batched=True, batch=A.batch,
                         a_shape=A.shape, b_shape=B.shape,
                         kwargs=_sorted_kwargs(kw),
                         work_bucket=(_nnz_bucket(A[i_heavy]),
                                      _nnz_bucket(B[i_heavy])),
                         cache_key=key, source=source, rule=rule,
                         backend=plan_bk)


def assemble_batched(outs: list, A: BatchedCSR, B: BatchedCSR) -> BatchedCSR:
    """Stack per-lane results (None = invalid lane) into the output
    BatchedCSR whose lane capacity is the max output nnz."""
    with trace.span(trace.SHARD_ASSEMBLE, lanes=len(outs)):
        empty = csr_from_coo([], [], [], (A.n_rows, B.n_cols))
        cap = max(int(np.asarray(o.indptr)[-1]) for o in outs
                  if o is not None)
        batched = batch_csr([o if o is not None else empty for o in outs],
                            nnz_cap=max(cap, 1))
    return BatchedCSR(batched.indptr, batched.indices, batched.data,
                      jnp.asarray(A.valid) & jnp.asarray(B.valid),
                      batched.shape)


def execute_batched(p: ExecutionPlan, A: BatchedCSR,
                    B: BatchedCSR) -> BatchedCSR:
    """Run a batched plan. Invalid lanes pass through as empty matrices
    with ``valid=False``."""
    if not p.batched:
        raise ValueError("single-pair plan passed to execute_batched(); "
                         "use execute()")
    check_batch(A, B)
    if A.shape != p.a_shape or B.shape != p.b_shape or A.batch != p.batch:
        raise ValueError(
            f"plan/operand mismatch: planned {p.batch}x{p.a_shape} @ "
            f"{p.b_shape}, got {A.batch}x{A.shape} @ {B.shape}")
    fi.fire("dispatch.execute_batched", engine=p.engine, backend=p.backend)
    with trace.span(trace.ENGINE, engine=p.engine, backend=str(p.backend),
                    lanes=A.batch):
        outs = _BATCH_DRIVERS[p.engine](A, B, **p.kwargs_dict)
    outs = fi.corrupt("dispatch.execute_batched", outs,
                      engine=p.engine, backend=p.backend)
    return assemble_batched(outs, A, B)


def spgemm_batched(A: BatchedCSR, B: BatchedCSR, engine: str = "auto", *,
                   cache: Optional[AutotuneCache] = None,
                   rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
                   model: Any = "auto",
                   **kw) -> BatchedCSR:
    """Multiply a batch of same-shape CSR pairs under one compilation.

    Exactly ``execute_batched(plan_batched(A, B, ...), A, B)``; see
    those for selection and execution semantics."""
    p = plan_batched(A, B, engine, cache=cache, rules=rules, model=model,
                     **kw)
    return execute_batched(p, A, B)


# ---------------------------------------------------------------------------
# compile-ahead plan warming (the serving layer's warm pool)
# ---------------------------------------------------------------------------

_warm_mu = threading.Lock()
_warmed_jit_keys: set = set()
_warm_counters = {"warmed": 0, "hits": 0, "misses": 0}


def note_warmed(jit_key: tuple) -> None:
    """Record a jit identity as compile-warmed in *this* process."""
    with _warm_mu:
        _warmed_jit_keys.add(jit_key)
        _warm_counters["warmed"] += 1


def jit_warmed(jit_key: tuple, count: bool = True) -> bool:
    """Whether ``jit_key`` was compiled ahead of traffic here.

    With ``count=True`` (the serving layer's per-flush check) the
    outcome lands on the warm hit/miss counters."""
    with _warm_mu:
        hit = jit_key in _warmed_jit_keys
        if count:
            _warm_counters["hits" if hit else "misses"] += 1
        return hit


def warm_stats() -> dict:
    """{"warmed": plans compiled ahead, "hits"/"misses": flush checks}."""
    with _warm_mu:
        return dict(_warm_counters)


def reset_warm_stats() -> None:
    with _warm_mu:
        _warmed_jit_keys.clear()
        _warm_counters.update(warmed=0, hits=0, misses=0)


def _synthetic_csr(shape: tuple, nnz_cap: int) -> CSR:
    """Deterministic stand-in operand landing in pad bucket ``nnz_cap``.

    nnz is pinned to ``nnz_cap - 1`` (clamped to the shape's capacity):
    a pad bucket holds nnz in (cap/2, cap], and ``cache_key``'s
    ``bit_length`` bucket puts cap-1 — but not cap itself — in the same
    plan bucket as that dominant range.  Entries spread uniformly with
    strictly increasing columns per row, so the operand is valid CSR
    without any RNG (warming must be deterministic and cheap)."""
    n_rows, n_cols = int(shape[0]), int(shape[1])
    nnz = int(max(1, min(nnz_cap - 1, n_rows * n_cols)))
    base, extra = divmod(nnz, n_rows)
    counts = np.full(n_rows, base, np.int64)
    counts[:extra] += 1
    counts = np.minimum(counts, n_cols)
    rows = np.repeat(np.arange(n_rows), counts)
    cols = (np.concatenate([(np.arange(c) * n_cols) // c
                            for c in counts if c > 0])
            if counts.sum() else np.zeros(0, np.int64))
    vals = np.ones(int(counts.sum()), np.float32)
    return csr_from_coo(rows, cols, vals, (n_rows, n_cols))


def synthetic_bucket_operands(bucket: tuple) -> tuple[CSR, CSR]:
    """A deterministic (A, B) pair whose serving pad bucket is ``bucket``
    (``(A.shape, B.shape, nnz_cap_a, nnz_cap_b)``)."""
    a_shape, b_shape, cap_a, cap_b = bucket
    return _synthetic_csr(a_shape, cap_a), _synthetic_csr(b_shape, cap_b)


def warm_bucket(bucket: tuple, *, engine: str = "auto", max_batch: int = 8,
                cache: Optional[AutotuneCache] = None, mesh=None,
                rules: Sequence[HeuristicRule] = DEFAULT_HEURISTICS,
                sample: Optional[tuple] = None,
                sticky_cap: Optional[int] = None,
                cap_headroom: int = 2) -> dict:
    """Compile one serving pad bucket ahead of its first request.

    Runs a flush-shaped pass — ``batch_csr`` at the bucket's pad
    capacities, ``plan_sharded``, ``execute_sharded`` — over a sampled
    real pair (``sample``) or a synthetic stand-in, so the plan lands in
    the autotune cache *and* the compiled computation lands in this
    process's jit cache before traffic hits the bucket.  The selection
    entry propagates cross-process through the shared cache file; the
    compilation is per-process, which is why coordinator workers run
    their own ``warm`` tasks.

    esc capacity handling: the resulting ``cap_products`` is raised by
    ``cap_headroom`` (a pow2 factor; the sample may not be the bucket's
    heaviest traffic) and by ``sticky_cap`` (the caller's running
    per-bucket max).  The caller seeds its sticky cap from the returned
    ``"cap"`` so real flushes pin to the warmed jit identity instead of
    recompiling at the next capacity boundary.

    Returns ``{"bucket", "engine", "backend", "source", "cap",
    "wall_s"}``."""
    from repro.distributed import spgemm_shard as shard
    if cache is None:
        cache = default_cache()
    _, _, cap_a, cap_b = bucket
    A, B = sample if sample is not None else synthetic_bucket_operands(bucket)
    t0 = time.perf_counter()
    fi.fire("dispatch.warm", bucket=tuple(bucket))
    Ab = batch_csr([A], nnz_cap=cap_a, batch_cap=max_batch)
    Bb = batch_csr([B], nnz_cap=cap_b, batch_cap=max_batch)
    sp = shard.plan_sharded(Ab, Bb, engine, mesh=mesh, cache=cache,
                            rules=rules)
    cap = None
    if sp.base.engine == "esc":
        cap = int(sp.base.kwargs_dict.get("cap_products", 0))
        cap = max(cap * max(int(cap_headroom), 1), int(sticky_cap or 0))
        kwargs = _sorted_kwargs({**sp.base.kwargs_dict,
                                 "cap_products": cap})
        sp = dataclasses.replace(
            sp, base=dataclasses.replace(sp.base, kwargs=kwargs))
    shard.execute_sharded(sp, Ab, Bb)
    note_warmed(sp.base.jit_key)
    return {"bucket": tuple(bucket), "engine": sp.base.engine,
            "backend": sp.base.backend, "source": sp.base.source,
            "cap": cap, "wall_s": time.perf_counter() - t0}
