"""Persistent tuning cache — searched kernel/runtime configs that
survive the process AND a crash mid-sweep.

One JSON file maps ``surface × shape-signature × dtype × backend:chip``
keys to the winning config plus its trial evidence (median ms, repeat
count, whether the timing backend was representative). Two invariants,
both proven under ``paddle_tpu.testing.FaultInjector``
(tests/test_tuner.py):

- **Atomic commit.** Every write goes through :func:`_atomic_write` —
  the same stage-to-``.part`` + fsync + size-check + ``os.replace``
  protocol as ``distributed/checkpoint`` (and the same hygiene gate:
  ``tools/check_atomic_writes.py`` walks this package too). A crash or
  ENOSPC mid-write can never leave a torn cache; transient I/O errors
  retry with bounded backoff (``utils/retry``).
- **Corrupt caches are discarded, never crashed on.** Load validates
  JSON shape, schema version and a SHA-256 checksum over the entries
  payload; any mismatch (torn write from a pre-atomic writer, silent
  truncation, bit rot, hand-edits gone wrong) logs one warning and
  starts empty — the sweep re-tunes, it does not traceback.

Backend namespacing (the non-TPU-poisoning rule): the key's last
component is ``backend:chip`` (e.g. ``tpu:v5e``, ``cpu:cpu``), so
configs timed under ``JAX_PLATFORMS=cpu`` land in a ``cpu:*`` namespace
a TPU process never reads.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import warnings

__all__ = ["TuningCache", "get_cache", "set_cache_path", "make_key",
           "backend_signature", "default_cache_path", "CACHE_VERSION"]

CACHE_VERSION = 1

#: env var overriding the on-disk location (the offline CLI's --cache
#: flag and tests point here).
CACHE_PATH_ENV = "PADDLE_TPU_TUNER_CACHE"


def default_cache_path() -> str:
    env = os.environ.get(CACHE_PATH_ENV)
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "paddle_tpu", "tuning_cache.json")


_backend_memo: str | None = None


def backend_signature(device=None) -> str:
    """``backend:chip`` namespace component (``tpu:v5e``, ``cpu:cpu``).
    The default-device answer is memoized — it is immutable for the
    process and this runs on every trace-time kernel lookup. A device
    query that fails, or a TPU kind the peak table does not know,
    raises: an entry is never filed under a made-up backend."""
    global _backend_memo
    if device is None and _backend_memo is not None:
        return _backend_memo
    memoize = device is None
    import jax
    if device is None:
        device = jax.devices()[0]
    platform = str(device.platform).lower()
    kind = str(getattr(device, "device_kind", "") or "unknown")
    kind = kind.lower().replace(" ", "_")
    if platform == "tpu":
        # normalize marketing names to the generation tag the
        # profiler peak table keys on (profiler/cost.py)
        from ..profiler.cost import device_peaks
        kind = device_peaks(device).kind
    sig = f"{platform}:{kind}"
    if memoize:
        _backend_memo = sig
    return sig


def make_key(surface: str, shape_sig: str, dtype, backend: str) -> str:
    """Cache key: ``surface|shape_sig|dtype|backend:chip``."""
    return "|".join((surface, shape_sig, str(dtype), backend))


def _entries_checksum(entries: dict) -> str:
    blob = json.dumps(entries, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def _atomic_write(path, data):
    """THE write primitive for the tuning cache: stage the fully
    serialized bytes to ``<path>.part``, flush + fsync, verify the
    on-disk size, atomically rename into place (the
    ``distributed/checkpoint`` commit protocol; enforced by
    tools/check_atomic_writes.py). Transient OSErrors (ENOSPC a GC
    frees, EIO blips) retry with bounded backoff."""
    from ..utils.retry import retry_call

    part = path + ".part"

    def _write():
        with open(part, "wb") as f:  # atomic-ok: the helper itself
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        size = os.stat(part).st_size
        if size != len(data):
            import errno as _e
            raise OSError(_e.EIO,
                          f"short write: {size} != {len(data)}", part)
        os.replace(part, path)

    try:
        retry_call(_write)
    finally:
        if os.path.exists(part):
            try:
                os.remove(part)
            except OSError:
                pass


class TuningCache:
    """In-memory view of one on-disk tuning-cache file (see module
    docstring). Thread-safe; every mutation persists atomically unless
    ``persist=False``."""

    def __init__(self, path: str | None = None, autoload: bool = True):
        self.path = os.fspath(path) if path is not None \
            else default_cache_path()
        self._entries: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._save_lock = threading.Lock()
        self.discarded_corrupt = False
        if autoload:
            self.load()

    # -- load / validate ---------------------------------------------------

    def load(self) -> int:
        """(Re)load from disk. A missing file is an empty cache; a
        corrupt/torn/stale-schema file is DISCARDED with one warning
        (``discarded_corrupt`` flags it for callers that want to log
        harder). Returns the number of live entries."""
        with self._lock:
            self._entries = {}
            self.discarded_corrupt = False
            try:
                with open(self.path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
                if not isinstance(raw, dict):
                    raise ValueError("cache root is not an object")
                if raw.get("version") != CACHE_VERSION:
                    raise ValueError(
                        f"schema version {raw.get('version')!r} != "
                        f"{CACHE_VERSION}")
                entries = raw.get("entries")
                if not isinstance(entries, dict):
                    raise ValueError("missing entries object")
                if raw.get("checksum") != _entries_checksum(entries):
                    raise ValueError("entries checksum mismatch "
                                     "(torn or corrupted write)")
                self._entries = entries
            except FileNotFoundError:
                pass
            except (ValueError, KeyError, OSError, UnicodeDecodeError) as e:
                # includes json.JSONDecodeError (a ValueError): discard,
                # warn once, re-tune — never traceback on a bad cache
                self.discarded_corrupt = True
                warnings.warn(
                    f"paddle_tpu.tuner: discarding corrupt tuning cache "
                    f"{self.path!r} ({e}); affected surfaces will "
                    f"re-tune", stacklevel=2)
            return len(self._entries)

    # -- read --------------------------------------------------------------

    def get(self, key: str) -> dict | None:
        with self._lock:
            ent = self._entries.get(key)
            return dict(ent) if ent is not None else None

    def lookup(self, surface, shape_sig, dtype, backend=None) -> dict | None:
        """The kernel-facing read: winning config dict for this
        surface × shape × dtype on THIS backend namespace, or None."""
        if backend is None:
            backend = backend_signature()
        ent = self.get(make_key(surface, shape_sig, dtype, backend))
        return dict(ent["config"]) if ent else None

    def keys(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- write -------------------------------------------------------------

    def put(self, key: str, config: dict, *, median_ms=None, repeats=None,
            representative=True, source="search", extra=None,
            persist=True) -> dict:
        """Record a winning config. ``representative=False`` marks
        timings taken on a non-target backend (CPU interpret-mode
        trials); they still land, but in that backend's namespace and
        flagged, so readers can refuse them."""
        entry = {"config": dict(config),
                 "representative": bool(representative),
                 "source": source,
                 "timestamp": time.time()}
        if median_ms is not None:
            entry["median_ms"] = float(median_ms)
        if repeats is not None:
            entry["repeats"] = int(repeats)
        if extra:
            entry.update(extra)
        with self._lock:
            self._entries[key] = entry
        if persist:
            self.save()
        return entry

    def discard(self, key: str, persist=True) -> bool:
        with self._lock:
            existed = self._entries.pop(key, None) is not None
        if existed and persist:
            self.save()
        return existed

    def save(self):
        """Atomic commit of the full cache state (see module
        docstring). Raises OSError only after bounded retries — callers
        on best-effort paths catch it (``save_best_effort``).

        ``_save_lock`` serializes whole save operations: snapshotting
        outside it would let two concurrent searches race their full-
        state writes and land the STALER snapshot last, dropping the
        other thread's committed winner from disk."""
        with self._save_lock:
            with self._lock:
                entries = dict(self._entries)
            payload = {"version": CACHE_VERSION,
                       "entries": entries,
                       "checksum": _entries_checksum(entries)}
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            _atomic_write(self.path,
                          json.dumps(payload, sort_keys=True,
                                     indent=1).encode())

    def save_best_effort(self) -> bool:
        """Persist, swallowing (with one warning) persistent I/O
        failure: a full disk must not crash the tuned program — the
        in-memory configs still serve this process."""
        try:
            self.save()
            return True
        except OSError as e:
            warnings.warn(
                f"paddle_tpu.tuner: could not persist tuning cache "
                f"{self.path!r} ({e}); tuned configs remain in-memory "
                f"only for this process", stacklevel=2)
            return False


# -- process-global default cache -------------------------------------------

_global_cache: TuningCache | None = None
_global_lock = threading.Lock()


def get_cache() -> TuningCache:
    """The process-wide cache (lazily loaded from
    :func:`default_cache_path` / ``PADDLE_TPU_TUNER_CACHE``)."""
    global _global_cache
    with _global_lock:
        if _global_cache is None:
            _global_cache = TuningCache()
        return _global_cache


def set_cache_path(path) -> TuningCache:
    """Point the process-global cache at ``path`` (reloads). The
    ``incubate.autotune.set_config`` cache_path knob and tests."""
    global _global_cache
    with _global_lock:
        _global_cache = TuningCache(path)
        return _global_cache
