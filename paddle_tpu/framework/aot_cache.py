"""Persistent AOT executable cache (serving warm restarts).

The in-memory executor cache (``Executor._cache``) dies with the
process, so every autoscaled serving replica re-pays the full
trace+compile for the whole bucket grid at startup — 9.7 s/process on
the CPU BERT-tiny bench, fatal behind an autoscaler that spins replicas
up on load spikes.  The reference never had this problem shape (its
per-op interpreter has no compile step); TPU-natively the executable IS
the startup cost, and XLA executables are serializable
(``jax.experimental.serialize_executable`` — PJRT
``client.serialize_executable``), so the cache can live on disk:

* **key** — a sha256 over the program's CONTENT hash (the versioned
  serialization desc — the per-process ``_uid`` counter is useless
  across restarts) × feed signature × fetch list × donation mode ×
  trace-time flags × device kind/platform × jax version × the ids of the
  devices the executable was built for.  Any of those changing is a
  different executable; a jax upgrade or a model edit silently misses
  instead of loading a stale binary;
* **entry** — one ``<key>.aotx`` file: a pickle of
  ``{format, meta, payload, in_tree, out_tree}`` where ``payload`` is
  the serialized executable and the trees are the pickled arg/result
  treedefs ``serialize`` hands back;
* **write** — atomic (tmp file in the cache dir + ``os.replace``), so
  N replicas racing on a shared cache dir never observe a torn entry;
* **read** — any failure (truncated pickle, wrong format, PJRT
  deserialize error, device-kind mismatch) counts an
  ``aot_cache_error``, deletes the bad entry when possible, and falls
  back to a fresh compile — a corrupt cache can cost time, never
  correctness.

Counters (``monitor.stat``): ``aot_cache_hit`` / ``aot_cache_miss`` /
``aot_cache_store`` / ``aot_cache_error``; host-side load/save phases
are ``aot_cache::load`` / ``aot_cache::save`` RecordEvent markers
surfaced by ``profiler.step_breakdown()``.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
from typing import Any, Dict, Optional

ENTRY_FORMAT = 1
_ENTRY_SUFFIX = ".aotx"


def program_content_hash(program) -> str:
    """Stable content hash of a Program — the cross-process analog of the
    in-memory ``(_uid, _version)`` cache key.  Built over the versioned
    serialization desc (names, shapes, dtypes, attrs — the same schema
    saved models use), so two processes loading the same artifact and
    applying the same passes agree byte-for-byte.  Cached on the program
    per ``_version`` (the desc walk is not free)."""
    cached = program.__dict__.get("_content_hash")
    if cached is not None and cached[0] == program._version:
        return cached[1]
    from .serialization import program_to_desc
    desc = program_to_desc(program)
    blob = json.dumps(desc, sort_keys=True, default=str).encode("utf-8")
    digest = hashlib.sha256(blob).hexdigest()
    program.__dict__["_content_hash"] = (program._version, digest)
    return digest


def device_identity() -> str:
    """Platform + device kind + jax/jaxlib version — executables are
    binary artifacts for one backend generation."""
    import jax
    dev = jax.devices()[0]
    parts = [jax.__version__, dev.platform,
             getattr(dev, "device_kind", "") or ""]
    try:
        import jaxlib
        parts.append(getattr(jaxlib, "__version__", ""))
    except Exception:
        pass
    return "|".join(parts)


def entry_key(program, feed_signature, fetch_names, donate_state: bool,
              trace_flags, devices) -> str:
    """Cache key for one executable (one bucket shape of one program),
    built for ``devices``."""
    blob = json.dumps({
        "program": program_content_hash(program),
        "feed_sig": [list(map(str, item)) for item in feed_signature],
        "fetches": list(fetch_names),
        "donate_state": bool(donate_state),
        "trace_flags": [str(f) for f in trace_flags],
        "device": device_identity(),
        "device_ids": [d.id for d in devices],
    }, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()


def entry_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, key + _ENTRY_SUFFIX)


def load(cache_dir: str, key: str, devices):
    """Deserialize the cached executable for ``key`` onto ``devices``
    (the ones it was built for — ``deserialize_and_load`` would
    otherwise bind it to every device of the backend), or None.

    Counts ``aot_cache_hit``/``aot_cache_miss``; any failure mode
    (corrupt pickle, format drift, PJRT rejection) counts
    ``aot_cache_error``, removes the offending entry, and returns None —
    the caller recompiles and overwrites."""
    from ..monitor import stat
    from ..profiler import RecordEvent
    path = entry_path(cache_dir, key)
    if not os.path.exists(path):
        stat("aot_cache_miss").add()
        return None
    try:
        with RecordEvent("aot_cache::load", key=key[:16]):
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if not isinstance(entry, dict) or \
                    entry.get("format") != ENTRY_FORMAT:
                raise ValueError(
                    f"aot cache entry format "
                    f"{entry.get('format') if isinstance(entry, dict) else '?'}"
                    f" != {ENTRY_FORMAT}")
            from jax.experimental import serialize_executable as _se
            compiled = _se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=list(devices))
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException:
        # corrupt / stale / wrong-backend entry: recompile-and-overwrite
        stat("aot_cache_error").add()
        stat("aot_cache_miss").add()
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    stat("aot_cache_hit").add()
    return compiled


def store(cache_dir: str, key: str, compiled,
          meta: Optional[Dict[str, Any]] = None) -> bool:
    """Serialize ``compiled`` (a jax.stages.Compiled) under ``key``.

    Atomic: pickles into a tmp file in the cache dir and ``os.replace``s
    it into place, so concurrent replicas sharing the dir never read a
    torn entry.  Returns False (counting ``aot_cache_error``) when the
    backend can't serialize — callers keep the live executable either
    way."""
    from ..monitor import stat
    from ..profiler import RecordEvent
    try:
        with RecordEvent("aot_cache::save", key=key[:16]):
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            entry = {"format": ENTRY_FORMAT, "meta": dict(meta or {}),
                     "payload": payload, "in_tree": in_tree,
                     "out_tree": out_tree}
            os.makedirs(cache_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=cache_dir,
                                       suffix=_ENTRY_SUFFIX + ".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump(entry, f, protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, entry_path(cache_dir, key))
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException:
        stat("aot_cache_error").add()
        return False
    stat("aot_cache_store").add()
    return True


def cache_stats() -> Dict[str, int]:
    """The cache counters, for bench artifacts and step_breakdown."""
    from ..monitor import stat
    return {"hits": stat("aot_cache_hit").get(),
            "misses": stat("aot_cache_miss").get(),
            "stores": stat("aot_cache_store").get(),
            "errors": stat("aot_cache_error").get()}


__all__ = ["program_content_hash", "device_identity", "entry_key",
           "entry_path", "load", "store", "cache_stats"]
