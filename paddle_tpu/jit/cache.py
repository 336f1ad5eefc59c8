"""Compile-once cold start: the AOT executable snapshot tier + cache_info.

Two persistence layers kill the per-process compile bill (ROADMAP north
star: restarts are the COMMON case under the PR-2 gang-restart controller,
and serving cold starts are user-visible latency):

1. jax's persistent compilation cache (framework/core.py: always on, at
   JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache) — XLA
   binaries keyed by (HLO, compile options) survive on disk, so a fresh
   process's compile request becomes a disk read.  Covers EVERY compile:
   eager op executables, @to_static steps, inference programs.
2. the AOT snapshot tier here, on when FLAGS_compile_cache_dir /
   PADDLE_COMPILE_CACHE_DIR names a root — a @to_static trace's lowered
   program (jax.export StableHLO) plus its state-layout metadata is
   serialized under <root>/aot/, keyed by (function source, arg signature, state
   avals, mesh/topology, platform) and guarded by a (jax + jaxlib +
   paddle_tpu version, relevant FLAGS, amp state) fingerprint.  A fresh
   process re-runs only the cheap discover pass (state slots are live
   Python objects) and then loads the executable — trace and lower are
   skipped entirely; stale fingerprints auto-invalidate instead of loading.

`cache_info()` is the single observability surface over both tiers plus
the eager dispatch executable cache (printed by profiler.summary).
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import re
import time

logger = logging.getLogger("paddle_tpu")

_FORMAT = 1

# snapshot-tier counters (module-global: one process, one report)
STATS = {
    "hits": 0,          # snapshots loaded (trace+lower+compile skipped)
    "misses": 0,        # lookups that found no usable snapshot
    "saves": 0,         # snapshots written
    "invalidated": 0,   # stale fingerprint: entry deleted, not loaded
    "corrupt": 0,       # unreadable/checksum-failed entries (fell back)
    "unsupported": 0,   # traces that could not be snapshotted (export failed)
    "load_ms": 0.0,
    "save_ms": 0.0,
    "traces": 0,        # fresh trace+lower events (StaticFunction._trace)
    "trace_ms": 0.0,
}

# warmup(dir) prefetches payload bytes here so later binds are memory reads
_PREFETCH = {}

_NAME_RE = re.compile(r"[^A-Za-z0-9_.]+")


def snapshot_dir():
    """<FLAGS_compile_cache_dir>/aot, or None when the tier is disabled."""
    from ..framework import core as _core

    d = _core.flag("FLAGS_compile_cache_dir")
    if not d:
        return None
    return os.path.join(d, "aot")


def enabled():
    return snapshot_dir() is not None


def _source_hash(fn):
    import inspect

    try:
        src = inspect.getsource(fn)
    except (OSError, TypeError):
        src = getattr(getattr(fn, "__code__", None), "co_code", b"")
        src = src.hex() if isinstance(src, bytes) else repr(src)
    return hashlib.sha256(src.encode()).hexdigest()[:16]


def _freeze(v, depth=0):
    """Closure value -> stable key component.  Simple values by value
    (generation steps bake top_k/top_p/eos as closure constants — same
    source, different program); nested functions recursed; opaque objects
    (models, caches) by type only — their behavior shows up in state avals."""
    if depth > 4:
        return "..."
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return (type(v).__name__, v)
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(_freeze(x, depth + 1) for x in v)
    if isinstance(v, dict):
        return ("dict",) + tuple(
            sorted((str(k), _freeze(x, depth + 1)) for k, x in v.items())
        )
    if callable(v) and getattr(v, "__code__", None) is not None:
        return ("fn", _source_hash(v), _closure_fingerprint(v, depth + 1))
    return ("obj", type(v).__qualname__)


def _closure_fingerprint(fn, depth=0):
    vals = []
    for c in getattr(fn, "__closure__", None) or ():
        try:
            vals.append(_freeze(c.cell_contents, depth))
        except ValueError:  # empty cell
            vals.append(("empty",))
    for d in getattr(fn, "__defaults__", None) or ():
        vals.append(_freeze(d, depth))
    for k, d in sorted((getattr(fn, "__kwdefaults__", None) or {}).items()):
        vals.append((k, _freeze(d, depth)))
    return tuple(vals)


def _mesh_fingerprint():
    import jax

    from ..distributed import mesh as _mesh

    m = _mesh.get_mesh()
    mk = None
    if m is not None:
        mk = (tuple(m.axis_names), tuple(m.devices.shape),
              str(m.devices.flat[0].platform))
    return (mk, jax.device_count(), jax.process_count(),
            str(jax.devices()[0].platform))


def _flags_fingerprint():
    """Behavior-controlling global state a trace may bake in — the same
    staleness class as ops.dispatch._dispatch_salt."""
    import jax

    from ..framework import core as _core

    amp = _core.active_amp()
    amp_key = (amp.enabled, amp.level, amp.dtype) if amp is not None else None
    return (
        _core.flag("FLAGS_check_nan_inf"),
        _core.flag("FLAGS_serve_kv_quant"),
        _core.get_default_dtype(),
        bool(jax.config.jax_enable_x64),
        amp_key,
    )


def _version_salt():
    import jax
    import jaxlib

    from .. import version as _version

    return (_version.full_version, jax.__version__, jaxlib.__version__)


def fn_name(fn):
    name = getattr(fn, "__qualname__", None) or getattr(fn, "__name__", "fn")
    return _NAME_RE.sub("_", name)[:80]


def entry_path(fn, sig_key, state_avals):
    """Snapshot file for one (function, call signature, state layout,
    topology) identity.  The version/flags fingerprint deliberately stays
    OUT of the filename: a version bump must find — and invalidate — the
    stale entry rather than silently leave it behind."""
    d = snapshot_dir()
    if d is None:
        return None
    sig_hash = hashlib.sha256(
        repr((sig_key, state_avals, _mesh_fingerprint(),
              _closure_fingerprint(fn))).encode()
    ).hexdigest()[:24]
    return os.path.join(d, f"{fn_name(fn)}-{sig_hash}.aot")


def fingerprint(fn, donate):
    """Full validity fingerprint embedded in the payload and compared on
    load; any mismatch auto-invalidates the entry."""
    return repr((_FORMAT, _version_salt(), _flags_fingerprint(),
                 _source_hash(fn), bool(donate)))


def save(path, fp, exported_blob, meta):
    """Atomically write one snapshot entry; never raises (cold start must
    not depend on a writable cache)."""
    t0 = time.perf_counter()
    try:
        payload = pickle.dumps(
            {
                "format": _FORMAT,
                "fingerprint": fp,
                "sha256": hashlib.sha256(exported_blob).hexdigest(),
                "exported": exported_blob,
                "meta": meta,
            },
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)
    except (OSError, pickle.PicklingError) as e:
        logger.warning("compile cache: snapshot save failed for %s: %s", path, e)
        return False
    STATS["saves"] += 1
    STATS["save_ms"] += (time.perf_counter() - t0) * 1000
    _PREFETCH.pop(path, None)
    return True


def _note_snapshot_miss(reason):
    """A snapshot-tier miss means a full trace+lower+compile follows; in a
    steady-state sanitizer region that is a GRAFT021 finding attributed to
    the caller (no-op unless FLAGS_debug_sanitize)."""
    try:
        from ..analysis import sanitizer as _san

        _san.note_eager_miss(f"aot-snapshot ({reason})")
    except Exception:
        pass


def load(path, fp):
    """Return (exported_blob, meta) or None.  Fingerprint mismatches delete
    the stale file (auto-invalidation); corrupt entries fall back silently."""
    t0 = time.perf_counter()
    raw = _PREFETCH.pop(path, None)
    if raw is None:
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            STATS["misses"] += 1
            _note_snapshot_miss("absent")
            return None
    try:
        payload = pickle.loads(raw)
        blob = payload["exported"]
        if payload["format"] != _FORMAT:
            raise ValueError(f"format {payload['format']}")
        if hashlib.sha256(blob).hexdigest() != payload["sha256"]:
            raise ValueError("checksum mismatch")
    except Exception as e:  # torn write, truncation, hostile bytes: all = miss
        STATS["corrupt"] += 1
        STATS["misses"] += 1
        _note_snapshot_miss("corrupt")
        logger.warning("compile cache: corrupt snapshot %s (%s); recompiling", path, e)
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    if payload["fingerprint"] != fp:
        STATS["invalidated"] += 1
        STATS["misses"] += 1
        _note_snapshot_miss("stale fingerprint")
        logger.info("compile cache: stale snapshot %s (version/flags changed); invalidating", path)
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    STATS["hits"] += 1
    STATS["load_ms"] += (time.perf_counter() - t0) * 1000
    return blob, payload["meta"]


def purge(fn):
    """Remove every on-disk snapshot belonging to `fn`
    (StaticFunction.clear_cache(persistent=True))."""
    d = snapshot_dir()
    if d is None:
        return 0
    prefix = fn_name(fn) + "-"
    n = 0
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    for name in names:
        if name.startswith(prefix) and name.endswith(".aot"):
            try:
                os.remove(os.path.join(d, name))
                n += 1
            except OSError:
                pass
    for path in [p for p in _PREFETCH if os.path.basename(p).startswith(prefix)]:
        _PREFETCH.pop(path, None)
    return n


def prefetch(directory=None):
    """Read snapshot payloads into memory ahead of first use
    (paddle.jit.warmup(dir)).  Returns the number of entries staged."""
    d = os.path.join(str(directory), "aot") if directory else snapshot_dir()
    if d is None:
        return 0
    if directory and os.path.basename(str(directory)) == "aot":
        d = str(directory)
    n = 0
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    for name in names:
        if not name.endswith(".aot"):
            continue
        path = os.path.join(d, name)
        try:
            with open(path, "rb") as f:
                _PREFETCH[path] = f.read()
            n += 1
        except OSError:
            pass
    return n


def _snapshot_disk_stats():
    d = snapshot_dir()
    entries = 0
    size = 0
    if d:
        try:
            for name in os.listdir(d):
                if name.endswith(".aot"):
                    entries += 1
                    try:
                        size += os.path.getsize(os.path.join(d, name))
                    except OSError:
                        pass
        except OSError:
            pass
    return entries, size


def cache_info():
    """One report over every compilation cache layer:

    - persistent: jax's disk cache (XLA binaries).  requests - disk_hits is
      the number of FRESH XLA compiles this process has paid.
    - aot: the snapshot tier (trace+lower+compile skipped on hit).
    - trace: fresh StaticFunction trace events and their cost.
    - eager: the per-op jitted executable cache (ops/dispatch.py).
    """
    from ..framework import core as _core
    from ..ops import dispatch as _dispatch

    entries, size = _snapshot_disk_stats()
    aot = {k: (round(v, 1) if isinstance(v, float) else v) for k, v in STATS.items()
           if k not in ("traces", "trace_ms")}
    aot["entries"] = entries
    aot["bytes"] = size
    aot["dir"] = snapshot_dir() or ""
    return {
        "persistent": _core.compile_cache_stats(),
        "aot": aot,
        "trace": {"traces": STATS["traces"], "trace_ms": round(STATS["trace_ms"], 1)},
        "eager": _dispatch.cache_stats(),
    }


def cache_report():
    """Human-readable cache_info (profiler.summary)."""
    info = cache_info()
    p, a, t, e = info["persistent"], info["aot"], info["trace"], info["eager"]
    lines = [
        "compile cache:",
        f"  persistent dir={p['dir'] or '(disabled)'} entries={p['entries']} "
        f"bytes={p['bytes']} disk_hits={p['disk_hits']} fresh_compiles={p['misses']}",
        f"  aot snapshots entries={a['entries']} bytes={a['bytes']} hits={a['hits']} "
        f"misses={a['misses']} saves={a['saves']} invalidated={a['invalidated']} "
        f"corrupt={a['corrupt']} load_ms={a['load_ms']} save_ms={a['save_ms']}",
        f"  traces count={t['traces']} trace_ms={t['trace_ms']}",
        f"  eager entries={e['entries']}/{e['capacity']} hits={e['hits']} "
        f"misses={e['misses']} evictions={e['evictions']} "
        f"invalidations={e['invalidations']}",
    ]
    return "\n".join(lines)
