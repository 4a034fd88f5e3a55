"""Two-tier persistent compilation cache.

The reference framework compiles a Program once and reuses the executor
across steps; this port re-pays trace + lower + XLA compile on every
process start and every elastic epoch.  With a cache dir set, that cost is
paid once per (program, flags, world, shapes) key and then amortized across
processes, restarts, and elastic re-quorums:

  tier A  JAX's native persistent XLA cache (``jax_compilation_cache_dir``):
          dedupes backend compiles of identical HLO, even across different
          framework-level keys.
  tier B  ``<dir>/aot``  framework-level serialized executables
          (``jax.experimental.serialize_executable``): a hit skips trace +
          lower + compile entirely and hands the executor a ready
          ``Compiled`` it can call.

Where the cache lives is decided here and nowhere else (``cache_dir``):

  ``JAX_COMPILATION_CACHE_DIR`` set   the machine placed the cache.  Tier A
          is that directory — JAX reads the variable itself and this
          package never writes ``jax_compilation_cache_dir`` — and tier B
          is its ``aot`` subdirectory.  No flag overrides it.
  else ``FLAGS_compile_cache_dir``    tier A ``<dir>/xla``, tier B
          ``<dir>/aot``.
  else    off.  Entry points (chip_smoke.py, bench.py, tools/serve.py,
          ``AnalysisConfig.set_optim_cache_dir``) call ``place()``, which
          falls back to ``DEFAULT_DIR``: one fixed path inside the checkout,
          because the path is part of tier A's key and a cache that moves
          never hits.

Tier-B layout: one directory per key, written with the checkpoint
machinery's crash-safe idiom (``LocalFS.atomic_write_dir`` temp-then-rename
plus a ``_SUCCESS`` manifest written last, carrying a per-file crc32):

  <dir>/aot/<sha256 key>/
      executable.bin   serialized XLA executable (PJRT wire format)
      trees.pkl        pickled (in_tree, out_tree) PyTreeDefs
      _SUCCESS         json manifest: format/jax/backend versions, meta,
                       per-file crc32 — absent or mismatched => the entry
                       never loads (a torn write degrades to a recompile)

Keys are CONTENT hashes — ``Program.to_dict()`` (so a re-built or
re-transpiled program with identical IR hits, regardless of ``_uid``), the
trace-affecting flag fingerprint, the ``_collective_meta`` world, feed
shapes/dtypes, fetch names, mesh axes, the jax version + backend
platform (an upgraded jaxlib must never deserialize a stale executable),
and ``code_fingerprint()``, the package's own sources: an executable is
what the lowerings made of the Program, so another checkout's lowerings
(PR 39: the parent's composed epilogue, restored by the change from a
cache directory the two shared) must never be restored either.

Invalidation is by construction: anything that changes the executable
changes the key; anything that changes the serialization contract fails
the manifest check.  Eviction is size-capped LRU over entry mtimes
(``FLAGS_compile_cache_max_bytes``; a load touches its entry).
"""

import hashlib
import json
import logging
import os
import pickle
import shutil
import time
import zlib

import numpy as np

from .. import flags as _flags
from . import telemetry as _tm

__all__ = [
    "enabled", "cache_dir", "aot_dir", "xla_dir", "enable_xla_cache",
    "place", "DEFAULT_DIR",
    "program_fingerprint", "code_fingerprint", "artifact_key",
    "raw_artifact_key", "load",
    "store", "invalidate",
    "entries", "stats", "clear", "evict_to_cap",
]

FORMAT = 1
_SUCCESS = "_SUCCESS"
_FILES = ("executable.bin", "trees.pkl")


_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir():
    return os.environ.get(_ENV) or _flags.flag("compile_cache_dir") or ""


def enabled():
    return bool(cache_dir())


def aot_dir():
    return os.path.join(cache_dir(), "aot")


def xla_dir():
    return os.environ.get(_ENV) or os.path.join(cache_dir(), "xla")


def place(path=None):
    """Turn the cache on for an entry point and return where it lives:
    ``$JAX_COMPILATION_CACHE_DIR`` if the machine set it, else ``path``,
    else an already-set FLAGS_compile_cache_dir, else ``DEFAULT_DIR``."""
    if not os.environ.get(_ENV) and (path or not cache_dir()):
        _flags.set_flags({"FLAGS_compile_cache_dir": path or DEFAULT_DIR})
    enable_xla_cache()
    return cache_dir()


# -- tier A: JAX's native persistent XLA cache -------------------------------

_xla_wired = [None]


def enable_xla_cache():
    """Wire tier A for the current ``cache_dir()`` (idempotent; re-wires if
    the flag changes).  Called from the executor's compile-miss path so a
    flag set after Executor construction still takes effect."""
    d = cache_dir()
    if not d or _xla_wired[0] == d:
        return bool(d)
    import jax

    if not os.environ.get(_ENV):
        os.makedirs(xla_dir(), exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", xla_dir())
    # cache everything: the defaults skip sub-second compiles, which is the
    # whole CPU-tier test population and most decode-step buckets
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _xla_wired[0] = d
    return True


# -- keys --------------------------------------------------------------------

def _json_default(o):
    if isinstance(o, np.ndarray):
        # hash large embedded constants exactly — str() would elide
        return ["__nd__", o.dtype.str, list(o.shape),
                hashlib.sha256(np.ascontiguousarray(o).tobytes()).hexdigest()]
    if isinstance(o, (np.integer, np.floating, np.bool_)):
        return o.item()
    if isinstance(o, bytes):
        return o.hex()
    return str(o)


_code_fp = []


def code_fingerprint():
    """sha256 over the package's ``*.py`` sources (relative path and
    bytes, in sorted order), once a process: 215 files, under 10 ms.  The
    lowerings are part of what an executable is made from, and tier A's
    key (the HLO) already says so; this makes tier B's say it."""
    if not _code_fp:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for d, subdirs, files in os.walk(root):
            subdirs.sort()
            for f in sorted(files):
                if f.endswith(".py"):
                    path = os.path.join(d, f)
                    h.update(os.path.relpath(path, root).encode())
                    with open(path, "rb") as fp:
                        h.update(fp.read())
        _code_fp.append(h.hexdigest())
    return _code_fp[0]


_fp_memo = {}


def program_fingerprint(program):
    """sha256 of the program's canonical to_dict() json — stable across
    processes (unlike ``_uid``), memoized per (uid, version)."""
    k = (program._uid, program.version)
    hit = _fp_memo.get(k)
    if hit is not None:
        return hit
    blob = json.dumps(program.to_dict(), sort_keys=True,
                      separators=(",", ":"), default=_json_default)
    h = hashlib.sha256(blob.encode()).hexdigest()
    if len(_fp_memo) > 1024:
        _fp_memo.clear()
    _fp_memo[k] = h
    return h


def artifact_key(program, feed_sig, fetch_names, trace_flags, mesh_sig=None,
                 extra=None):
    """Content key for one executable.  ``feed_sig`` is the sorted
    (name, shape, dtype-str) tuple the executor already builds; ``mesh_sig``
    is the mesh's axis names/sizes; the device ids the executable was
    compiled for ride in ``extra`` (a serialized executable loads only
    onto the ids it names)."""
    import jax

    cmeta = getattr(program, "_collective_meta", None)
    world = None
    if cmeta:
        world = {k: cmeta.get(k)
                 for k in ("nranks", "mode", "allreduce_dtype", "nrings")}
    payload = {
        "format": FORMAT,
        "program": program_fingerprint(program),
        "feeds": [list(map(str, (n, tuple(s), d))) for n, s, d in feed_sig],
        "fetch": [str(f) for f in fetch_names],
        "flags": [list(map(str, kv)) for kv in trace_flags],
        "mesh": mesh_sig,
        "world": world,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "code": code_fingerprint(),
        "extra": extra,
    }
    blob = json.dumps(payload, sort_keys=True, default=_json_default)
    return hashlib.sha256(blob.encode()).hexdigest()


def raw_artifact_key(kind, payload):
    """Content key for a non-Program executable (the decode-serving
    CarriedStepFn path): ``payload`` is any JSON-able description of
    everything that affects the compiled artifact — model weight
    fingerprint, cache geometry, argument signature, trace flags.  The
    jax version + backend are folded in for the same reason as
    ``artifact_key``."""
    import jax

    blob = json.dumps({"format": FORMAT, "kind": str(kind),
                       "payload": payload, "jax": jax.__version__,
                       "backend": jax.default_backend(),
                       "code": code_fingerprint()},
                      sort_keys=True, default=_json_default)
    return hashlib.sha256(blob.encode()).hexdigest()


# -- tier B store/load -------------------------------------------------------

def _crc(data):
    return zlib.crc32(data) & 0xFFFFFFFF


def _entry_names(root):
    if not os.path.isdir(root):
        return []
    return sorted(n for n in os.listdir(root)
                  if "._tmp." not in n and
                  os.path.isdir(os.path.join(root, n)))


def _entry_bytes(path):
    total = 0
    try:
        for n in os.listdir(path):
            try:
                total += os.path.getsize(os.path.join(path, n))
            except OSError:
                pass
    except OSError:
        pass
    return total


def _read_manifest(path):
    try:
        with open(os.path.join(path, _SUCCESS)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def store(key, payload, in_tree, out_tree, meta=None):
    """Write one serialized executable under its key (atomic, manifest
    last), then evict down to FLAGS_compile_cache_max_bytes.  Returns True
    when the entry is on disk (pre-existing counts); never raises."""
    if not enabled():
        return False
    import jax

    from ..utils.fs import LocalFS

    path = os.path.join(aot_dir(), key)
    if os.path.exists(os.path.join(path, _SUCCESS)):
        return True
    try:
        os.makedirs(aot_dir(), exist_ok=True)
        trees = pickle.dumps((in_tree, out_tree),
                             protocol=pickle.HIGHEST_PROTOCOL)
        blobs = {"executable.bin": bytes(payload), "trees.pkl": trees}
        with LocalFS().atomic_write_dir(path) as tmp:
            for name, data in blobs.items():
                with open(os.path.join(tmp, name), "wb") as f:
                    f.write(data)
            manifest = {
                "format": FORMAT,
                "key": key,
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "created": time.time(),
                "meta": meta or {},
                "files": {n: _crc(d) for n, d in blobs.items()},
            }
            with open(os.path.join(tmp, _SUCCESS), "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
        nbytes = sum(len(d) for d in blobs.values())
        _tm.inc("compile_cache_store_total")
        _tm.inc("compile_cache_bytes_written_total", nbytes)
        evict_to_cap()
        return True
    except Exception as e:
        logging.warning("compile_cache: store %s failed: %s", key[:12], e)
        _tm.inc("compile_cache_errors_total", kind="store")
        return False


def invalidate(key):
    """Drop one tier-B entry (defective or superseded) so the next store
    rewrites it instead of skipping on the surviving _SUCCESS marker."""
    try:
        shutil.rmtree(os.path.join(aot_dir(), key))
        return True
    except OSError:
        return False


def _defect(key, kind):
    _tm.inc("compile_cache_disk_miss_total")
    _tm.inc("compile_cache_errors_total", kind=kind)
    # delete the bad entry NOW: store() skips keys whose _SUCCESS exists
    # (concurrent-writer dedup), so a corrupt-but-manifested entry would
    # otherwise force a recompile in every future process
    invalidate(key)
    return None


def load(key):
    """-> {"payload", "in_tree", "out_tree", "manifest"} or None.  Any
    defect — missing/torn manifest, format or jax/backend version mismatch,
    crc mismatch, unpicklable trees — counts an error by kind, deletes the
    entry, and returns None (the caller recompiles and re-stores)."""
    if not enabled():
        return None
    import jax

    path = os.path.join(aot_dir(), key)
    if not os.path.isdir(path):
        _tm.inc("compile_cache_disk_miss_total")
        return None
    man = _read_manifest(path)
    if man is None:
        return _defect(key, "manifest")
    if (man.get("format") != FORMAT or man.get("jax") != jax.__version__
            or man.get("backend") != jax.default_backend()):
        return _defect(key, "version")
    blobs = {}
    for name in _FILES:
        try:
            with open(os.path.join(path, name), "rb") as f:
                blobs[name] = f.read()
        except OSError:
            return _defect(key, "missing")
        if _crc(blobs[name]) != man.get("files", {}).get(name):
            return _defect(key, "crc")
    try:
        in_tree, out_tree = pickle.loads(blobs["trees.pkl"])
    except Exception:
        return _defect(key, "trees")
    try:
        os.utime(path)  # LRU touch
    except OSError:
        pass
    _tm.inc("compile_cache_disk_hit_total")
    _tm.inc("compile_cache_bytes_read_total",
            sum(len(b) for b in blobs.values()))
    return {"payload": blobs["executable.bin"], "in_tree": in_tree,
            "out_tree": out_tree, "manifest": man}


# -- maintenance / CLI surface ----------------------------------------------

def entries():
    """One record per tier-B entry: key, bytes, validity, created/last_used
    timestamps, stored meta.  Sorted least-recently-used first."""
    root = aot_dir()
    out = []
    for name in _entry_names(root):
        path = os.path.join(root, name)
        man = _read_manifest(path)
        try:
            last_used = os.stat(path).st_mtime
        except OSError:
            last_used = 0.0
        out.append({
            "key": name,
            "bytes": _entry_bytes(path),
            "valid": man is not None,
            "created": (man or {}).get("created"),
            "last_used": last_used,
            "jax": (man or {}).get("jax"),
            "meta": (man or {}).get("meta") or {},
        })
    out.sort(key=lambda r: r["last_used"])
    return out


def _xla_files():
    """Tier A's files.  Placed by the environment, tier A is the cache dir
    itself and ``aot/`` sits inside it — that subtree is tier B's."""
    out = []
    for dirpath, dirs, files in os.walk(xla_dir()):
        if dirpath == cache_dir() and "aot" in dirs:
            dirs.remove("aot")
        out.extend(os.path.join(dirpath, f) for f in files)
    return out


def stats():
    ents = entries()
    total = sum(r["bytes"] for r in ents)
    xla_files = xla_bytes = 0
    for path in _xla_files():
        xla_files += 1
        try:
            xla_bytes += os.path.getsize(path)
        except OSError:
            pass
    return {
        "dir": cache_dir(),
        "enabled": enabled(),
        "aot_entries": len(ents),
        "aot_valid": sum(1 for r in ents if r["valid"]),
        "aot_bytes": total,
        "max_bytes": int(_flags.flag("compile_cache_max_bytes") or 0),
        "xla_files": xla_files,
        "xla_bytes": xla_bytes,
    }


def evict_to_cap():
    """LRU-evict tier-B entries until the total fits
    FLAGS_compile_cache_max_bytes (<=0 disables).  Invalid entries go
    first regardless of age."""
    cap = int(_flags.flag("compile_cache_max_bytes") or 0)
    if cap <= 0 or not enabled():
        return 0
    ents = entries()
    total = sum(r["bytes"] for r in ents)
    if total <= cap:
        return 0
    # invalid first, then least-recently-used
    ents.sort(key=lambda r: (r["valid"], r["last_used"]))
    evicted = 0
    for r in ents:
        if total <= cap:
            break
        path = os.path.join(aot_dir(), r["key"])
        try:
            shutil.rmtree(path)
            total -= r["bytes"]
            evicted += 1
        except OSError:
            pass
    if evicted:
        _tm.inc("compile_cache_evictions_total", evicted)
        _tm.set_gauge("compile_cache_size_bytes", total)
    return evicted


def clear():
    """Wipe both tiers (the directories survive).  -> tier-B entries plus
    tier-A files removed."""
    n = 0
    root = aot_dir()
    for name in _entry_names(root):
        try:
            shutil.rmtree(os.path.join(root, name))
            n += 1
        except OSError:
            pass
    for path in _xla_files():
        try:
            os.remove(path)
            n += 1
        except OSError:
            pass
    return n
