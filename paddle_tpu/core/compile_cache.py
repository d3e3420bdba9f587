"""Where compiled executables persist between processes.

One rule, applied once when the package is imported — so it covers every
compile path that exists (Executor, ParallelExecutor, the serving engines
and the bare `jax.jit`s under parallel/):

- `JAX_COMPILATION_CACHE_DIR` set: JAX already honours it; no directory is
  set here.
- otherwise the cache is `<checkout>/.jax_cache`, computed from this
  package's own location. The path is part of the cache key, so it is never
  a temporary directory, a pid or a timestamp: a directory that moves never
  hits.
- wherever a directory is in effect, from either bullet, the cache admits
  EVERY executable the process compiles: JAX's
  `jax_persistent_cache_min_compile_time_secs` goes from its default of 1 s
  to 0, unless `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS` names one from
  outside (left as given, like the directory). At the default JAX writes
  nothing that compiled in under a second, and a process on a filled cache
  compiles its weight generators, eager `jnp` ops and startup programs
  anew, dozens of executables of a serving set-up. The rule is about WHAT
  is written, not where.
- a bound on the directory's size (`JAX_COMPILATION_CACHE_MAX_SIZE`) is
  JAX's own and never set here. Under one JAX lists the directory and reads
  a timestamp file an entry at EVERY write, so with every executable
  admitted a process that builds hundreds of new programs pays for the
  bound by the entry (PERF.md section 6, PR 58, has the seconds).

A process pinned to the CPU platform (`JAX_PLATFORMS=cpu` — the test tier)
with no directory given keeps no cache, and nothing is set for it. Not for
the time: GIVEN one directory a run, tier-1's whole suite took 563 s for 786
and 3,116 s of case time for 4,091 (PR 63: six workers from an empty
directory; the eager ops' compiles, made once a run, are most of it). For
the store below. On the CPU backend (jax 0.9.0) an executable that JAX
LOADED from its own cache serializes again without its kernels: the copy
deserializes, loads, and fails at its first launch (`NOT_FOUND: Function
<kernel> not found`). A step that misses the store and hits JAX's cache
writes such an entry, and eleven tests of that run died on one. With the
threshold named out of reach, so that JAX's cache holds nothing and the store
works alone, the run was steady and 771 s: no gain worth a mechanism. A TPU's
executable serializes again whole. Until the store declines what came out of
JAX's cache on a CPU (ROADMAP.md D20 (7)), a CPU process is given no
directory, or a threshold with it.

**The executor's store** lives in the subdirectory `STORE_SUBDIR` of whatever
directory is in effect (none in effect, no store). JAX's cache finds an
executable by the hash of its lowered module, so a process that holds every
executable it needs still traces and lowers each program to ask for it. The
store keeps an executor's launch functions LOADED-AND-READY, one file each,
under a key the executor computes from what it holds before it traces
(`framework/executor.py` `Executor._store_key`: the program, the launch's
signature, the flags, the versions, the devices, `source_digest()`): a hit is
`jax.experimental.serialize_executable.deserialize_and_load` and nothing
else. A file is named by the program and the key LESS the source digest and
carries the whole key's digest in its header: an entry of an older source is
replaced, not kept beside the new one, so the directory is bounded by the
programs a checkout runs. It is trusted exactly as JAX's own directory is (an
entry is a pickle), and deleting it is always safe: a missing, truncated,
foreign or unreadable entry is a miss, and a miss lowers, compiles (JAX's
cache serves that as before) and writes the entry again.
"""

from __future__ import annotations

import functools
import hashlib
import os
import pickle
import tempfile
import zlib
from typing import Optional

import jax

from . import flags

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(os.path.dirname(_PACKAGE), ".jax_cache")


#: the `jax.config` options that existed when the package was imported:
#: the ones a stored executable's key holds the values of
CONFIG_NAMES: frozenset = frozenset()


def configure() -> None:
    """Apply the rule above. Touches `jax.config` only — no backend is
    initialized and no directory is created until JAX first writes."""
    global CONFIG_NAMES
    CONFIG_NAMES = frozenset(jax.config._value_holders)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if (jax.config.jax_platforms or "").strip().lower() == "cpu":
            return
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# -- the executor's store of loaded-and-ready executables --------------------

STORE_SUBDIR = "paddle_tpu_executables"

# An entry's payload is compressed as JAX compresses its own cache's (a TPU
# executable is mostly padding: the LM training step's 163 MB are 35): with
# zstandard where it is installed, zlib's fastest level otherwise. The entry
# names its codec, so either process reads the other's.
_CODECS = {"zlib": (lambda b: zlib.compress(b, 1), zlib.decompress)}
try:
    import zstandard
    _CODECS["zstd"] = (
        lambda b: zstandard.ZstdCompressor(level=1, threads=-1).compress(b),
        lambda b: zstandard.ZstdDecompressor().decompress(b))
    _CODEC = "zstd"
except ImportError:
    _CODEC = "zlib"


def store_dir() -> Optional[str]:
    """Where the executor's executables are kept, or None where no
    compile-cache directory is in effect (a CPU-pinned process given none)."""
    root = jax.config.jax_compilation_cache_dir
    if not root or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(root, STORE_SUBDIR)


@functools.lru_cache(maxsize=None)
def file_digest(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@functools.lru_cache(maxsize=None)
def source_digest() -> str:
    """One digest over every module under `paddle_tpu/`, by relative path
    and content (~10 ms, once a process): any edit to the package is a new
    key, whether or not it reaches a given program's trace."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, _PACKAGE).encode() + b"\0")
                h.update(file_digest(path).encode())
    return h.hexdigest()


def in_package(path: str) -> bool:
    return os.path.abspath(path).startswith(_PACKAGE + os.sep)


def load_executable(path: str, key: str):
    """The entry at `path` as a loaded `jax.stages.Compiled`, or None where
    there is none under this `key`: no file, a file that does not unpickle,
    another key in its header, devices this process does not have. Never
    raises: whatever goes wrong is a miss."""
    from jax.experimental import serialize_executable
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if entry["key"] != key:
            return None
        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in entry["devices"]]
        executable, in_tree, out_tree = pickle.loads(
            _CODECS[entry["codec"]][1](entry["payload"]))
        return serialize_executable.deserialize_and_load(
            executable, in_tree, out_tree, backend=devices[0].client,
            execution_devices=devices)
    except FileNotFoundError:
        return None
    except Exception as e:      # whatever the file holds, it is a miss
        flags.vlog(1, "stored executable %s not used: %s: %s", path,
                   type(e).__name__, e)
        return None


def store_executable(path: str, key: str, program: str, compiled) -> bool:
    """Write `compiled` (a `jax.stages.Compiled`) as the entry at `path`,
    over whatever was there, through a temporary file and a rename: a reader
    finds the old entry or the new one, never a part of either. False, and
    nothing written, where the executable does not serialize (host callbacks)
    or the directory cannot be written."""
    from jax.experimental import serialize_executable
    tmp = None
    try:
        payload = pickle.dumps(serialize_executable.serialize(compiled),
                               pickle.HIGHEST_PROTOCOL)
        devices = compiled._executable._unloaded_executable.device_list
        entry = pickle.dumps({
            "key": key, "program": program, "codec": _CODEC,
            "payload": _CODECS[_CODEC][0](payload),
            "devices": [d.id for d in devices]}, pickle.HIGHEST_PROTOCOL)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(entry)
        os.replace(tmp, path)
        return True
    except Exception as e:      # the launch goes on without an entry
        flags.vlog(1, "executable %s not stored: %s: %s", path,
                   type(e).__name__, e)
        if tmp is not None and os.path.exists(tmp):
            os.unlink(tmp)
        return False
