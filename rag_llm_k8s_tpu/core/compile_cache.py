"""Placement of JAX's persistent compilation cache, and the executable store
that sits in front of it.

Every entry point that compiles for a device (``server/main.main``,
``chip_smoke.py``, ``benchmark/run.py``, ``scripts/validate_8b.py``, the hardware
test lane's fixture) calls :func:`ensure_compile_cache` FIRST. The directory
is part of a cache entry's key, so it must never move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
  configured here, so whoever runs the program decides where the cache lives;
- unset: the one fixed path ``<checkout>/.jax_cache`` (gitignored).

**The store.** JAX's cache is keyed on the LOWERED module, so a warm boot
traces and lowers every program before it is asked. The store
(``<cache directory>/rag_executables``) is keyed on what a program is MADE
FROM: :func:`entry_for` forms the key from the build's name and key, the
abstract arguments, the identity the building site hands over and
:func:`environment`, without tracing anything; ``obs/tracing.py build_span``
loads the entry where there is one and keeps what it built where there was
none. It exists exactly where the persistent cache is placed, has no option of
its own, holds at most ``STORE_BUDGET_BYTES`` (entries of another source hash
go first, then the one read longest ago), and an entry that is missing, short,
unreadable or refused by the backend is a miss.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import tempfile
import threading
import time
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_PACKAGE_ROOT = os.path.join(_REPO_ROOT, "rag_llm_k8s_tpu")
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

STORE_SUBDIR = "rag_executables"
STORE_BUDGET_BYTES = 2 << 30  # a cell's executables are tens of MiB compressed (PERF.md §6, PR 52)
_SUFFIX = ".rexe"
_MAGIC = b"RAGEXE1\n"
_LENGTH = struct.Struct("<Q")
_STALE_TEMP_S = 3600.0


def ensure_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    # JAX's own variable, not a knob of this program: read only to stay out
    # of its way  # ragcheck: disable=CONFIG-DRIFT
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cache_entry_count(path: str) -> int:
    """Executables currently in the cache directory (0 when absent) — the
    cold-vs-warm evidence entry points print."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# the executable store
# ---------------------------------------------------------------------------


def store_dir() -> Optional[str]:
    """Where the store lives: beside JAX's entries, in the directory JAX itself
    was given (by its variable or by :func:`ensure_compile_cache`). None where
    no cache directory is placed: then nothing is read and nothing is kept."""
    import jax

    placed = jax.config.jax_compilation_cache_dir
    return os.path.join(placed, STORE_SUBDIR) if placed else None


def store_bytes() -> int:
    """Bytes the store holds (0 where there is none)."""
    return sum(size for _, size, _ in _entries(store_dir()))


_source_hash: Optional[str] = None
_source_lock = threading.Lock()


def source_hash() -> str:
    """SHA-256 over the package's ``*.py`` (relative path and bytes, sorted),
    once a process: a new image keys itself out of an old one's entries."""
    global _source_hash
    with _source_lock:
        if _source_hash is None:
            digest = hashlib.sha256()
            for root, dirs, files in os.walk(_PACKAGE_ROOT):
                dirs.sort()
                for name in sorted(files):
                    if name.endswith(".py"):
                        path = os.path.join(root, name)
                        digest.update(os.path.relpath(path, _PACKAGE_ROOT).encode() + b"\0")
                        with open(path, "rb") as f:
                            digest.update(f.read() + b"\0")
            _source_hash = digest.hexdigest()
        return _source_hash


def _versions() -> Dict[str, str]:
    import flax
    import jax
    import jaxlib
    import numpy

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "flax": flax.__version__, "numpy": numpy.__version__}


def environment() -> Dict[str, object]:
    """What every executable of this process is a function of besides its own
    program: the package's source, the libraries, the backend and its devices,
    the compiler's flags and the ``jax.config`` values that change lowering."""
    import jax

    devices = jax.devices()
    client = devices[0].client
    # XLA's and libtpu's own variables, which change what the compiler emits:
    # read to key on, never to configure  # ragcheck: disable=CONFIG-DRIFT
    flags = {name: os.environ.get(name, "") for name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
    return {
        "source": source_hash(), **_versions(),
        "platform": client.platform, "platform_version": client.platform_version,
        "device_kind": devices[0].device_kind, "device_count": len(devices),
        "process_count": jax.process_count(), **flags,
        "jax_enable_x64": bool(jax.config.jax_enable_x64),
        "jax_default_matmul_precision": str(jax.config.jax_default_matmul_precision),
        "jax_default_prng_impl": str(jax.config.jax_default_prng_impl),
        "jax_threefry_partitionable": bool(jax.config.jax_threefry_partitionable),
    }


def _describe_sharding(sharding) -> str:
    """A sharding as the executable depends on it: the mesh's axes, sizes and
    device ids, the spec and the memory kind."""
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or not hasattr(sharding, "spec"):
        return repr(sharding)
    ids = getattr(mesh, "device_ids", None)
    return (f"{type(sharding).__name__}({dict(mesh.shape)!r}, "
            f"{ids.ravel().tolist() if ids is not None else None}, {sharding.spec!r}, "
            f"{getattr(sharding, 'memory_kind', None)!r})")


def describe_avals(avals) -> List[str]:
    """The abstract arguments as the key holds them: the tree's structure,
    then shape, dtype, weak type and sharding of every leaf."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(avals)
    return [str(tree)] + [
        f"{tuple(leaf.shape)} {leaf.dtype} {bool(getattr(leaf, 'weak_type', False))} "
        f"{_describe_sharding(getattr(leaf, 'sharding', None))}"
        for leaf in leaves
    ]


class StoreEntry:
    """One executable's place in the store: ``load()`` where it is held,
    ``save()`` after a build where it was not. ``inputs`` is every input of
    the key by name (the manifest's clear text), ``digest`` their SHA-256."""

    def __init__(self, directory: str, inputs: Dict[str, object]):
        self.inputs = inputs
        self.digest = hashlib.sha256(
            json.dumps(inputs, sort_keys=True, default=repr).encode()).hexdigest()
        self.path = os.path.join(
            directory, f"{str(inputs['source'])[:12]}-{self.digest}{_SUFFIX}")

    def load(self):
        """``(compiled, kernel_builds)`` from the entry, or None: no file, a
        short or garbled one, or bytes the backend refuses are all a miss."""
        try:
            with open(self.path, "rb") as f:
                data = f.read()
        except OSError:
            return None
        try:
            manifest, blob = _unpack(data)
            if manifest["digest"] != self.digest:
                raise ValueError("the entry is another key's")
            if hashlib.sha256(blob).hexdigest() != manifest["blob_sha256"]:
                raise ValueError("the payload does not match its manifest")
            import jax
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = pickle.loads(_decompress(manifest["codec"], blob))
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in manifest["devices"]]
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, backend=devices[0].client,
                execution_devices=devices)
            kernels = [(str(m), str(k), int(n)) for m, k, n in manifest["kernel_builds"]]
        except Exception as exc:  # noqa: BLE001: whatever is wrong with it, build
            logger.warning("executable store: %s is unusable (%s: %s); building instead",
                           os.path.basename(self.path), type(exc).__name__, exc)
            return None
        try:
            os.utime(self.path)  # read now: the last to go when room is made
        except OSError:
            pass
        return compiled, kernels

    def save(self, compiled, lowered_sha256: str,
             kernel_builds: Sequence[Tuple[str, str, int]]) -> None:
        """Keep ``compiled`` under this key: one whole file, by temp file and
        ``rename``. Where the executable cannot be serialized, the store cannot
        be written or the entry alone is over the budget, nothing is kept (one
        line in the log a program) and nothing raises."""
        try:
            from jax.experimental import serialize_executable

            codec, blob = _compress(pickle.dumps(serialize_executable.serialize(compiled),
                                                 protocol=pickle.HIGHEST_PROTOCOL))
            # the device assignment, in its own order: what the backend is
            # handed back with the bytes when they are loaded
            devices = [d.id for d in compiled._executable._unloaded_executable.device_list]
        except Exception as exc:  # noqa: BLE001: not every executable serializes
            _say_once(self.inputs["program"],
                      "executable store: %r cannot be serialized (%s: %s); it is built "
                      "on every boot", self.inputs["program"], type(exc).__name__, exc)
            return
        manifest = dict(
            self.inputs, digest=self.digest, lowered_sha256=lowered_sha256, devices=devices,
            codec=codec, kernel_builds=[list(k) for k in kernel_builds],
            blob_sha256=hashlib.sha256(blob).hexdigest(), written_at=time.time())
        head = json.dumps(manifest, indent=1, sort_keys=True, default=repr).encode()
        data = b"".join((_MAGIC, _LENGTH.pack(len(head)), head, b"\n",
                         _LENGTH.pack(len(blob)), blob))
        directory = os.path.dirname(self.path)
        try:
            os.makedirs(directory, exist_ok=True)
            if not _make_room(directory, len(data), os.path.basename(self.path)[:12]):
                return
            fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                os.replace(tmp, self.path)
            except BaseException:
                _remove(tmp)
                raise
        except OSError as exc:
            _say_once("<write>", "executable store: cannot write %s (%s)", directory, exc)


def entry_for(program: str, key, avals, identity) -> Optional[StoreEntry]:
    """The store's entry for one build, formed WITHOUT tracing it. None where
    there is no store (no cache directory placed) or the site handed no
    identity: such a build is never looked up and never kept. An identity
    whose ``repr`` holds an object's address differs in every process and is
    refused the same way, once in the log."""
    if identity is None:
        return None
    directory = store_dir()
    if directory is None:
        return None
    described = repr(identity)
    if " at 0x" in described:
        _say_once(program, "executable store: the identity of %r names an object by its "
                  "address; it is built on every boot", program)
        return None
    return StoreEntry(directory, {
        "program": program, "key": repr(key), "identity": described,
        "avals": describe_avals(avals), **environment()})


def lowered_text_sha256(lowered) -> str:
    """SHA-256 of a lowering's module text: what a manifest records, so a key
    that stopped covering its program can be found out by lowering it again."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def read_manifest(path: str) -> Dict[str, object]:
    """The clear-text manifest of one entry (raises on a file that is none)."""
    with open(path, "rb") as f:
        return _unpack(f.read())[0]


def _compress(blob: bytes) -> Tuple[str, bytes]:
    """As JAX's own cache does: zstd where it is installed, else zlib (an
    executable's bytes shrink four to five times; the manifest says which)."""
    try:
        import zstandard

        return "zstd", zstandard.ZstdCompressor().compress(blob)
    except ImportError:
        return "zlib", zlib.compress(blob, 1)


def _decompress(codec: str, blob: bytes) -> bytes:
    if codec == "zlib":
        return zlib.decompress(blob)
    import zstandard

    return zstandard.ZstdDecompressor().decompress(blob)


def _unpack(data: bytes):
    n = len(_MAGIC)
    if data[:n] != _MAGIC:
        raise ValueError("not an entry of the store")
    (head,) = _LENGTH.unpack_from(data, n)
    start = n + _LENGTH.size
    manifest = json.loads(data[start:start + head])
    at = start + head + 1
    (size,) = _LENGTH.unpack_from(data, at)
    blob = data[at + _LENGTH.size:]
    if len(blob) != size:
        raise ValueError(f"{len(blob)} payload bytes of {size}")
    return manifest, blob


def _entries(directory: Optional[str]) -> List[Tuple[str, int, float]]:
    """``(path, bytes, last read)`` of every entry; stale temp files go."""
    out = []
    try:
        names = os.listdir(directory) if directory else []
    except OSError:
        return out
    for name in names:
        path = os.path.join(directory, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        if name.endswith(_SUFFIX):
            out.append((path, st.st_size, st.st_mtime))
        elif name.endswith(".tmp") and time.time() - st.st_mtime > _STALE_TEMP_S:
            _remove(path)
    return out


def _make_room(directory: str, need: int, source_prefix: str) -> bool:
    """Remove entries until ``need`` more bytes fit the budget: another source
    hash's first, then the one read longest ago. False where ``need`` alone
    is over the budget."""
    if need > STORE_BUDGET_BYTES:
        return False
    entries = _entries(directory)
    held = sum(size for _, size, _ in entries)
    entries.sort(key=lambda e: (os.path.basename(e[0]).startswith(source_prefix), e[2]))
    for path, size, _ in entries:
        if held + need <= STORE_BUDGET_BYTES:
            break
        _remove(path)
        held -= size
    return True


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


_said: set = set()


def _say_once(what, message: str, *args) -> None:
    if what not in _said:
        _said.add(what)
        logger.warning(message, *args)
