"""Device-resident vector store — the framework's ``faiss.IndexFlatL2`` +
pickle-metadata replacement, with the reference's concurrency bugs fixed.

Reference behavior being replaced (/root/reference/llm/rag.py):
- ``IndexFlatL2`` create/add/search/serialize — rag.py:61,80,116,62,82
- pickled metadata sidecar — rag.py:63-64,82-84
- **data race**: ``update_index`` is an unlocked read-modify-write of two
  files, reachable concurrently from ``/upload_pdf`` (rag.py:68-86,141) —
  fixed here by a single-writer lock around all mutation.
- **boot duplication**: ingest re-runs on every pod start and unconditionally
  appends, duplicating every chunk in the persisted index (survey §3.1) —
  fixed here by content-hash dedup.
- **non-atomic persistence**: ``faiss.write_index`` + a separate pickle can
  desync on crash — fixed by write-temp-then-rename of a single snapshot
  (plus a generation number for observability).

Search runs on device: embeddings live as a padded ``[N_pad, D]`` fp32 array
(padded so the executable shape only changes when the index outgrows its
bucket), queried through the fused Pallas kNN kernel on TPU (XLA fallback
elsewhere) — ``ops/knn.py``.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rag_llm_k8s_tpu.ops.knn import BIG, knn_topk
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.utils.buckets import next_pow2

_FORMAT_VERSION = 1


def _indexio():
    """The C++ snapshot codec (native/indexio.cpp): CRC32-verified payload,
    fsync-before-rename durability. None ⇒ numpy .npy fallback (no checksum
    — the codec exists because faiss's writer and np.save both lack one)."""
    try:
        from rag_llm_k8s_tpu.native import load_library
    except ImportError:
        return None
    import ctypes

    lib = load_library("indexio")
    if lib is None:
        return None
    lib.indexio_write.restype = ctypes.c_int32
    lib.indexio_write.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.indexio_read_header.restype = ctypes.c_int32
    lib.indexio_read_header.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)
    ]
    lib.indexio_read.restype = ctypes.c_int32
    lib.indexio_read.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64
    ]
    return lib


def _save_vectors(vec_path: str, vectors: np.ndarray, generation: int) -> str:
    """Persist the fp32 payload. Native codec when available (checksummed,
    fsynced, atomic); tmp-then-rename .npy otherwise. Returns the format
    actually written ("indexio" | "npy") for the metadata record."""
    import ctypes

    lib = _indexio()
    vectors = np.ascontiguousarray(vectors, np.float32)
    if lib is not None:
        rc = lib.indexio_write(
            vec_path.encode(), vectors.shape[1] if vectors.ndim == 2 else 0,
            vectors.shape[0], generation,
            vectors.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        if rc == 0:
            return "indexio"
        import logging

        logging.getLogger(__name__).warning(
            "native index write failed (rc=%d); falling back to npy", rc
        )
    dir_ = os.path.dirname(vec_path) or "."
    fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.save(f, vectors)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, vec_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return "npy"


def _load_vectors(vec_path: str, dim: int) -> np.ndarray:
    """Load the payload, auto-detecting format: the native codec's magic
    first (CRC-verified — corruption raises instead of silently mis-ranking
    every future search), .npy otherwise (including pre-codec snapshots)."""
    import ctypes

    with open(vec_path, "rb") as f:
        magic = f.read(8)
    if magic == b"TPURIDX1":
        lib = _indexio()
        if lib is None:
            raise RuntimeError(
                f"{vec_path} is a native-codec snapshot but no C++ toolchain "
                "is available to read it"
            )
        hdr = (ctypes.c_int64 * 4)()
        rc = lib.indexio_read_header(vec_path.encode(), hdr)
        if rc != 0:
            raise ValueError(f"index payload header corrupt ({vec_path}, rc={rc})")
        f_dim, count, _gen, payload = hdr[0], hdr[1], hdr[2], hdr[3]
        if f_dim != dim:
            raise ValueError(f"index payload dim {f_dim} != expected {dim}")
        # the CRC covers the payload, not the header: a corrupted header
        # must fail HERE, not size the read buffer (count/payload mismatch
        # would otherwise hand indexio_read a larger byte count than the
        # numpy allocation — heap overflow, not a clean error)
        if count < 0 or payload != count * dim * 4:
            raise ValueError(
                f"index payload header inconsistent ({vec_path}: count={count}, "
                f"dim={dim}, payload_bytes={payload}) — snapshot is corrupt"
            )
        out = np.empty((count, dim), np.float32)
        rc = lib.indexio_read(
            vec_path.encode(),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            payload,
        )
        if rc != 0:
            raise ValueError(
                f"index payload failed CRC/read ({vec_path}, rc={rc}) — "
                "snapshot is corrupt"
            )
        return out
    return np.load(vec_path)


@jax.jit
def _dev_append(emb, norms, rows, n_old, n_real):
    """Produce a NEW snapshot with ``rows[:n_real]`` written at ``n_old``.

    Deliberately NOT donated: concurrent searches hold references to the old
    ``(emb, norms)`` pair outside the store lock — immutable snapshots are
    the concurrency contract, and donation would invalidate them mid-search.
    The cost is one device-side O(capacity) buffer copy per ingest batch
    (HBM-to-HBM, ~ms even at GB scale — dwarfed by the embedding forward);
    the host->device transfer stays O(batch). ``rows`` is padded to a
    power-of-two row count to bound executable variants; padding rows carry
    BIG norms so they stay unrankable until a later add overwrites them."""
    emb = jax.lax.dynamic_update_slice(emb, rows.astype(emb.dtype), (n_old, 0))
    real = jnp.arange(rows.shape[0]) < n_real
    row_norms = jnp.where(real, jnp.sum(rows * rows, axis=1), BIG)[None, :]
    norms = jax.lax.dynamic_update_slice(norms, row_norms, (0, n_old))
    return emb, norms


@jax.jit
def _tok_append(toks, lens, rows, rlens, n_old):
    """Splice freshly tokenized chunk rows into the token sidecar at
    ``n_old`` — the token-plane sibling of ``_dev_append`` (same O(batch)
    transfer + immutable-pair contract; not donated for the same reason)."""
    toks = jax.lax.dynamic_update_slice(toks, rows, (n_old, 0))
    lens = jax.lax.dynamic_update_slice(lens, rlens, (n_old,))
    return toks, lens


@dataclass
class SearchResult:
    """One hit: metadata dict + squared-L2 distance (faiss-parity score);
    ``row`` is the store row id (lets consumers reach the cached token row
    without re-tokenizing — -1 when externally constructed)."""

    metadata: Dict
    distance: float
    row: int = -1


def _content_hash(metadata: Dict) -> str:
    """Dedup key: document identity + chunk text (NOT the embedding — vectors
    for identical content are regenerated identically by the same encoder;
    encoder CHANGES are handled by the store-level ``fingerprint``)."""
    h = hashlib.sha256()
    h.update(str(metadata.get("filename", "")).encode())
    h.update(str(metadata.get("chunk_id", "")).encode())
    h.update(str(metadata.get("text", "")).encode())
    return h.hexdigest()


def _pad_bucket(n: int, minimum: int = 512) -> int:
    return max(minimum, next_pow2(n))


class VectorStore:
    """Append-only exact-kNN store. Thread-safe: one writer lock serializes
    mutation + persistence; searches read an immutable device snapshot."""

    def __init__(self, dim: int, path: Optional[str] = None, fingerprint: str = ""):
        self.dim = dim
        self.path = path
        # identifies the embedder that produced the stored vectors; a mismatch
        # at open time means the index is stale (e.g. swapped encoder weights)
        self.fingerprint = fingerprint
        self._lock = threading.RLock()
        self._vectors = np.zeros((0, dim), np.float32)
        self._metadata: List[Dict] = []
        self._hashes: set = set()
        # per-row content hashes, index-aligned with _metadata: the STABLE
        # chunk identity (survives restarts, reloads and re-ingest order)
        # that the KV prefix cache keys segment blocks by
        self._row_hashes: List[str] = []
        self.generation = 0
        # device snapshot: padded [cap, D] embeddings + [1, cap] squared
        # norms. IMMUTABLE pair: mutation swaps in a NEW pair (O(batch)
        # host transfer + an on-device copy — see _dev_append, never
        # in-place/donated: concurrent searches hold the old pair); only
        # outgrowing the padded bucket forces a full re-upload.
        self._dev: Optional[Tuple[jax.Array, jax.Array]] = None
        # observability: ingest-path transfer accounting (tests assert on it)
        self.transfer_stats = {"row_update_batches": 0, "full_uploads": 0}
        # optional chunk-token sidecar for the single-fetch serving path:
        # per-row LLM token ids of each chunk's prompt segment, index-aligned
        # with the vectors, materialized on device via token_snapshot() so a
        # /query's retrieved rows can be assembled into the prompt ON DEVICE
        # (the ids never cross to the host before generation). Populated by
        # the token_source callback at add() time; rows missing it (e.g.
        # after load()) re-tokenize lazily from metadata in token_snapshot.
        self._token_fn = None
        self._chunk_tokens: List[Optional[np.ndarray]] = []
        self._tok_dev: Optional[Tuple[jax.Array, jax.Array]] = None
        self._tok_count = 0  # rows reflected in _tok_dev
        self._tok_build_lock = threading.Lock()  # serializes sidecar builds

    # ------------------------------------------------------------------
    # mutation (single-writer)
    # ------------------------------------------------------------------
    def add(
        self,
        vectors: Sequence[np.ndarray],
        metadata: Sequence[Dict],
        dedup: bool = True,
    ) -> int:
        """Append vectors; returns how many were actually added (content-hash
        duplicates are skipped so boot-time re-ingest is idempotent)."""
        if len(vectors) != len(metadata):
            raise ValueError("vectors and metadata length mismatch")
        with self._lock:  # dedup check and append are one atomic step
            fresh_v, fresh_m, fresh_h = [], [], []
            for v, m in zip(vectors, metadata):
                v = np.asarray(v, np.float32).reshape(-1)
                if v.shape[0] != self.dim:
                    raise ValueError(f"vector dim {v.shape[0]} != index dim {self.dim}")
                h = _content_hash(m)
                if dedup and (h in self._hashes or h in fresh_h):
                    continue
                fresh_v.append(v)
                fresh_m.append(dict(m))
                fresh_h.append(h)
            if not fresh_v:
                return 0
            n_old = len(self._metadata)
            new_rows = np.stack(fresh_v)
            self._vectors = np.concatenate([self._vectors, new_rows], axis=0)
            self._metadata.extend(fresh_m)
            self._hashes.update(fresh_h)
            self._row_hashes.extend(fresh_h)
            # token rows fill LAZILY in token_snapshot (tokenizing here would
            # tax the ingest hot path); the live sidecar pair stays — its
            # row-coverage counter marks it stale and the next snapshot
            # call splices just the new rows
            self._chunk_tokens.extend([None] * len(fresh_m))
            self.generation += 1
            self._append_device_rows(n_old, new_rows)
        return len(fresh_v)

    def _append_device_rows(self, n_old: int, new_rows: np.ndarray):
        """Write freshly added rows into the live device snapshot in place;
        drop the snapshot only when the padded bucket is outgrown (the next
        search rebuilds at the larger bucket). Caller holds the lock."""
        if self._dev is None:
            return  # nothing materialized yet; first search uploads once
        emb, norms = self._dev
        n_real = new_rows.shape[0]
        n_pad = next_pow2(max(n_real, 1))
        if n_old + n_pad > emb.shape[0]:
            self._dev = None  # bucket growth: full re-upload on next search
            return
        rows = np.zeros((n_pad, new_rows.shape[1]), np.float32)
        rows[:n_real] = new_rows
        # one O(batch) host->device transfer into a NEW snapshot pair —
        # deliberately not donated/in-place (see _dev_append: concurrent
        # searches hold the old immutable pair; the device-side O(capacity)
        # copy is the price of that contract)
        self._dev = _dev_append(
            emb, norms, jnp.asarray(rows), jnp.int32(n_old), jnp.int32(n_real)
        )
        self.transfer_stats["row_update_batches"] += 1

    # ------------------------------------------------------------------
    # search (on device)
    # ------------------------------------------------------------------
    def device_snapshot(self) -> Tuple[jax.Array, jax.Array]:
        """The immutable device pair ``(emb [cap, D] fp32, sq_norms [1, cap])``
        consumers rank against (e.g. the server's fused embed+kNN call).
        Contract: rows past ``ntotal`` are zero vectors whose norms are BIG,
        so they can never enter a top-k with ``k <= ntotal``; the pair is
        never mutated — mutation swaps in a new pair under the lock."""
        with self._lock:
            if self._dev is not None:
                return self._dev
            n = len(self._metadata)
            n_pad = _pad_bucket(max(n, 1))
            emb = np.zeros((n_pad, self.dim), np.float32)
            emb[:n] = self._vectors
            norms = np.full((1, n_pad), BIG, np.float32)
            norms[0, :n] = (self._vectors**2).sum(axis=1)
            self._dev = (jnp.asarray(emb), jnp.asarray(norms))
            self.transfer_stats["full_uploads"] += 1
            return self._dev

    def attach_token_source(self, fn) -> None:
        """Configure the chunk→LLM-token-ids callback (``fn(metadata) ->
        list[int]``) behind the single-fetch serving path. Idempotent; a
        CHANGED source drops cached rows (they were produced by the old
        one). Sources carrying an equal ``cache_key`` attribute are treated
        as the same source (a new service attaching a fresh closure over
        the same tokenizer keeps the rows)."""
        with self._lock:
            old = self._token_fn
            if old is not None and old is not fn:
                okey = getattr(old, "cache_key", None)
                nkey = getattr(fn, "cache_key", None)
                if okey is None or nkey is None or okey != nkey:
                    self._chunk_tokens = [None] * len(self._metadata)
                    self._tok_dev = None
                    self._tok_count = 0
            self._token_fn = fn

    def release_token_device(self) -> None:
        """Drop the device sidecar pair (host rows stay cached) — called by
        a service's shutdown so a long-lived store does not pin sidecar HBM
        for a serving stack that no longer exists. The next snapshot call
        re-uploads from the cached host rows."""
        with self._lock:
            self._tok_dev = None
            self._tok_count = 0

    @staticmethod
    def _build_token_plane(rows, n: int) -> Tuple[jax.Array, jax.Array]:
        """Pad ``rows[:n]`` into a bucketed ``(tokens [cap, Lc], lens [cap])``
        device pair — the ONE place the sidecar's bucketing lives."""
        cap = _pad_bucket(max(n, 1))
        max_len = max((r.shape[0] for r in rows[:n]), default=1)
        lc = _pad_bucket(max(max_len, 1), minimum=128)
        toks = np.zeros((cap, lc), np.int32)
        lens = np.zeros((cap,), np.int32)
        for i, row in enumerate(rows[:n]):
            toks[i, : row.shape[0]] = row
            lens[i] = row.shape[0]
        return jnp.asarray(toks), jnp.asarray(lens)

    def token_snapshot(self, blocking: bool = True):
        """Immutable device pair ``(tokens [cap, Lc] int32, lens [cap] int32)``
        of per-chunk prompt-segment token ids, row-aligned with
        ``device_snapshot()`` — the gather source for device-side prompt
        assembly. Requires ``attach_token_source``.

        INCREMENTAL like the vector path: rows added since the last call
        tokenize (lazily — never inside ``add``) and splice into the live
        pair with an O(batch) transfer (``_tok_append``); only outgrowing
        the (cap, Lc) bucket forces a full re-upload, so executable shapes
        grow O(log N). The service's post-ingest hook calls this so queries
        at most pay one O(batch) splice, never a corpus rebuild.

        Tokenization and device transfers run OUTSIDE the store lock
        (seconds at corpus scale — concurrent searches/ingest must not stall
        behind them). Rows are append-only with stable indices, so a
        mid-build add just means another loop iteration; a mid-build token-
        source swap discards the build. ``_tok_build_lock`` serializes
        builders.

        ``blocking=False`` (the QUERY path's mode): never wait behind —
        or perform — a large build inside a request. Returns the fresh
        pair when available, otherwise None if another thread is mid-build
        (the caller falls back to the host path); when the build lock is
        free the splice/build still runs inline, which is O(new rows) —
        the post-ingest hook keeps that small."""
        with self._lock:
            if self._tok_dev is not None and self._tok_count == len(self._metadata):
                return self._tok_dev
            if self._token_fn is None:
                raise RuntimeError("no token source attached (attach_token_source)")
        if not blocking:
            if not self._tok_build_lock.acquire(blocking=False):
                return None
            try:
                return self._token_snapshot_locked()
            finally:
                self._tok_build_lock.release()
        with self._tok_build_lock:
            return self._token_snapshot_locked()

    def _token_snapshot_locked(self) -> Tuple[jax.Array, jax.Array]:
        """Body of token_snapshot; caller holds ``_tok_build_lock``."""
        while True:
            with self._lock:
                n = len(self._metadata)
                if self._tok_dev is not None and self._tok_count == n:
                    return self._tok_dev
                fn = self._token_fn
                if fn is None:
                    raise RuntimeError(
                        "no token source attached (attach_token_source)"
                    )
                rows = list(self._chunk_tokens)
                metas = list(self._metadata)
                pair, count = self._tok_dev, self._tok_count
            # -- expensive part, no lock held --
            fresh = {
                i: np.asarray(fn(metas[i]), np.int32)
                for i in range(n)
                if rows[i] is None
            }
            for i, r in fresh.items():
                rows[i] = r
            new_rows = rows[count:n]
            n_pad = next_pow2(max(len(new_rows), 1))
            if (
                pair is not None
                # the PADDED write block must fit: dynamic_update_slice
                # CLAMPS an overflowing start index, which would shift
                # the block onto earlier real rows (same guard as the
                # vector sibling _append_device_rows)
                and count + n_pad <= pair[0].shape[0]
                and all(r.shape[0] <= pair[0].shape[1] for r in new_rows)
            ):
                # splice: O(batch) transfer into a NEW pair (the old one
                # stays immutable for concurrent readers)
                lc = int(pair[0].shape[1])
                rpad = np.zeros((n_pad, lc), np.int32)
                rlen = np.zeros((n_pad,), np.int32)
                for j, r in enumerate(new_rows):
                    rpad[j, : r.shape[0]] = r
                    rlen[j] = r.shape[0]
                built = _tok_append(
                    pair[0], pair[1], jnp.asarray(rpad), jnp.asarray(rlen),
                    jnp.int32(count),
                )
                self.transfer_stats["tok_row_splices"] = (
                    self.transfer_stats.get("tok_row_splices", 0) + 1
                )
            else:
                built = self._build_token_plane(rows, n)
                self.transfer_stats["tok_full_uploads"] = (
                    self.transfer_stats.get("tok_full_uploads", 0) + 1
                )
            with self._lock:
                if self._token_fn is not fn:
                    continue  # source swapped mid-build: discard
                # bank the tokenization (append-only, content-stable)
                for i, r in fresh.items():
                    if self._chunk_tokens[i] is None:
                        self._chunk_tokens[i] = r
                self._tok_dev = built
                self._tok_count = n
                if len(self._metadata) == n:
                    return built
            # adds landed mid-build: loop — the committed pair is a
            # valid n-row snapshot; the next pass splices the rest

    def content_key(self, row: int) -> Optional[str]:
        """The stable chunk identity for one store row — the content hash
        its dedup already computes. Restart/reload-stable (derived from
        document + chunk text, never from row order or embeddings), so the
        KV prefix cache can key cached chunk KV blocks on it. None when
        ``row`` is out of range."""
        with self._lock:
            if 0 <= row < len(self._row_hashes):
                return self._row_hashes[row]
            return None

    def cached_token_row(self, row: int) -> Optional[np.ndarray]:
        """The cached token ids for one store row (None when not yet
        tokenized or out of range) — lets the host prompt path reuse the
        sidecar's work instead of re-tokenizing the segment per query."""
        with self._lock:
            if 0 <= row < len(self._chunk_tokens):
                return self._chunk_tokens[row]
            return None

    def token_lengths(self, idxs) -> List[int]:
        """Cached token-row lengths for the given row ids (0 when a row has
        not been tokenized yet) — the host mirror of the device budget rule
        reads these for prefill accounting and context rendering."""
        with self._lock:
            out = []
            for i in map(int, idxs):  # a negative id is out of range too, not the last row
                row = self._chunk_tokens[i] if 0 <= i < len(self._chunk_tokens) else None
                out.append(0 if row is None else int(row.shape[0]))
            return out

    def search(self, query: np.ndarray, k: int = 5) -> List[SearchResult]:
        """Exact kNN by squared L2 (parity with rag.py:114-120, including the
        distance values the reference surfaces as 'score')."""
        n = len(self._metadata)
        if n == 0:
            return []
        k_eff = min(k, n)
        emb, norms = self.device_snapshot()
        q = np.asarray(query, np.float32).reshape(1, self.dim)
        dists, idx = knn_topk(jnp.asarray(q), emb, norms, k=k_eff)
        return self.results_at(np.asarray(idx[0]), np.asarray(dists[0]))

    def results_at(self, idx, dists) -> List[SearchResult]:
        """Materialize SearchResults for externally computed (idx, dists) —
        the fused embed+kNN serving path ranks on device and only the final
        k indices ever reach the host."""
        faults.maybe_fail("store_lookup")
        return [
            SearchResult(metadata=self._metadata[int(i)], distance=float(d), row=int(i))
            for d, i in zip(dists, idx)
        ]

    # ------------------------------------------------------------------
    # introspection (parity with GET /index_info, rag.py:183-197)
    # ------------------------------------------------------------------
    @property
    def ntotal(self) -> int:
        return len(self._metadata)

    def info(self) -> Dict:
        with self._lock:
            return {
                "total_vectors": len(self._metadata),
                "dimension": self.dim,
                "total_chunks": len(self._metadata),
                "sample_chunks": [dict(m) for m in self._metadata[:5]],
                "generation": self.generation,
            }

    # ------------------------------------------------------------------
    # persistence (atomic snapshot; replaces faiss file + pickle sidecar)
    # ------------------------------------------------------------------
    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("no path configured")
        with self._lock:
            payload_meta = {
                "format_version": _FORMAT_VERSION,
                "dim": self.dim,
                "count": len(self._metadata),
                "generation": self.generation,
                "fingerprint": self.fingerprint,
                "metadata": self._metadata,
                "hashes": sorted(self._hashes),
            }
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            dir_ = os.path.dirname(path) or "."
            # vectors (native codec or npy) and metadata (json), each written
            # tmp-then-rename; metadata lands LAST and names the payload it
            # belongs to, so a crash between the renames leaves a usable pair
            vec_path = path + ".vectors.npy"
            payload_meta["vector_format"] = _save_vectors(
                vec_path, self._vectors, self.generation
            )
            fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload_meta, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
            # make the rename itself durable (the codec fsyncs its parent
            # dir for the payload; the metadata rename needs the same)
            dfd = os.open(dir_, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        return path

    @classmethod
    def load(cls, path: str, dim: Optional[int] = None) -> "VectorStore":
        with open(path) as f:
            meta = json.load(f)
        if meta.get("format_version") != _FORMAT_VERSION:
            raise ValueError(f"unsupported index format: {meta.get('format_version')}")
        store = cls(dim=meta["dim"], path=path)
        vectors = _load_vectors(path + ".vectors.npy", meta["dim"])
        count = meta["count"]
        if vectors.shape[0] < count:
            raise ValueError(
                f"index corrupt: metadata says {count} vectors, payload has {vectors.shape[0]}"
            )
        store._vectors = np.asarray(vectors[:count], np.float32)
        store._metadata = list(meta["metadata"])
        # token rows are not persisted: they re-derive from metadata text
        # lazily (token_snapshot) once a token source is attached
        store._chunk_tokens = [None] * len(store._metadata)
        store._hashes = set(meta.get("hashes", []))
        # per-row identities re-derive from metadata (snapshots predating
        # the prefix cache don't persist them; content hashing is cheap)
        store._row_hashes = [_content_hash(m) for m in store._metadata]
        store.generation = meta.get("generation", 0)
        store.fingerprint = meta.get("fingerprint", "")
        if dim is not None and store.dim != dim:
            raise ValueError(f"index dim {store.dim} != expected {dim}")
        return store

    @classmethod
    def open_or_create(
        cls, path: str, dim: int, fingerprint: Optional[str] = None
    ) -> "VectorStore":
        """ensure_index_exists parity (rag.py:57-66): load if present, else
        create empty (persisted on first save). A persisted index whose
        embedder fingerprint doesn't match is discarded — its vectors were
        produced by a different encoder and would silently mis-rank against
        fresh query embeddings."""
        if os.path.exists(path):
            store = cls.load(path, dim=dim)
            if fingerprint is not None and store.fingerprint != fingerprint:
                import logging

                logging.getLogger(__name__).warning(
                    "index at %s was built by a different embedder "
                    "(fingerprint %r != %r); rebuilding fresh",
                    path, store.fingerprint, fingerprint,
                )
                return cls(dim=dim, path=path, fingerprint=fingerprint)
            return store
        return cls(dim=dim, path=path, fingerprint=fingerprint or "")
