"""kNN kernel numerics (interpret mode vs oracles) + vector store semantics."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.ops.knn import BIG, knn_topk_pallas, knn_topk_xla


def _random_problem(seed, N=1024, D=64, Q=4, n_valid=None):
    rng = np.random.RandomState(seed)
    e = rng.randn(N, D).astype(np.float32)
    q = rng.randn(Q, D).astype(np.float32)
    n_valid = N if n_valid is None else n_valid
    norms = (e**2).sum(1)
    norms[n_valid:] = BIG
    return q, e, norms, n_valid


class TestKnnKernel:
    @pytest.mark.parametrize("n_valid", [1024, 1000, 700])
    def test_pallas_matches_numpy_oracle(self, n_valid):
        q, e, norms, nv = _random_problem(0, n_valid=n_valid)
        pv, pi = knn_topk_pallas(
            jnp.asarray(q), jnp.asarray(e), jnp.asarray(norms)[None, :],
            k=5, block_n=256, interpret=True,
        )
        d = ((q[:, None, :] - e[None, :nv, :]) ** 2).sum(-1)
        oracle_idx = np.argsort(d, axis=1)[:, :5]
        np.testing.assert_array_equal(np.asarray(pi), oracle_idx)
        np.testing.assert_allclose(
            np.asarray(pv), np.take_along_axis(d, oracle_idx, 1), rtol=1e-4, atol=1e-3
        )

    def test_xla_fallback_matches_oracle(self):
        q, e, norms, nv = _random_problem(1)
        ev, ei = knn_topk_xla(jnp.asarray(q), jnp.asarray(e), jnp.asarray(norms)[None, :], k=5)
        d = ((q[:, None, :] - e[None, :, :]) ** 2).sum(-1)
        oracle_idx = np.argsort(d, axis=1)[:, :5]
        np.testing.assert_array_equal(np.asarray(ei), oracle_idx)

    def test_dispatch_refuses_a_misaligned_index_on_tpu(self, monkeypatch):
        """On the chip a row count that does not tile block_n is an error,
        never a quiet fall to the XLA path; off the chip XLA serves."""
        from rag_llm_k8s_tpu.ops import knn

        q, e, norms, _ = _random_problem(3, N=700)
        args = (jnp.asarray(q), jnp.asarray(e), jnp.asarray(norms)[None, :])
        _, want = knn_topk_xla(*args, k=5)
        _, got = knn.knn_topk(*args, k=5)  # CPU: any row count
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        monkeypatch.setattr(knn.jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="multiple of block_n=512"):
            knn.knn_topk(*args, k=5)

    def test_single_query_single_block(self):
        q, e, norms, _ = _random_problem(2, N=256, Q=1)
        pv, pi = knn_topk_pallas(
            jnp.asarray(q), jnp.asarray(e), jnp.asarray(norms)[None, :],
            k=5, block_n=256, interpret=True,
        )
        ev, ei = knn_topk_xla(jnp.asarray(q), jnp.asarray(e), jnp.asarray(norms)[None, :], k=5)
        np.testing.assert_array_equal(np.asarray(pi), np.asarray(ei))


class TestVectorStore:
    def _mk(self, n=10, dim=8, path=None, seed=0):
        rng = np.random.RandomState(seed)
        store = VectorStore(dim=dim, path=path)
        vecs = rng.randn(n, dim).astype(np.float32)
        meta = [{"filename": "a.pdf", "chunk_id": i, "text": f"chunk {i}"} for i in range(n)]
        assert store.add(vecs, meta) == n
        return store, vecs, meta

    def test_search_returns_nearest(self):
        store, vecs, meta = self._mk()
        res = store.search(vecs[3], k=3)
        assert res[0].metadata["chunk_id"] == 3
        assert res[0].distance == pytest.approx(0.0, abs=1e-4)
        assert len(res) == 3

    def test_search_k_clamped_to_size(self):
        store, vecs, _ = self._mk(n=2)
        assert len(store.search(vecs[0], k=5)) == 2

    def test_empty_store_search(self):
        store = VectorStore(dim=8)
        assert store.search(np.zeros(8)) == []

    def test_dedup_idempotent_reingest(self):
        """The reference duplicates every chunk on pod restart (survey §3.1);
        re-adding identical content must be a no-op here."""
        store, vecs, meta = self._mk()
        assert store.add(vecs, meta) == 0
        assert store.ntotal == 10

    def test_dim_mismatch_rejected(self):
        store = VectorStore(dim=8)
        with pytest.raises(ValueError, match="dim"):
            store.add([np.zeros(4, np.float32)], [{"text": "x"}])

    def test_save_load_roundtrip(self, tmp_path):
        p = str(tmp_path / "idx")
        store, vecs, meta = self._mk(path=p)
        store.save()
        loaded = VectorStore.load(p)
        assert loaded.ntotal == 10
        assert loaded.generation == store.generation
        r1 = store.search(vecs[5], k=2)
        r2 = loaded.search(vecs[5], k=2)
        assert [x.metadata for x in r1] == [y.metadata for y in r2]
        # dedup state survives persistence
        assert loaded.add(vecs, meta) == 0

    def test_open_or_create(self, tmp_path):
        p = str(tmp_path / "idx")
        s = VectorStore.open_or_create(p, dim=8)
        assert s.ntotal == 0
        s.add([np.ones(8, np.float32)], [{"text": "t"}])
        s.save()
        s2 = VectorStore.open_or_create(p, dim=8)
        assert s2.ntotal == 1

    def test_corrupt_metadata_rejected(self, tmp_path):
        p = str(tmp_path / "idx")
        store, _, _ = self._mk(path=p)
        store.save()
        with open(p) as f:
            meta = json.load(f)
        meta["count"] = 99
        with open(p, "w") as f:
            json.dump(meta, f)
        with pytest.raises(ValueError, match="corrupt"):
            VectorStore.load(p)

    def test_native_codec_writes_checksummed_payload(self, tmp_path):
        """The C++ snapshot codec (native/indexio.cpp) is the payload
        writer when the toolchain is present: magic header + CRC."""
        from rag_llm_k8s_tpu.index.store import _indexio

        if _indexio() is None:
            pytest.skip("no C++ toolchain")
        p = str(tmp_path / "idx")
        store, vecs, _ = self._mk(path=p)
        store.save()
        with open(p + ".vectors.npy", "rb") as f:
            assert f.read(8) == b"TPURIDX1"
        with open(p) as f:
            assert json.load(f)["vector_format"] == "indexio"
        loaded = VectorStore.load(p)
        np.testing.assert_array_equal(loaded._vectors, store._vectors)

    def test_payload_corruption_detected_by_crc(self, tmp_path):
        """A flipped payload byte fails the CRC on load — faiss's writer and
        np.save would both return silently corrupted vectors here."""
        from rag_llm_k8s_tpu.index.store import _indexio

        if _indexio() is None:
            pytest.skip("no C++ toolchain")
        p = str(tmp_path / "idx")
        store, _, _ = self._mk(path=p)
        store.save()
        vec_path = p + ".vectors.npy"
        data = bytearray(open(vec_path, "rb").read())
        data[60] ^= 0xFF  # one payload byte (header is 48 bytes)
        open(vec_path, "wb").write(bytes(data))
        with pytest.raises(ValueError, match="CRC|corrupt"):
            VectorStore.load(p)

    def test_header_corruption_rejected_before_allocation(self, tmp_path):
        """The CRC covers the payload only — a corrupted header (count vs
        payload_bytes mismatch) must raise cleanly, never size the read
        buffer (heap-overflow vector)."""
        import struct

        from rag_llm_k8s_tpu.index.store import _indexio

        if _indexio() is None:
            pytest.skip("no C++ toolchain")
        p = str(tmp_path / "idx")
        store, _, _ = self._mk(path=p)
        store.save()
        vec_path = p + ".vectors.npy"
        data = bytearray(open(vec_path, "rb").read())
        data[16:24] = struct.pack("<q", 1 << 40)  # count field
        open(vec_path, "wb").write(bytes(data))
        with pytest.raises(ValueError, match="inconsistent|corrupt"):
            VectorStore.load(p)

    def test_npy_snapshots_still_load(self, tmp_path):
        """Back-compat: pre-codec snapshots (plain .npy payload) load."""
        p = str(tmp_path / "idx")
        store, vecs, _ = self._mk(path=p)
        store.save()
        # overwrite the payload with the legacy npy format
        np.save(open(p + ".vectors.npy", "wb"), store._vectors)
        loaded = VectorStore.load(p)
        assert loaded.ntotal == store.ntotal
        np.testing.assert_array_equal(loaded._vectors, store._vectors)

    def test_empty_store_roundtrips_through_codec(self, tmp_path):
        p = str(tmp_path / "idx")
        s = VectorStore(dim=8, path=p)
        s.save()
        assert VectorStore.load(p).ntotal == 0

    def test_info_shape(self):
        store, _, _ = self._mk()
        info = store.info()
        assert info["total_vectors"] == 10
        assert info["dimension"] == 8
        assert len(info["sample_chunks"]) == 5

    @pytest.mark.parametrize("row, want", [(-1, 0), (10, 0), (3, 4)], ids=["negative", "len", "valid"])
    def test_token_lengths_out_of_range_row_is_zero(self, row, want):
        """A row id outside [0, len) has no tokens: -1 must not read the last
        row's length (Python's negative index), as one past the end does not."""
        store, _, _ = self._mk()
        store.attach_token_source(lambda md: list(range(1 + int(md["chunk_id"]))))
        store.token_snapshot()  # tokenizes every row: row i holds i + 1 tokens
        assert store.token_lengths([9]) == [10]  # the last row is there to be misread
        assert store.token_lengths([row]) == [want]

    def test_concurrent_adds_no_loss(self):
        """The race the reference has at rag.py:68-86: concurrent ingest must
        not lose vectors."""
        store = VectorStore(dim=8)
        rng = np.random.RandomState(7)
        batches = [
            (
                rng.randn(5, 8).astype(np.float32),
                [{"filename": f"f{t}.pdf", "chunk_id": i, "text": f"{t}-{i}"} for i in range(5)],
            )
            for t in range(8)
        ]
        threads = [
            threading.Thread(target=lambda b=b: store.add(b[0], b[1])) for b in batches
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert store.ntotal == 40

    def test_grow_across_pad_bucket(self):
        """Index growth past the padding bucket keeps search correct."""
        rng = np.random.RandomState(3)
        store = VectorStore(dim=8)
        v1 = rng.randn(500, 8).astype(np.float32)
        store.add(v1, [{"text": f"a{i}"} for i in range(500)])
        _ = store.search(v1[0], k=1)  # builds 512-pad snapshot
        v2 = rng.randn(50, 8).astype(np.float32)
        store.add(v2, [{"text": f"b{i}"} for i in range(50)])
        res = store.search(v2[10], k=1)  # needs 1024-pad snapshot
        assert res[0].metadata["text"] == "b10"


class TestIncrementalDeviceIndex:
    def test_adds_within_bucket_are_row_updates(self):
        """Ingest must not re-upload the whole padded matrix per add: once a
        snapshot exists, in-bucket adds transfer only the new rows."""
        rng = np.random.RandomState(7)
        store = VectorStore(dim=8)
        v0 = rng.randn(20, 8).astype(np.float32)
        store.add(v0, [{"text": f"a{i}"} for i in range(20)])
        _ = store.search(v0[0], k=1)  # materializes the 512-pad snapshot
        assert store.transfer_stats == {"row_update_batches": 0, "full_uploads": 1}

        for b in range(5):  # five more batches, all within the 512 bucket
            vb = rng.randn(30, 8).astype(np.float32)
            store.add(vb, [{"text": f"b{b}_{i}"} for i in range(30)])
            last = vb[-1]
        assert store.transfer_stats["row_update_batches"] == 5
        assert store.transfer_stats["full_uploads"] == 1  # no re-uploads

        # and the in-place snapshot ranks exactly like a fresh rebuild
        res = store.search(last, k=3)
        assert res[0].metadata["text"] == "b4_29"
        fresh = VectorStore(dim=8)
        fresh.add(np.asarray(store._vectors), [dict(m) for m in store._metadata])
        want = fresh.search(last, k=3)
        assert [r.metadata["text"] for r in res] == [r.metadata["text"] for r in want]
        assert [r.distance for r in res] == pytest.approx([r.distance for r in want])

    def test_bucket_growth_triggers_one_full_upload(self):
        rng = np.random.RandomState(8)
        store = VectorStore(dim=8)
        store.add(rng.randn(500, 8).astype(np.float32),
                  [{"text": f"a{i}"} for i in range(500)])
        _ = store.search(np.zeros(8, np.float32), k=1)
        v2 = rng.randn(50, 8).astype(np.float32)
        store.add(v2, [{"text": f"b{i}"} for i in range(50)])  # outgrows 512
        res = store.search(v2[10], k=1)
        assert res[0].metadata["text"] == "b10"
        assert store.transfer_stats["full_uploads"] == 2
        assert store.transfer_stats["row_update_batches"] == 0


class TestCorpusScale:
    """Retrieval at corpus scale (VERDICT r3 #5): ingest to N >= 100k in
    batches, assert transfers stay O(batch) with O(log N) full uploads, and
    ranking stays exact vs the numpy oracle. (faiss IndexFlatL2 — rag.py:61 —
    shrugs at this scale; the device index must too.)"""

    def test_100k_ingest_bucket_growth_and_exactness(self):
        rng = np.random.RandomState(11)
        D, BATCH, NBATCH = 16, 4096, 25  # 102_400 vectors
        store = VectorStore(dim=D)
        _ = store.search(np.zeros(D, np.float32), k=1)  # materialize early
        for b in range(NBATCH):
            vb = rng.randn(BATCH, D).astype(np.float32)
            store.add(vb, [{"text": f"b{b}_{i}"} for i in range(BATCH)])
            # touch the snapshot each batch (as serving does between ingests)
            store.device_snapshot()
        N = store.ntotal
        assert N == BATCH * NBATCH
        # transfers: one row-update per in-bucket batch; a full re-upload only
        # on the O(log N) bucket growths (512 -> 131072 is 8 doublings; +1
        # initial + 1 final-bucket rebuild tolerance)
        growths = int(np.log2(131072 // 512))
        stats = store.transfer_stats
        assert stats["row_update_batches"] + stats["full_uploads"] <= NBATCH + growths + 2
        assert stats["full_uploads"] <= growths + 2
        assert stats["row_update_batches"] >= NBATCH - growths - 1

        # exactness at scale: top-5 matches brute-force numpy on 3 queries
        V = np.asarray(store._vectors)
        for qi in (0, 7, 31):
            q = V[qi * 100] + rng.randn(D).astype(np.float32) * 0.01
            got = store.search(q, k=5)
            d = ((V - q[None, :]) ** 2).sum(axis=1)
            want = np.argsort(d, kind="stable")[:5]
            assert [r.metadata["text"] for r in got] == [
                store._metadata[int(i)]["text"] for i in want
            ]
            np.testing.assert_allclose(
                [r.distance for r in got], d[want], rtol=1e-4, atol=1e-4
            )
