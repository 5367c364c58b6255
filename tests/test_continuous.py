"""Continuous (slot-based) batching: greedy parity with the one-shot engine,
mid-generation admission, slot reuse, and the scheduler's no-head-of-line
guarantee (BASELINE config #5)."""

import threading
import time

import jax
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models.llama import init_llama_params

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
ENG_CFG = EngineConfig(prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    oracle = InferenceEngine(
        cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
    )
    return cfg, params, oracle


def make_engine(cfg, params):
    return ContinuousEngine(
        cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
    )


class TestContinuousEngine:
    def test_warmup_primes_state_ops_without_touching_state(self, setup):
        """warmup() runs the admission path's op-by-op device calls once
        (so no first request compiles them — chip_smoke.py counts) and
        leaves the rng stream and the slot state exactly where they were."""
        cfg, params, _ = setup
        eng = make_engine(cfg, params)
        rng, keys, active = (np.asarray(x) for x in
                             (eng._rng, eng._rng_keys, eng._active))
        eng.warmup(batch_sizes=(1,), buckets=eng.buckets[:1])
        assert np.array_equal(np.asarray(eng._rng), rng)
        assert np.array_equal(np.asarray(eng._rng_keys), keys)
        assert np.array_equal(np.asarray(eng._active), active)

    def test_greedy_parity_with_oneshot(self, setup):
        cfg, params, oracle = setup
        eng = make_engine(cfg, params)
        prompts = [[3, 17, 42, 7, 99], [5, 5, 8], [11] * 12]
        want = [oracle.generate([p])[0] for p in prompts]

        for rid, p in enumerate(prompts):
            _, finished = eng.admit(rid, p, GREEDY.max_new_tokens)
            assert finished is None
        results = {}
        for _ in range(GREEDY.max_new_tokens + 1):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert [results[i] for i in range(len(prompts))] == want

    def test_mid_generation_admission(self, setup):
        """A request admitted after several decode steps of another must
        produce exactly its solo greedy continuation."""
        cfg, params, oracle = setup
        eng = make_engine(cfg, params)
        p1, p2 = [3, 17, 42, 7, 99], [5, 5, 8]
        want1 = oracle.generate([p1])[0]
        want2 = oracle.generate([p2])[0]

        eng.admit(1, p1, GREEDY.max_new_tokens)
        results = {}
        for _ in range(3):  # run p1 alone for a few steps
            for rid, toks in eng.step():
                results[rid] = toks
        eng.admit(2, p2, GREEDY.max_new_tokens)  # joins mid-flight
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results[1] == want1
        assert results[2] == want2

    def test_slot_reuse_is_clean(self, setup):
        """A slot freed by a finished request must not leak stale KV into
        the next occupant."""
        cfg, params, oracle = setup
        eng = make_engine(cfg, params)
        rng = np.random.RandomState(0)
        for round_i in range(3):  # same slot reused every round (B=4, 1 req)
            p = rng.randint(2, cfg.vocab_size, 10).tolist()
            want = oracle.generate([p])[0]
            _, finished = eng.admit(round_i, p, GREEDY.max_new_tokens)
            results = {}
            while eng.has_active():
                for rid, toks in eng.step():
                    results[rid] = toks
            assert results[round_i] == want, f"round {round_i}"

    def test_more_requests_than_slots(self, setup):
        cfg, params, oracle = setup
        eng = make_engine(cfg, params)
        sched = ContinuousScheduler(eng)
        try:
            prompts = [[3, 17, 42], [5, 5, 8], [9, 9], [2, 4, 6, 8], [7] * 5, [1]]
            want = [oracle.generate([p])[0] for p in prompts]
            outs = [None] * len(prompts)

            def run(i):
                outs[i] = sched.submit(prompts[i], timeout=120)

            threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert outs == want
        finally:
            sched.shutdown()


class TestNoHeadOfLineBlocking:
    def test_late_arrival_completes_before_long_job(self, setup):
        """THE continuous-batching property: a short request arriving while a
        long one is mid-generation finishes first — it does not wait for the
        long request's slot to free (the coalescing scheduler made it wait
        for the whole previous batch)."""
        cfg, params, _ = setup
        eng = ContinuousEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=40),
            engine_config=EngineConfig(
                prompt_buckets=(16,), max_batch_size=4, max_seq_len=64
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        try:
            order = []
            lock = threading.Lock()

            def run(name, prompt, max_new):
                sched.submit(prompt, max_new_tokens=max_new, timeout=120)
                with lock:
                    order.append((name, eng.steps))

            t_long = threading.Thread(target=run, args=("long", [3, 17, 42], 40))
            t_long.start()
            # let the long request decode a few steps before the short arrives
            while eng.steps < 3:
                time.sleep(0.01)
            t_short = threading.Thread(target=run, args=("short", [5, 5], 4))
            t_short.start()
            t_short.join(timeout=120)
            t_long.join(timeout=120)
            assert [n for n, _ in order] == ["short", "long"]
            # and the short one finished long before the long one's last step
            steps = dict(order)
            assert steps["short"] < steps["long"]
        finally:
            sched.shutdown()


class TestPerRequestSeed:
    def test_seeded_request_is_batch_invariant(self, setup):
        """A seeded sampling request draws identically whether it runs solo
        or shares the batch with other requests (per-row position-keyed
        PRNG), and different seeds diverge."""
        cfg, params, _ = setup
        samp = SamplingConfig(do_sample=True, temperature=1.0, top_p=1.0,
                              max_new_tokens=6)

        def fresh():
            return ContinuousEngine(
                cfg, params, sampling=samp, engine_config=ENG_CFG, dtypes=FP32
            )

        def run(eng, reqs):
            results = {}
            for rid, (p, seed) in enumerate(reqs):
                _, fin = eng.admit(rid, p, samp.max_new_tokens, seed=seed)
                assert fin is None
            while eng.has_active():
                for rid, toks in eng.step():
                    results[rid] = toks
            return results

        p = [3, 17, 42, 7]
        solo = run(fresh(), [(p, 123)])[0]
        # same request with two noisy companions in the batch
        shared = run(fresh(), [(p, 123), ([5, 5], None), ([9, 9, 9], None)])[0]
        assert solo == shared  # batchmates must not perturb seeded draws
        other = run(fresh(), [(p, 124)])[0]
        assert other != solo  # different seed -> different draws

    def test_scheduler_honors_seed(self, setup):
        cfg, params, _ = setup
        samp = SamplingConfig(do_sample=True, temperature=1.0, top_p=1.0,
                              max_new_tokens=6)
        eng = ContinuousEngine(
            cfg, params, sampling=samp, engine_config=ENG_CFG, dtypes=FP32
        )
        sched = ContinuousScheduler(eng)
        try:
            a = sched.submit([3, 17, 42], seed=7, timeout=120)
            b = sched.submit([3, 17, 42], seed=7, timeout=120)
            c = sched.submit([3, 17, 42], seed=8, timeout=120)
            assert a == b
            assert c != a
        finally:
            sched.shutdown()


class TestDispatcherSurvivesStepFailure:
    def test_step_error_recovers_transparently_by_default(self, setup):
        """ISSUE 4: a transient device error inside step() is INVISIBLE to
        the caller — the scheduler resets, resubmits the in-flight request
        (token budget reduced by what was already emitted), and the result
        still matches the solo greedy oracle."""
        cfg, params, oracle = setup
        want = oracle.generate([[3, 17, 42]])[0]
        eng = make_engine(cfg, params)
        sched = ContinuousScheduler(eng, retry_backoff_s=0.0)
        try:
            boom = RuntimeError("synthetic device failure")
            real_step = eng.step
            calls = {"n": 0}

            def flaky_step():
                calls["n"] += 1
                if calls["n"] == 2:
                    raise boom
                return real_step()

            eng.step = flaky_step
            out = sched.submit([3, 17, 42], timeout=120)
            # the failure really happened AND the resubmission seamlessly
            # continued the emitted stream (greedy: identical to solo)
            assert calls["n"] >= 2
            assert out == want
            # still serving afterwards
            eng.step = real_step
            out2 = sched.submit([5, 5, 8], timeout=120)
            assert isinstance(out2, list) and out2
        finally:
            sched.shutdown()

    def test_step_error_fails_waiters_with_retries_disabled(self, setup):
        """retries=0 restores the fail-on-first-fault contract: the error
        reaches in-flight callers and the scheduler keeps serving."""
        cfg, params, _ = setup
        eng = make_engine(cfg, params)
        sched = ContinuousScheduler(eng, retries=0)
        try:
            boom = RuntimeError("synthetic device failure")
            real_step = eng.step
            calls = {"n": 0}

            def flaky_step():
                calls["n"] += 1
                if calls["n"] == 2:
                    raise boom
                return real_step()

            eng.step = flaky_step
            with pytest.raises(RuntimeError, match="synthetic device failure"):
                sched.submit([3, 17, 42], timeout=120)
            eng.step = real_step
            # the dispatcher must still be alive and serving
            out = sched.submit([5, 5, 8], timeout=120)
            assert isinstance(out, list) and out
        finally:
            sched.shutdown()


class TestContinuousOnMesh:
    def test_tp_mesh_greedy_parity(self, setup):
        """Continuous batching on a tp>1 mesh with SHARDED params: the
        executables must be lowered with the state shardings they receive
        (an unsharded lowering rejects every admit with 'sharding does not
        match' → EngineStateLost on each request — a total serving outage
        of the default scheduler on any multi-chip deployment)."""
        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params, oracle = setup
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        placed = shard_llama_params(params, ctx)
        eng = ContinuousEngine(
            cfg, placed, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32,
            mesh=ctx,
        )
        prompts = [[3, 17, 42, 7, 99], [5, 5, 8]]
        want = [oracle.generate([p])[0] for p in prompts]
        for rid, p in enumerate(prompts):
            _, fin = eng.admit(rid, p, GREEDY.max_new_tokens)
            assert fin is None
        results = {}
        for _ in range(GREEDY.max_new_tokens + 1):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert [results[i] for i in range(len(prompts))] == want

    def test_tp_mesh_int8_kv(self, setup):
        """Same mesh path with the int8 cache: sharded scale planes ride
        along (kv-head axis over tp)."""
        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        cfg, params, _ = setup
        import dataclasses

        ec = dataclasses.replace(ENG_CFG, kv_quant="int8")
        ref = InferenceEngine(
            cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32
        ).generate([[3, 17, 42]])[0]
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        eng = ContinuousEngine(
            cfg, shard_llama_params(params, ctx), sampling=GREEDY,
            engine_config=ec, dtypes=FP32, mesh=ctx,
        )
        _, fin = eng.admit(1, [3, 17, 42], GREEDY.max_new_tokens)
        assert fin is None
        results = {}
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results[1] == ref


class TestResetRebuildsDeviceState:
    def test_recovery_after_donated_buffers_invalidated(self, setup):
        """A step failing DURING device execution has already consumed its
        donated inputs (cache, kv_len, last_tok, active). reset() must
        rebuild them, or the engine serves 'Array has been deleted' forever
        while reporting healthy."""
        cfg, params, _ = setup
        eng = make_engine(cfg, params)
        _, fin = eng.admit(1, [3, 17, 42], GREEDY.max_new_tokens)
        assert fin is None
        eng.step()
        # simulate the donation outcome of a mid-execution failure
        for buf in (*eng._cache, eng._kv_len, eng._last_tok, eng._active):
            buf.delete()
        eng.reset()
        # the engine must serve again, correctly
        oracle = InferenceEngine(
            cfg, params, sampling=GREEDY, engine_config=ENG_CFG, dtypes=FP32
        )
        want = oracle.generate([[5, 5, 8]])[0]
        _, fin = eng.admit(2, [5, 5, 8], GREEDY.max_new_tokens)
        assert fin is None
        results = {}
        while eng.has_active():
            for rid, toks in eng.step():
                results[rid] = toks
        assert results[2] == want


class TestShutdownDrainsWaiters:
    def test_inflight_callers_unblock_on_shutdown(self, setup):
        """shutdown() while requests are mid-generation must error them out,
        not leave timeout=None callers blocked forever."""
        cfg, params, _ = setup
        eng = ContinuousEngine(
            cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=2000),
            engine_config=EngineConfig(
                prompt_buckets=(16,), max_batch_size=4, max_seq_len=2048
            ),
            dtypes=FP32,
        )
        sched = ContinuousScheduler(eng)
        errors = []

        def run():
            try:
                sched.submit([3, 17, 42], timeout=None)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=run)
        t.start()
        while eng.steps < 2:  # definitely mid-generation
            time.sleep(0.01)
        sched.shutdown()
        t.join(timeout=30)
        assert not t.is_alive(), "caller still blocked after shutdown"
        assert errors and "shut down" in str(errors[0])


class TestMultiStepSync:
    """decode_sync_steps > 1: k decode steps run as ONE device program
    (lax.scan) with a single [k, B] host fetch — outputs must be identical
    to per-step sync, including EOS mid-window and budget mid-window."""

    def _engine(self, cfg, params, k, sampling=GREEDY, eng_cfg=ENG_CFG):
        import dataclasses
        return ContinuousEngine(
            cfg, params, sampling=sampling,
            engine_config=dataclasses.replace(eng_cfg, decode_sync_steps=k),
            dtypes=FP32,
        )

    def _drain(self, eng, reqs):
        results = {}
        for rid, p, mn in reqs:
            _, finished = eng.admit(rid, p, mn)
            if finished is not None:
                results[rid] = finished
        for _ in range(200):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        return results

    def test_greedy_parity_with_per_step_sync(self, setup):
        cfg, params, oracle = setup
        prompts = [[3, 17, 42, 7, 99], [5, 5, 8], [11] * 12, [2, 9]]
        want = {i: oracle.generate([p])[0] for i, p in enumerate(prompts)}
        for k in (3, 8):
            eng = self._engine(cfg, params, k)
            got = self._drain(eng, [(i, p, GREEDY.max_new_tokens) for i, p in enumerate(prompts)])
            assert got == want, f"k={k}"

    def test_budget_ends_mid_window(self, setup):
        """max_new not a multiple of k: the extra window steps the device ran
        past the budget must be discarded, not emitted."""
        cfg, params, oracle = setup
        p = [3, 17, 42, 7, 99]
        want = oracle.generate([p], max_new_tokens=5)[0]
        eng = self._engine(cfg, params, 4)
        got = self._drain(eng, [(1, p, 5)])
        assert got[1] == want
        assert len(got[1]) == len(want) == 5

    def test_mid_flight_admission_between_windows(self, setup):
        cfg, params, oracle = setup
        p1, p2 = [3, 17, 42, 7, 99], [5, 5, 8]
        want1 = oracle.generate([p1])[0]
        want2 = oracle.generate([p2])[0]
        eng = self._engine(cfg, params, 3)
        eng.admit(1, p1, GREEDY.max_new_tokens)
        results = {}
        for rid, toks in eng.step():  # one 3-step window with p1 alone
            results[rid] = toks
        eng.admit(2, p2, GREEDY.max_new_tokens)  # joins between windows
        for _ in range(200):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert results == {1: want1, 2: want2}

    def test_sampled_parity_with_per_step_sync(self, setup):
        """Seeded sampling: draws are (seed, position)-keyed, so the window
        size must not change what a request samples."""
        cfg, params, _ = setup
        sampling = SamplingConfig(do_sample=True, temperature=0.8, top_p=0.9,
                                  max_new_tokens=8, seed=7)
        p = [3, 17, 42, 7, 99]
        e1 = self._engine(cfg, params, 1, sampling=sampling)
        _, f1 = e1.admit(1, p, 8, seed=123)
        assert f1 is None
        r1 = self._drain_one(e1, 1)
        e4 = self._engine(cfg, params, 4, sampling=sampling)
        _, f4 = e4.admit(1, p, 8, seed=123)
        assert f4 is None
        r4 = self._drain_one(e4, 1)
        assert r1 == r4

    @staticmethod
    def _drain_one(eng, rid):
        for _ in range(200):
            for got_rid, toks in eng.step():
                if got_rid == rid:
                    return toks
            if not eng.has_active():
                break
        raise AssertionError("request never completed")

    def test_eos_mid_window_freezes_row(self, setup):
        """A row that samples EOS mid-window must stop there (post-EOS window
        tokens discarded) while a batchmate keeps decoding — k=1 parity is
        the oracle. The EOS id is chosen from the greedy stream itself so the
        hit genuinely lands mid-window."""
        import dataclasses
        cfg, params, oracle = setup
        p1, p2 = [3, 17, 42, 7, 99], [5, 5, 8]
        stream = oracle.generate([p1])[0]
        eos_tok = stream[4]  # EOS strikes at the 5th token: mid-window for k=4
        cfg_eos = dataclasses.replace(cfg, eos_token_ids=(eos_tok,))
        outs = {}
        for k in (1, 4):
            eng = self._engine(cfg_eos, params, k)
            outs[k] = self._drain(eng, [(1, p1, 8), (2, p2, 8)])
        assert outs[1] == outs[4]
        assert len(outs[1][1]) < 8, "EOS never fired — the fixture is vacuous"
        assert outs[1][1] == stream[:len(outs[1][1])]


class TestBatchedAdmission:
    """admit_many: a group of queued requests prefills together (one batched
    forward per bucket chunk, one first-token fetch) — results must be
    identical to admitting each request alone."""

    def test_group_equals_solo_admission(self, setup):
        cfg, params, oracle = setup
        prompts = [[3, 17, 42, 7, 99], [5, 5, 8], [11] * 12, [2, 9]]
        want = {i: oracle.generate([p])[0] for i, p in enumerate(prompts)}
        eng = make_engine(cfg, params)
        outs = eng.admit_many(
            [(i, p, GREEDY.max_new_tokens, None) for i, p in enumerate(prompts)]
        )
        results = {i: fin for (i, p), (_, fin) in zip(enumerate(prompts), outs) if fin}
        for _ in range(200):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert results == want

    def test_mixed_buckets_in_one_group(self, setup):
        """Requests landing in different buckets split into per-bucket
        chunks but still admit in one call."""
        cfg, params, oracle = setup
        prompts = [[3] * 4, [7] * 20, [9] * 5, [4] * 30]  # buckets 16 and 32
        want = {i: oracle.generate([p])[0] for i, p in enumerate(prompts)}
        eng = make_engine(cfg, params)
        eng.admit_many([(i, p, GREEDY.max_new_tokens, None) for i, p in enumerate(prompts)])
        results = {}
        for _ in range(200):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert results == want

    def test_seeded_draws_independent_of_grouping(self, setup):
        cfg, params, _ = setup
        sampling = SamplingConfig(do_sample=True, temperature=0.8, top_p=0.9,
                                  max_new_tokens=6, seed=0)
        p1, p2 = [3, 17, 42], [5, 9, 2, 7]

        def run(grouped):
            eng = ContinuousEngine(cfg, params, sampling=sampling,
                                   engine_config=ENG_CFG, dtypes=FP32)
            if grouped:
                eng.admit_many([(1, p1, 6, 11), (2, p2, 6, 22)])
            else:
                eng.admit(1, p1, 6, seed=11)
                eng.admit(2, p2, 6, seed=22)
            results = {}
            for _ in range(100):
                for rid, toks in eng.step():
                    results[rid] = toks
                if not eng.has_active():
                    break
            return results

        assert run(True) == run(False)

    def test_early_eos_in_group_frees_slot(self, setup):
        """A request whose FIRST token is EOS finishes inside the group and
        its slot is immediately reusable."""
        cfg, params, oracle = setup
        import dataclasses
        p_live, p_dead = [5, 5, 8], [3, 17, 42, 7, 99]
        first = oracle.generate([p_dead], max_new_tokens=1)[0][0]
        cfg_eos = dataclasses.replace(cfg, eos_token_ids=(first,))
        oracle2 = InferenceEngine(cfg_eos, params, sampling=GREEDY,
                                  engine_config=ENG_CFG, dtypes=FP32)
        want_live = oracle2.generate([p_live])[0]
        eng = ContinuousEngine(cfg_eos, params, sampling=GREEDY,
                               engine_config=ENG_CFG, dtypes=FP32)
        outs = eng.admit_many([(1, p_dead, 8, None), (2, p_live, 8, None)])
        assert outs[0][1] == []  # finished instantly at EOS
        assert outs[1][1] is None
        assert len(eng.free_slots()) == ENG_CFG.max_batch_size - 1
        results = {}
        for _ in range(100):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert results == {2: want_live}

    def test_scheduler_groups_concurrent_submits(self, setup):
        """Concurrent scheduler submits land as grouped admissions (fewer
        prefill fetches) with unchanged results."""
        cfg, params, oracle = setup
        prompts = [[3, 17, 42, 7, 99], [5, 5, 8], [11] * 12, [2, 9]]
        want = [oracle.generate([p])[0] for p in prompts]
        eng = make_engine(cfg, params)
        sched = ContinuousScheduler(eng)
        results = [None] * len(prompts)

        def run(i):
            results[i] = sched.submit(prompts[i], timeout=120)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.shutdown()
        assert results == want

    def test_chunk_failure_isolated_to_its_items(self, setup, monkeypatch):
        """A failed admission chunk fails ONLY its own requests; other
        chunks' admissions stand and decode to completion."""
        cfg, params, oracle = setup
        eng = make_engine(cfg, params)
        p16, p32 = [3] * 4, [7] * 20  # buckets 16 and 32
        want16 = oracle.generate([p16])[0]
        real = eng._admit_chunk

        def flaky(S, chunk, rows, results):
            if S == 32:
                raise RuntimeError("synthetic chunk failure")
            return real(S, chunk, rows, results)

        monkeypatch.setattr(eng, "_admit_chunk", flaky)
        outs = eng.admit_many([(1, p16, GREEDY.max_new_tokens, None),
                               (2, p32, GREEDY.max_new_tokens, None)])
        assert not isinstance(outs[0], BaseException)
        assert isinstance(outs[1], RuntimeError)
        results = {}
        for _ in range(100):
            for rid, toks in eng.step():
                results[rid] = toks
            if not eng.has_active():
                break
        assert results == {1: want16}
        # the single-admit wrapper re-raises per-item errors
        monkeypatch.setattr(eng, "_admit_chunk", flaky)
        import pytest as _pytest
        with _pytest.raises(RuntimeError, match="synthetic"):
            eng.admit(3, p32, 4)


class TestAdmitChunkFailureReleasesRows:
    """A failure AFTER the batched insert spliced rows device-active (e.g.
    the tok0 fetch dying) must not leave those rows decoding garbage
    forever with no host _Slot to retire them: _admit_chunk deactivates the
    chunk's rows on device and resets their slots before per-chunk
    isolation swallows the error (ADVICE r4 #1)."""

    def test_post_insert_failure_deactivates_rows(self, setup):
        cfg, params, _ = setup
        eng = make_engine(cfg, params)

        class BoomList(list):
            def __setitem__(self, i, v):
                raise RuntimeError("boom")

        prompts = [[3, 17, 42], [5, 5, 8]]
        prepared = []
        for i, p in enumerate(prompts):
            key = jax.random.PRNGKey(i)
            prepared.append((i, i, 16, p, 4, key))
        with pytest.raises(RuntimeError, match="boom"):
            eng._admit_chunk(16, prepared, [0, 1], BoomList([None, None]))
        # rows released on device AND on host
        assert not np.asarray(eng._active)[:2].any()
        assert all(not s.active for s in eng.slots)
        # the engine still serves: a real admission on the same rows works
        outs = eng.admit_many([(9, [3, 17, 42], 4, None)])
        assert outs[0][1] is None or isinstance(outs[0][1], list)
        for _ in range(50):
            done = eng.step()
            if done:
                assert done[0][0] == 9
                break
        else:
            raise AssertionError("request 9 never completed")
