"""Disaggregated prefill/decode pools + prefix-affinity routing (ISSUE 20).

The load-bearing contract is the hand-off pin: a request admitted on a
prefill-role engine, exported as a migration packet, and imported by a
decode-role engine produces the BYTE-IDENTICAL stream a unified engine
produces — greedy and seeded (the packet carries the row's unfolded rng
key, kv frontier, and last token, so every (seed, position)-keyed draw
lands on the same values), at tp=1 and tp=2 (the arena layout is
identical across roles, so migration is block-table surgery plus one
device copy) — and neither engine leaks a block. Around it: the router's
affinity scoring actually concentrating repeat chunk compositions
(non-vacuous hit rate), health gating and unified fallback, session
stickiness, and the offline pool-sizing arithmetic
(``policy.pool_split`` / ``simulator.pool_plan``).

``TestSmoke`` is the ``make disagg-smoke`` lane (wired into ``make ci``);
the tp=2 class rides the conftest-forced 8-virtual-device CPU platform;
the mid-migration chaos reset rides ``make chaos`` in
tests/test_resilience.py (fault site ``migrate``).
"""

import dataclasses
import importlib.util
import os
import threading

import jax
import pytest

from rag_llm_k8s_tpu.core.config import (
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    MeshConfig,
    RouterConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import flight
from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.server.router import NoReplicaAvailable, Replica, Router

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=8)
SEEDED = SamplingConfig(do_sample=True, temperature=0.8, top_p=0.9,
                        max_new_tokens=8)
PAGED = EngineConfig(
    prompt_buckets=(16, 32), max_batch_size=4, max_seq_len=64,
    kv_paged=True, kv_block_size=16,
)
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [12, 13, 14], [3] * 20, [9] * 25]


def _load_sim(name):
    here = os.path.join(os.path.dirname(__file__), "..",
                        "rag_llm_k8s_tpu", "sim", name + ".py")
    spec = importlib.util.spec_from_file_location("_rt_" + name,
                                                  os.path.normpath(here))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    return cfg, params


def _pair(cfg, params, sampling, **eng_kw):
    """A routed prefill-role + decode-role scheduler pair."""
    pre = ContinuousScheduler(
        ContinuousEngine(
            cfg, params, sampling=sampling,
            engine_config=dataclasses.replace(PAGED, pool_role="prefill"),
            **eng_kw,
        ),
        retry_backoff_s=0.0,
    )
    dec = ContinuousScheduler(
        ContinuousEngine(
            cfg, params, sampling=sampling,
            engine_config=dataclasses.replace(PAGED, pool_role="decode"),
            **eng_kw,
        ),
        retry_backoff_s=0.0,
    )
    return pre, dec


def _unified_streams(cfg, params, sampling, seeds, **eng_kw):
    uni = ContinuousScheduler(
        ContinuousEngine(cfg, params, sampling=sampling,
                         engine_config=PAGED, **eng_kw),
        retry_backoff_s=0.0,
    )
    try:
        return [uni.submit(p, seed=s) for p, s in zip(PROMPTS, seeds)]
    finally:
        uni.shutdown()


def _assert_no_leaks(*scheds):
    for sc in scheds:
        assert sc.engine.kv_pool.blocks_in_use() == 0, (
            f"leaked blocks on {sc.engine.pool_role} engine"
        )


# ---------------------------------------------------------------------------
# the disagg-smoke lane (make disagg-smoke / make ci)
# ---------------------------------------------------------------------------
class TestSmoke:
    def test_greedy_disagg_stream_is_byte_identical(self, setup):
        cfg, params = setup
        base = _unified_streams(cfg, params, GREEDY, [None] * len(PROMPTS))
        pre, dec = _pair(cfg, params, GREEDY)
        router = Router([Replica("prefill-0", pre), Replica("decode-0", dec)])
        try:
            got = [router.submit(p) for p in PROMPTS]
            assert got == base
            _assert_no_leaks(pre, dec)
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_seeded_disagg_stream_is_byte_identical(self, setup):
        """The hard half of the pin: sampled draws are (seed, position)
        keyed, and the packet carries the UNFOLDED row key + kv frontier,
        so the decode engine's draws continue the prefill engine's
        sequence exactly."""
        cfg, params = setup
        seeds = [100 + i for i in range(len(PROMPTS))]
        base = _unified_streams(cfg, params, SEEDED, seeds)
        pre, dec = _pair(cfg, params, SEEDED)
        router = Router([Replica("prefill-0", pre), Replica("decode-0", dec)])
        try:
            got = [router.submit(p, seed=s) for p, s in zip(PROMPTS, seeds)]
            assert got == base
            _assert_no_leaks(pre, dec)
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_migration_events_journal_the_handoff(self, setup):
        """Every routed hand-off journals route_decision +
        migrate_begin/migrate_done with matching block counts — the
        events ``flightview --router`` aggregates."""
        cfg, params = setup
        pre, dec = _pair(cfg, params, GREEDY)
        router = Router([Replica("p0", pre), Replica("d0", dec)])
        rec = flight.recorder()
        # by sequence number, not by position: the process-wide ring is
        # bounded, and once earlier tests on this worker have filled it a
        # slice past its old length is empty
        before = rec.events_emitted
        try:
            router.submit([4, 5, 6, 7], chunk_keys=[("doc", 1)])
        finally:
            pre.shutdown()
            dec.shutdown()
        evs = [e for e in rec.snapshot() if e["seq"] >= before]
        types = [e["type"] for e in evs]
        assert "route_decision" in types
        rd = next(e for e in evs if e["type"] == "route_decision")
        assert rd["mode"] in ("disagg", "unified")
        if rd["mode"] == "disagg":
            beg = next(e for e in evs if e["type"] == "migrate_begin")
            done = next(e for e in evs if e["type"] == "migrate_done")
            assert beg["rid"] == done["rid"] == rd["rid"]
            assert beg["blocks"] == done["blocks"] > 0

    def test_affinity_routing_is_non_vacuous(self):
        """Two stub prefill replicas, a repeating chunk composition: after
        the first decision the router must keep routing the composition
        to the SAME replica with affinity > 0 — chunk reuse becomes a
        fleet property only if routing concentrates compositions."""
        a, b = _StubReplica("p-a"), _StubReplica("p-b")
        router = Router([a, b], RouterConfig(load_weight=0.0))
        keys = [("doc", 7), ("doc", 8)]
        first, _, aff0 = router.select("prefill", chunk_keys=keys)
        assert aff0 == 0.0  # nothing hot yet
        hits = 0
        for _ in range(6):
            r, _, aff = router.select("prefill", chunk_keys=keys)
            assert r.name == first.name
            hits += aff > 0.0
        assert hits == 6
        # a disjoint composition is NOT forced onto the hot replica once
        # load matters: with equal (stub) load it may land either side,
        # but its affinity score starts at zero
        _, _, aff_new = router.select("prefill", chunk_keys=[("doc", 99)])
        assert aff_new == 0.0

    def test_pool_split_sizes_both_tiers(self):
        policy = _load_sim("policy")
        plan = policy.pool_split(30.0, 120.0, span_s=100.0,
                                 target_util=0.6, min_each=1)
        assert plan["prefill"] == 1 and plan["decode"] == 2
        assert 0.0 < plan["prefill_util"] <= 1.0
        assert 0.0 < plan["decode_util"] <= 1.0
        # tightening the target grows both tiers, never shrinks them
        tight = policy.pool_split(30.0, 120.0, span_s=100.0,
                                  target_util=0.2)
        assert tight["prefill"] >= plan["prefill"]
        assert tight["decode"] >= plan["decode"]

    def test_pool_plan_answers_from_a_simulated_trace(self):
        """The offline sizing loop: generate a trace, simulate it, read
        how many prefill vs decode replicas the load needs."""
        sim = _load_sim("simulator")
        tg = _load_sim("tracegen")
        res = sim.simulate(tg.generate(24, seed=3), max_batch_size=8)
        plan = res["pool_plan"]
        assert plan["prefill"] >= 1 and plan["decode"] >= 1
        assert plan["prefill_s"] > 0 and plan["decode_s"] > 0
        # re-planning the same journal at a tighter target only grows
        tight = sim.pool_plan(res["journal"], target_util=0.05)
        assert tight["prefill"] >= plan["prefill"]
        assert tight["decode"] >= plan["decode"]


# ---------------------------------------------------------------------------
# router policy (stub replicas: no engines, no jax dispatch)
# ---------------------------------------------------------------------------
class _StubEngine:
    def __init__(self, role, free=4):
        self.pool_role = role
        self.B = 4
        self.kv_pool = None
        self._free = free

    def free_slots(self):
        return list(range(self._free))


class _StubScheduler:
    def __init__(self, role, free=4):
        self.engine = _StubEngine(role, free)
        self._stop = threading.Event()


class _StubBreaker:
    def __init__(self):
        self.open = False


def _StubReplica(name, role="prefill", free=4, breaker=None):
    return Replica(name, _StubScheduler(role, free), breaker=breaker)


class TestRouterPolicy:
    def test_unhealthy_replicas_take_no_traffic(self):
        brk = _StubBreaker()
        sick = _StubReplica("sick", breaker=brk)
        well = _StubReplica("well")
        router = Router([sick, well])
        brk.open = True
        for _ in range(4):
            r, _, _ = router.select("prefill")
            assert r.name == "well"
        brk.open = False  # breaker self-heals: replica is eligible again
        assert sick.healthy()

    def test_all_unhealthy_raises_no_replica(self):
        brk = _StubBreaker()
        brk.open = True
        router = Router([_StubReplica("only", breaker=brk)])
        with pytest.raises(NoReplicaAvailable):
            router.select("prefill")

    def test_stopped_scheduler_is_unhealthy(self):
        rep = _StubReplica("r0")
        assert rep.healthy()
        rep.scheduler._stop.set()
        assert not rep.healthy()

    def test_load_prefers_the_emptier_replica(self):
        full = _StubReplica("full", free=0)
        empty = _StubReplica("empty", free=4)
        router = Router([full, empty],
                        RouterConfig(affinity_weight=0.0, load_weight=1.0))
        r, _, _ = router.select("prefill")
        assert r.name == "empty"

    def test_session_sticks_within_ttl_and_expires_after(self):
        a, b = _StubReplica("a"), _StubReplica("b")
        router = Router([a, b], RouterConfig(session_ttl_s=0.2))
        r0, _, _ = router.select("prefill", session="conv-1")
        for _ in range(4):
            r, _, _ = router.select("prefill", session="conv-1")
            assert r.name == r0.name
        # expire: rewrite the stamp into the past instead of sleeping
        name, stamp = router._sessions["conv-1"]
        router._sessions["conv-1"] = (name, stamp - 1.0)
        router.select("prefill", session="conv-1")  # re-scores, re-pins
        _, fresh = router._sessions["conv-1"]
        assert fresh > stamp - 1.0

    def test_hot_chunk_registry_is_bounded(self):
        rep = _StubReplica("solo")
        router = Router([rep], RouterConfig(hot_chunks=8))
        for i in range(50):
            router.select("prefill", chunk_keys=[("doc", i)])
        assert len(router._hot["solo"]) <= 8

    def test_unified_fallback_when_no_decode_tier(self, setup):
        """A unified replica alone serves end to end through the router:
        no packet, mode=unified, stream matches a direct submit."""
        cfg, params = setup
        uni = ContinuousScheduler(
            ContinuousEngine(cfg, params, sampling=GREEDY,
                             engine_config=PAGED),
            retry_backoff_s=0.0,
        )
        router = Router([Replica("uni-0", uni)])
        try:
            got = router.submit(PROMPTS[0])
            base = uni.submit(PROMPTS[0])
            assert got == base
            _assert_no_leaks(uni)
        finally:
            uni.shutdown()

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError):
            Router([_StubReplica("x"), _StubReplica("x")])

    def test_stats_snapshot_shape(self):
        router = Router([_StubReplica("p0"),
                         _StubReplica("d0", role="decode")])
        router.select("prefill", chunk_keys=[("doc", 0)], session="s")
        st = router.stats()
        assert {r["name"] for r in st["replicas"]} == {"p0", "d0"}
        assert st["sessions"] == 1
        assert all(0.0 <= r["load"] <= 1.0 for r in st["replicas"])


# ---------------------------------------------------------------------------
# tp=2: migration is layout-preserving across the tp mesh axis
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 (virtual) devices for tp=2")
class TestDisaggTP2:
    @pytest.fixture(scope="class")
    def tp_setup(self):
        cfg = LlamaConfig.tiny()  # 4 q heads / 2 kv heads: tp=2 tiles
        params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
        ctx = make_mesh(MeshConfig(dp=4, sp=1, tp=2))
        placed = shard_llama_params(params, ctx)
        return cfg, placed, ctx

    def test_tp2_disagg_greedy_byte_identical(self, tp_setup):
        """The packet's gather/scatter run under the arena's own
        shardings, so a head-sharded pool migrates without resharding —
        streams stay pinned to the tp=2 unified baseline."""
        cfg, placed, ctx = tp_setup
        base = _unified_streams(cfg, placed, GREEDY,
                                [None] * len(PROMPTS), mesh=ctx)
        pre, dec = _pair(cfg, placed, GREEDY, mesh=ctx)
        router = Router([Replica("tp-p0", pre), Replica("tp-d0", dec)])
        try:
            got = [router.submit(p) for p in PROMPTS]
            assert got == base
            _assert_no_leaks(pre, dec)
        finally:
            pre.shutdown()
            dec.shutdown()
