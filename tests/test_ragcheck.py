"""ragcheck (scripts/ragcheck): the repo-native static-analysis suite.

Per-rule contract tests — each rule must flag its seeded fixture violation
and stay silent on the compliant twin — plus the framework contracts
(suppressions, baseline ratchet, CLI exit codes) and the whole-repo gate:
the analyzer over THIS tree yields zero non-baselined findings and zero
stale baseline entries. docs/STATIC_ANALYSIS.md is the rule catalog.

No jax required: ragcheck is stdlib-only AST analysis.
"""

import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from scripts.ragcheck import core  # noqa: E402
from scripts.ragcheck.rules.config_drift import ConfigDriftRule  # noqa: E402
from scripts.ragcheck.rules.debug_gate import DebugGateRule  # noqa: E402
from scripts.ragcheck.rules.event_registry import EventRegistryRule  # noqa: E402
from scripts.ragcheck.rules.fault_sites import FaultSiteRegistryRule  # noqa: E402
from scripts.ragcheck.rules.jit_hygiene import JitHygieneRule  # noqa: E402
from scripts.ragcheck.rules.lock_discipline import LockDisciplineRule  # noqa: E402
from scripts.ragcheck.rules.metric_drift import MetricDriftRule  # noqa: E402
from scripts.ragcheck.rules.sharding_contract import ShardingContractRule  # noqa: E402
from scripts.ragcheck.rules.durable_write import DurableWriteRule  # noqa: E402
from scripts.ragcheck.rules.sim_purity import SimPurityRule  # noqa: E402

BASELINE = REPO_ROOT / "scripts" / "ragcheck" / "baseline.json"


def run_rule(tmp_path, rule_cls, files):
    """Materialize a fixture repo and run one rule over it."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    _, findings = core.run_analysis(str(tmp_path), rules=[rule_cls()])
    return findings


def keys(findings):
    return {f.key for f in findings}


# ---------------------------------------------------------------------------
# LOCK-DISCIPLINE
# ---------------------------------------------------------------------------


class TestLockDiscipline:
    def test_flags_blocking_work_under_lock(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import jax

                class Cache:
                    def bad(self, x):
                        with self._lock:
                            y = jax.device_put(x, None)
                            x.block_until_ready()
                            time.sleep(0.1)
                            self._thread.join(timeout=5)
                            self.coalescer.submit(x)
                        return y
                """,
        })
        assert keys(fs) == {
            "Cache.bad:device_put",
            "Cache.bad:block_until_ready",
            "Cache.bad:time.sleep",
            "Cache.bad:thread-join",
            "Cache.bad:submit",
        }
        assert all(f.rule == "LOCK-DISCIPLINE" for f in fs)

    def test_flags_executable_work_under_lock(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                class Engine:
                    def bad(self, key, args):
                        with self._lock:
                            out = self._compiled[key](*args)
                            fn = self._build_step(2)
                            exe = jax.jit(fn).lower(args).compile()
                        return out, exe
                """,
        })
        assert "Engine.bad:compiled-executable-call" in keys(fs)
        assert "Engine.bad:executable-build:_build_step" in keys(fs)
        assert "Engine.bad:jit-lower-compile" in keys(fs)

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import jax

                class Cache:
                    def good(self, x):
                        y = jax.device_put(x, None)  # transfer OFF-lock
                        time.sleep(0)
                        with self._lock:
                            self._entries[id(x)] = y  # bookkeeping only
                            parts = ",".join(["a", "b"])  # str.join is fine
                        return y, parts
                """,
        })
        assert fs == []

    def test_deferred_closures_are_not_lock_held(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                class Cache:
                    def register(self, x):
                        with self._lock:
                            def probe():  # runs later, not under the lock
                                return jax.device_put(x, None)
                            self._probe = probe
                """,
        })
        assert fs == []


# ---------------------------------------------------------------------------
# JIT-HYGIENE
# ---------------------------------------------------------------------------


class TestJitHygiene:
    def test_flags_host_calls_and_concretization(self, tmp_path):
        fs = run_rule(tmp_path, JitHygieneRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import random
                import jax

                def traced(x, n):
                    t = time.time()
                    r = random.random()
                    v = x.item()
                    m = float(n)
                    return x * t * r * v * m

                fn = jax.jit(traced)
                """,
        })
        assert keys(fs) == {
            "traced:time.time",
            "traced:random.random",
            "traced:item",
            "traced:float:n",
        }

    def test_nested_loop_bodies_are_traced_too(self, tmp_path):
        fs = run_rule(tmp_path, JitHygieneRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import jax

                def gen(x):
                    def body(c):
                        return c + time.perf_counter()
                    return jax.lax.while_loop(lambda c: c < 9, body, x)

                fn = jax.jit(gen)
                """,
        })
        assert keys(fs) == {"gen:time.perf_counter"}

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, JitHygieneRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import jax
                import jax.numpy as jnp

                def traced(x, n):
                    return x * jnp.float32(n)  # jnp casts stay traced

                t0 = time.time()  # host code outside the traced fn: fine
                fn = jax.jit(traced)
                """,
        })
        assert fs == []

    def test_decorator_forms_are_traced(self, tmp_path):
        # the repo's dominant jit idiom: @jax.jit and
        # @functools.partial(jax.jit, ...) trace exactly like jit(f)
        fs = run_rule(tmp_path, JitHygieneRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import functools
                import time
                import jax

                @jax.jit
                def bare(x):
                    return x * time.time()

                @functools.partial(jax.jit, static_argnames=("n",))
                def partial_form(x, n):
                    return x * float(n) * time.perf_counter()
                """,
        })
        assert keys(fs) == {
            "bare:time.time",
            "partial_form:float:n",
            "partial_form:time.perf_counter",
        }

    def test_name_collision_with_host_method_does_not_leak(self, tmp_path):
        # regression: ContinuousEngine.step (host, times itself) shares its
        # name with the traced local `def step` — lexical scoping must bind
        # jit(step) to the sibling def, not the class method
        fs = run_rule(tmp_path, JitHygieneRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import time
                import jax

                class Engine:
                    def step(self):  # HOST method: timing is fine here
                        t0 = time.perf_counter()
                        return t0

                    def _build_step(self):
                        def step(cache):
                            return cache * 2
                        return jax.jit(step)
                """,
        })
        assert fs == []


# ---------------------------------------------------------------------------
# SHARDING-CONTRACT
# ---------------------------------------------------------------------------


class TestShardingContract:
    def test_flags_state_returning_jit_without_out_shardings(self, tmp_path):
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                def build(model):
                    def prefill(params, cache, toks):
                        new_cache = cache
                        return new_cache, toks
                    return jax.jit(prefill).lower().compile()
                """,
        })
        assert keys(fs) == {"jit:build.prefill"}

    def test_indirect_state_return_is_caught(self, tmp_path):
        # the _build_segment_kv shape: state tuple bound to a neutral name
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                def build(model):
                    def seg(params, cache):
                        out = (cache.k, cache.v)
                        return out
                    return jax.jit(seg).lower().compile()
                """,
        })
        assert keys(fs) == {"jit:build.seg"}

    def test_pinned_out_shardings_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                def build(model, specs):
                    def prefill(params, cache, toks):
                        return cache, toks
                    return jax.jit(prefill, out_shardings=specs).lower().compile()
                """,
        })
        assert fs == []

    def test_token_returning_executables_are_exempt(self, tmp_path):
        # regression (a benchmark's fwd): a value DERIVED from cache through a
        # call is logits, not state — call results don't taint the return,
        # whether bound to a temp or returned inline
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                def build(model):
                    def fwd(params, toks, cache):
                        logits, _ = model.apply(params, toks, cache)
                        return logits
                    return jax.jit(fwd)

                def build_inline(model):
                    def fwd2(params, toks, cache):
                        return model.apply(params, toks, cache)[0]
                    return jax.jit(fwd2)
                """,
        })
        assert fs == []

    def test_decorator_form_is_checked(self, tmp_path):
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import functools
                import jax

                @jax.jit
                def bad(params, cache):
                    return cache  # bare decorator cannot pin out_shardings

                @functools.partial(jax.jit, out_shardings=None)
                def pinned(params, cache):
                    return cache
                """,
        })
        assert keys(fs) == {"jit:bad"}

    def test_same_named_functions_get_distinct_fingerprints(self, tmp_path):
        # two ClassX.step methods must not collapse into one fingerprint —
        # a shared key would dedupe one finding and let a single baseline
        # entry mask every same-named function in the file
        fs = run_rule(tmp_path, ShardingContractRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                class A:
                    @jax.jit
                    def step(self, cache):
                        return cache

                class B:
                    @jax.jit
                    def step(self, cache):
                        return cache
                """,
        })
        assert keys(fs) == {"jit:A.step", "jit:B.step"}


# ---------------------------------------------------------------------------
# CONFIG-DRIFT
# ---------------------------------------------------------------------------


_CONFIG_OK = """
    import os

    def from_env(env=None):
        env = dict(os.environ if env is None else env)
        return env.get("TPU_RAG_FOO", "0")
    """
_DEPLOY_OK = """
    env:
      - name: TPU_RAG_FOO
        value: "0"
    """
_RUNBOOK_OK = """
    # RUNBOOK

    1. [Configuration reference](#configuration-reference)

    ## 8. Configuration reference

    | env var | default | meaning |
    |---|---|---|
    | `TPU_RAG_FOO` | `0` | the knob |

    ## 9. Operations
    """


class TestConfigDrift:
    def test_flags_env_read_outside_config(self, tmp_path):
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/obs/thing.py": """
                import os
                def probe():
                    return os.environ.get("TPU_RAG_THING", "1")
                """,
        })
        assert keys(fs) == {"env-read:probe"}

    def test_config_home_and_bootstrap_allowlist_are_exempt(self, tmp_path):
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/core/config.py": _CONFIG_OK,
            "rag_llm_k8s_tpu/server/main.py": """
                import os
                LEVEL = os.environ.get("TPU_RAG_LOG_LEVEL", "INFO")
                """,
            "deploy/llm/deploy.yaml": _DEPLOY_OK,
            "docs/RUNBOOK.md": _RUNBOOK_OK,
        })
        assert fs == []

    def test_flags_unpinned_knob(self, tmp_path):
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/core/config.py": _CONFIG_OK,
            "deploy/llm/deploy.yaml": "env: []\n",
            "docs/RUNBOOK.md": _RUNBOOK_OK.replace("TPU_RAG_FOO", "TPU_RAG_OTHER"),
        })
        assert keys(fs) == {"knob-deploy:TPU_RAG_FOO", "knob-runbook:TPU_RAG_FOO"}

    def test_prefix_knob_is_not_pinned_by_its_longer_sibling(self, tmp_path):
        # TPU_RAG_FOO must not read as deploy-pinned just because
        # TPU_RAG_FOO_EXTRA is (substring match would miss the drift)
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/core/config.py": """
                import os

                def from_env(env=None):
                    env = dict(os.environ if env is None else env)
                    return env.get("TPU_RAG_FOO", "0")

                def more(env):
                    return env.get("TPU_RAG_FOO_EXTRA")
                """,
            "deploy/llm/deploy.yaml": """
                env:
                  - name: TPU_RAG_FOO_EXTRA
                    value: "1"
                """,
            "docs/RUNBOOK.md": _RUNBOOK_OK.replace(
                "| `TPU_RAG_FOO` | `0` | the knob |",
                "| `TPU_RAG_FOO` | `0` | the knob |\n"
                "    | `TPU_RAG_FOO_EXTRA` | `1` | the other knob |",
            ),
        })
        assert keys(fs) == {"knob-deploy:TPU_RAG_FOO"}

    def test_missing_manifest_or_section_is_loud(self, tmp_path):
        # renaming deploy.yaml (or dropping the RUNBOOK section) must not
        # silently retire the whole pinning gate — same scanner-rot class
        # METRIC-DRIFT guards against
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/core/config.py": _CONFIG_OK,
            "docs/RUNBOOK.md": "# RUNBOOK\n\nno config section here\n",
        })
        assert keys(fs) == {
            "missing-deploy-manifest",
            "missing-runbook-config-section",
        }

    def test_knob_outside_config_section_does_not_count(self, tmp_path):
        # a troubleshooting aside naming the knob is not a table row
        runbook = _RUNBOOK_OK.replace("| `TPU_RAG_FOO` | `0` | the knob |", "") \
            + "\n    raise `TPU_RAG_FOO` when paged\n"
        fs = run_rule(tmp_path, ConfigDriftRule, {
            "rag_llm_k8s_tpu/core/config.py": _CONFIG_OK,
            "deploy/llm/deploy.yaml": _DEPLOY_OK,
            "docs/RUNBOOK.md": runbook,
        })
        assert keys(fs) == {"knob-runbook:TPU_RAG_FOO"}


# ---------------------------------------------------------------------------
# FAULT-SITE-REGISTRY
# ---------------------------------------------------------------------------


_FAULTS_FIXTURE = """
    SITES = ("alpha", "beta")

    def maybe_fail(site):
        pass

    def arm(site, times=1):
        pass
    """


class TestFaultSiteRegistry:
    def test_flags_unknown_site_and_untested_site(self, tmp_path):
        fs = run_rule(tmp_path, FaultSiteRegistryRule, {
            "rag_llm_k8s_tpu/resilience/faults.py": _FAULTS_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.resilience import faults
                def hot_path():
                    faults.maybe_fail("gamma")  # not in SITES
                """,
            "tests/test_thing.py": """
                def test_alpha():
                    assert "alpha"
                """,
        })
        assert keys(fs) == {"unknown-site:gamma", "untested-site:beta"}

    def test_docstring_mention_does_not_count_as_exercised(self, tmp_path):
        # exercised = EXACT string literal in a test; a docstring sentence
        # naming the site (with quotes, even) is not a test pulling it
        fs = run_rule(tmp_path, FaultSiteRegistryRule, {
            "rag_llm_k8s_tpu/resilience/faults.py": _FAULTS_FIXTURE,
            "tests/test_thing.py": '''
                """The "beta" site falls back to recompute."""

                def test_alpha():
                    assert "alpha"
                ''',
        })
        assert keys(fs) == {"untested-site:beta"}

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, FaultSiteRegistryRule, {
            "rag_llm_k8s_tpu/resilience/faults.py": _FAULTS_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.resilience import faults
                def hot_path():
                    faults.maybe_fail("alpha")
                """,
            "tests/test_thing.py": """
                def test_both():
                    assert "alpha" and "beta"
                """,
        })
        assert fs == []


# ---------------------------------------------------------------------------
# EVENT-REGISTRY
# ---------------------------------------------------------------------------

_FLIGHT_FIXTURE = """
    EVENTS = {
        "admit": "request admitted",
        "reset": "engine reset",
    }

    def emit(etype, request_id=None, **attrs):
        pass
"""

_EVENTS_DOC = """
    # Observability

    | event | meaning |
    |---|---|
    | `admit` | request admitted |
    | `reset` | engine reset |
"""


class TestEventRegistry:
    def test_flags_unknown_and_unemitted_events(self, tmp_path):
        fs = run_rule(tmp_path, EventRegistryRule, {
            "rag_llm_k8s_tpu/obs/flight.py": _FLIGHT_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.obs import flight
                def hot_path():
                    flight.emit("admitt", slot=1)  # typo: not in EVENTS
                    flight.emit("admit", slot=1)
                """,
            "docs/OBSERVABILITY.md": _EVENTS_DOC,
        })
        # "reset" is declared + documented but nothing emits it
        assert keys(fs) == {"unknown-event:admitt", "unemitted-event:reset"}

    def test_test_file_emits_do_not_satisfy_coverage(self, tmp_path):
        # a test calling flight.emit("reset") validates the literal but
        # does NOT count as the package instrumenting the decision point
        fs = run_rule(tmp_path, EventRegistryRule, {
            "rag_llm_k8s_tpu/obs/flight.py": _FLIGHT_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.obs import flight
                def hot_path():
                    flight.emit("admit", slot=1)
                """,
            "tests/test_thing.py": """
                from rag_llm_k8s_tpu.obs import flight
                def test_reset():
                    flight.emit("reset")
                """,
            "docs/OBSERVABILITY.md": _EVENTS_DOC,
        })
        assert keys(fs) == {"unemitted-event:reset"}

    def test_flags_undocumented_event_and_missing_doc(self, tmp_path):
        files = {
            "rag_llm_k8s_tpu/obs/flight.py": _FLIGHT_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.obs import flight
                def hot_path():
                    flight.emit("admit")
                    flight.emit("reset")
                """,
            # the doc table documents only one of the two; "reset" appears
            # in PROSE (unbackticked) and must not count
            "docs/OBSERVABILITY.md": """
                | `admit` | request admitted |

                After a reset the engine rebuilds its state.
            """,
        }
        fs = run_rule(tmp_path, EventRegistryRule, files)
        assert keys(fs) == {"undocumented-event:reset"}
        del files["docs/OBSERVABILITY.md"]
        fs = run_rule(tmp_path / "nodoc", EventRegistryRule, files)
        assert keys(fs) == {"events-doc-missing"}

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, EventRegistryRule, {
            "rag_llm_k8s_tpu/obs/flight.py": _FLIGHT_FIXTURE,
            "rag_llm_k8s_tpu/engine/thing.py": """
                from rag_llm_k8s_tpu.obs import flight
                def hot_path():
                    flight.emit("admit", slot=1)
                    flight.emit("reset")
                """,
            "docs/OBSERVABILITY.md": _EVENTS_DOC,
        })
        assert fs == []


# ---------------------------------------------------------------------------
# DEBUG-GATE
# ---------------------------------------------------------------------------


class TestDebugGate:
    def test_flags_ungated_debug_route(self, tmp_path):
        fs = run_rule(tmp_path, DebugGateRule, {
            "rag_llm_k8s_tpu/server/app.py": """
                class WsgiApp:
                    def __init__(self):
                        self.url_map = Map([
                            Rule("/debug/stuff", endpoint="debug_stuff",
                                 methods=["GET"]),
                            Rule("/healthz", endpoint="healthz"),
                        ])

                    def _debug_enabled(self):
                        return False

                    def ep_debug_stuff(self, request):
                        return {"secret": "journal"}  # no gate call

                    def ep_healthz(self, request):
                        return {"ok": True}  # non-debug: no gate needed
                """,
        })
        assert keys(fs) == {"ungated-debug-route:debug_stuff"}

    def test_flags_missing_handler(self, tmp_path):
        fs = run_rule(tmp_path, DebugGateRule, {
            "rag_llm_k8s_tpu/server/app.py": """
                class WsgiApp:
                    def __init__(self):
                        self.url_map = Map([
                            Rule("/debug/ghost", endpoint="debug_ghost"),
                        ])
                """,
        })
        assert keys(fs) == {"missing-handler:debug_ghost"}

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, DebugGateRule, {
            "rag_llm_k8s_tpu/server/app.py": """
                class WsgiApp:
                    def __init__(self):
                        self.url_map = Map([
                            Rule("/debug/stuff", endpoint="debug_stuff"),
                            Rule("/debug/faults", endpoint="debug_faults"),
                        ])

                    def _debug_enabled(self):
                        return False

                    def ep_debug_stuff(self, request):
                        if not self._debug_enabled():
                            return 403
                        return {"ok": True}

                    def ep_debug_faults(self, request):
                        if not faults.endpoint_enabled():
                            return 403
                        return {"ok": True}
                """,
        })
        assert fs == []

    def test_no_server_module_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, DebugGateRule, {
            "rag_llm_k8s_tpu/mod.py": "x = 1\n",
        })
        assert fs == []


# ---------------------------------------------------------------------------
# SIM-PURITY
# ---------------------------------------------------------------------------


class TestSimPurity:
    def test_flags_every_violation_class(self, tmp_path):
        fs = run_rule(tmp_path, SimPurityRule, {
            "rag_llm_k8s_tpu/sim/bad.py": """
                import jax
                import numpy as np
                from rag_llm_k8s_tpu.obs import flight
                import rag_llm_k8s_tpu.core.config
                from . import policy
                import os, json
                """,
        })
        assert keys(fs) == {
            "nonstdlib-import:jax",
            "nonstdlib-import:numpy",
            "package-import:rag_llm_k8s_tpu.obs",
            "package-import:rag_llm_k8s_tpu.core.config",
            "relative-import:",
        }
        assert all(f.rule == "SIM-PURITY" for f in fs)

    def test_flags_path_loaded_obs_modules(self, tmp_path):
        fs = run_rule(tmp_path, SimPurityRule, {
            "rag_llm_k8s_tpu/obs/goodput.py": """
                import numpy as np
                import time
                """,
        })
        assert keys(fs) == {"nonstdlib-import:numpy"}

    def test_pure_module_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, SimPurityRule, {
            "rag_llm_k8s_tpu/sim/ok.py": """
                import importlib.util
                import os
                from collections import deque
                from typing import Dict
                """,
            # the rest of the package is NOT held to the pure contract
            "rag_llm_k8s_tpu/engine/dev.py": """
                import jax
                from rag_llm_k8s_tpu.obs import flight
                """,
        })
        assert fs == []

    def test_repo_sim_modules_are_pure(self):
        # the real tree's pure set stays clean — the contract the rule
        # exists to hold (a finding here means someone imported jax or
        # the package into a path-loaded module)
        _, findings = core.run_analysis(
            str(REPO_ROOT), rules=[SimPurityRule()]
        )
        assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# DURABLE-WRITE
# ---------------------------------------------------------------------------


class TestDurableWrite:
    def test_flags_raw_write_and_bare_replace(self, tmp_path):
        fs = run_rule(tmp_path, DurableWriteRule, {
            "rag_llm_k8s_tpu/obs/flight.py": """
                import json
                import os

                def save_manifest(path, doc):
                    with open(path, "w") as f:
                        json.dump(doc, f)

                def swap(tmp, path):
                    os.replace(tmp, path)
                """,
        })
        assert keys(fs) == {
            "raw-open:save_manifest:w",
            "raw-replace:swap",
        }
        assert all(f.rule == "DURABLE-WRITE" for f in fs)

    def test_compliant_twin_is_silent(self, tmp_path):
        # the helper itself owns the tmp-write + replace; append-mode
        # (the WAL's per-event fsync discipline) and reads are exempt,
        # and modules outside the writer set are not held to the rule
        fs = run_rule(tmp_path, DurableWriteRule, {
            "rag_llm_k8s_tpu/obs/flight.py": """
                import json
                import os

                def durable_write(path, obj):
                    tmp = path + ".tmp"
                    with open(tmp, "w") as f:
                        json.dump(obj, f)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(tmp, path)

                def append_event(path, line):
                    with open(path, "a") as f:
                        f.write(line)

                def load(path):
                    with open(path) as f:
                        return json.load(f)
                """,
            "rag_llm_k8s_tpu/engine/other.py": """
                def scratch(path):
                    with open(path, "w") as f:
                        f.write("not durable state")
                """,
        })
        assert fs == []

    def test_repo_writer_modules_are_compliant(self):
        # the real tree holds the discipline — a finding here means a raw
        # write-mode open or bare os.replace crept into a writer module
        _, findings = core.run_analysis(
            str(REPO_ROOT), rules=[DurableWriteRule()]
        )
        assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# METRIC-DRIFT
# ---------------------------------------------------------------------------


class TestMetricDrift:
    def test_flags_undocumented_metric(self, tmp_path):
        fs = run_rule(tmp_path, MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": """
                def bind(reg):
                    reg.counter("rag_widgets_total", "widgets")
                """,
            "docs/OBSERVABILITY.md": "| `rag_other_total` | counter |\n",
        })
        assert keys(fs) == {"undocumented:rag_widgets_total"}

    def test_flags_inconsistent_label_sets(self, tmp_path):
        fs = run_rule(tmp_path, MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": """
                def bind(reg):
                    fam = reg.labeled_counter("rag_widgets_total", "widgets")
                    fam.labels(stage="a").inc()
                    fam.labels(phase="b").inc()  # same family, new label name
                """,
            "docs/OBSERVABILITY.md": "| `rag_widgets_total` | counter |\n",
        })
        assert len(fs) == 1
        assert fs[0].key.startswith("labelset:rag_widgets_total:")

    def test_flags_dynamic_label_value(self, tmp_path):
        fs = run_rule(tmp_path, MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": """
                def bind(reg, i):
                    fam = reg.labeled_counter("rag_widgets_total", "widgets")
                    fam.labels(stage=f"s{i}").inc()
                """,
            "docs/OBSERVABILITY.md": "| `rag_widgets_total` | counter |\n",
        })
        assert keys(fs) == {"dynamic-label:rag_widgets_total:stage"}

    def test_compliant_twin_is_silent(self, tmp_path):
        fs = run_rule(tmp_path, MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": """
                def bind(reg, code):
                    fam = reg.labeled_counter("rag_widgets_total", "widgets")
                    fam.labels(stage="a").inc()
                    fam.labels(stage=str(code)).inc()  # bounded str() is fine
                """,
            "docs/OBSERVABILITY.md": "| `rag_widgets_total` | counter |\n",
        })
        assert fs == []

    def test_zero_registrations_with_doc_is_scanner_rot(self, tmp_path):
        # the old check_metrics_docs self-check: a tree shipping an
        # OBSERVABILITY.md in which the scanner finds NO registrations
        # means the matcher broke — fail loudly, never vacuously pass
        fs = run_rule(tmp_path, MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": "def nothing():\n    pass\n",
            "docs/OBSERVABILITY.md": "| `rag_widgets_total` | counter |\n",
        })
        assert keys(fs) == {"no-registrations-found"}
        # fixture repos WITHOUT the doc stay silent (no metrics surface)
        fs = run_rule(tmp_path / "bare", MetricDriftRule, {
            "rag_llm_k8s_tpu/mod.py": "def nothing():\n    pass\n",
        })
        assert fs == []


# ---------------------------------------------------------------------------
# framework: suppressions, baseline, CLI
# ---------------------------------------------------------------------------


class TestFramework:
    def test_inline_suppression(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                class Cache:
                    def known(self, x):
                        with self._lock:
                            # one-time init, measured harmless  # ragcheck: disable=LOCK-DISCIPLINE
                            return jax.device_put(x, None)
                """,
        })
        assert fs == []

    def test_suppression_is_per_rule(self, tmp_path):
        fs = run_rule(tmp_path, LockDisciplineRule, {
            "rag_llm_k8s_tpu/mod.py": """
                import jax

                class Cache:
                    def known(self, x):
                        with self._lock:
                            # ragcheck: disable=JIT-HYGIENE
                            return jax.device_put(x, None)
                """,
        })
        assert keys(fs) == {"Cache.known:device_put"}

    def test_baseline_gate_and_ratchet(self):
        findings = [
            core.Finding("R", "a.py", 3, "m", "k1"),
            core.Finding("R", "b.py", 9, "m", "k2"),
        ]
        baseline = {"R::a.py::k1": "known"}
        new, stale = core.gate(findings, baseline)
        assert [f.key for f in new] == ["k2"] and stale == []
        # the ratchet: a GROWN baseline (an entry nothing fires for) fails
        grown = dict(baseline, **{"R::zombie.py::gone": "stale"})
        new, stale = core.gate(findings, grown)
        assert stale == ["R::zombie.py::gone"]

    def test_baseline_requires_justification(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text('{"entries": [{"fingerprint": "R::a.py::k"}]}')
        with pytest.raises(ValueError, match="justification"):
            core.load_baseline(str(p))

    def test_cli_exits_nonzero_on_seeded_violation(self, tmp_path):
        from scripts.ragcheck.__main__ import main

        (tmp_path / "rag_llm_k8s_tpu").mkdir()
        (tmp_path / "rag_llm_k8s_tpu" / "mod.py").write_text(
            "import jax\n\n"
            "class C:\n"
            "    def bad(self, x):\n"
            "        with self._lock:\n"
            "            return jax.device_put(x, None)\n"
        )
        empty = tmp_path / "no_baseline.json"  # absent file = empty baseline
        assert main(["--root", str(tmp_path), "--baseline", str(empty)]) == 1
        # --json still exits 1 and is parseable
        assert main(
            ["--root", str(tmp_path), "--baseline", str(empty), "--json"]
        ) == 1


# ---------------------------------------------------------------------------
# the whole-repo gate (what `make analyze` enforces)
# ---------------------------------------------------------------------------


class TestWholeRepo:
    def test_repo_tree_is_clean_against_baseline(self):
        _, findings = core.run_analysis(str(REPO_ROOT))
        baseline = core.load_baseline(str(BASELINE))
        new, stale = core.gate(findings, baseline)
        assert new == [], "unbaselined findings:\n" + "\n".join(
            f.render() for f in new
        )
        assert stale == [], f"stale baseline entries (delete them): {stale}"

    @pytest.mark.parametrize("scan_root", core.Repo.SCAN_ROOTS)
    def test_every_scan_root_exists(self, scan_root):
        """A scan root that is gone is skipped in silence (``Repo.__init__``
        walks what it finds), so a deleted file would stay listed for ever."""
        assert (REPO_ROOT / scan_root).exists()

    def test_grown_baseline_fails(self):
        _, findings = core.run_analysis(str(REPO_ROOT))
        baseline = core.load_baseline(str(BASELINE))
        baseline["CONFIG-DRIFT::rag_llm_k8s_tpu/gone.py::env-read:nope"] = "x"
        _, stale = core.gate(findings, baseline)
        assert stale  # the extra entry reads as stale -> make analyze fails

    def test_cli_green_on_repo(self):
        from scripts.ragcheck.__main__ import main

        assert main(["--root", str(REPO_ROOT), "--baseline", str(BASELINE)]) == 0

    def test_metric_docs_shim_still_works(self):
        import importlib

        shim = importlib.import_module("scripts.check_metrics_docs")
        assert shim.main() == 0
