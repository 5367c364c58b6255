#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` ``workloads``; its configuration
and traffic are the data files that entry names. Set-up (weights from the
seed, ``assemble_service``, ``warmup()``, a real server, one uploaded PDF, a
few unmeasured requests) ends when the window opens; the window lasts
``--seconds`` (a closed loop whose callers are through their plans sooner
ends there); what was due in it is drained, then a seeded sample of what was
served is scored against the program's exact path and against the plain
float32 reference. The configuration's published ``model_type`` names the
decoder family: ``families/<model_type>.py`` makes the file a model and its
seeded weights, ``references/<model_type>.py`` is the reference its answers
are judged by; this file knows neither. Every stdout line but the last is
information; the last is the result object.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)
GIB = float(1 << 30)
# The traced slice is the window's end, so that writing the trace out disturbs
# no request. Stopping the profiler costs ~0.1 ms an event (PR 23: 65 s for the
# 0.7 M events of 8 s on one chip, 310 s for 2.7 M on four, which broke the
# 360 s a run may take), so more chips get a shorter slice.
TRACE_SECONDS_ONE_CHIP = 8.0
TRACE_SECONDS_FLOOR = 2.5
N_AUDITS = 4
OPEN_WORKERS = 48  # sender threads of an open loop: more than it ever has in flight

# --allow-cpu-rehearsal: the same control flow at a size the CPU finishes in
# a minute (the toy model is the family's ``REHEARSAL_MODEL``). Its result line
# says ``correct: false`` and its numbers mean nothing.
REHEARSAL = {
    "engine": dict(prompt_buckets=[512], max_seq_len=544, max_batch_size=4,
                   max_chunked_prompt=2048),
    "traffic": dict(corpus_pages=3, words_per_page=60, max_new_tokens=8, question_pool=8),
    "retrieval": dict(chunk_size=40, chunk_overlap=8),
    "tokenizer_vocab": 512, "encoder_pieces": 2000, "corpus_mb": 0.3,
}


def say(event: str, **fields) -> None:
    """One information line on stdout."""
    fields = {"event": event, "t": round(time.monotonic() - T_START, 2), **fields}
    print(json.dumps(fields, default=str), flush=True)


def load_cell(name: str):
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, config


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    """The cell's metrics of one kind: those that list it, or list no cell."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_reader(kind: str, name: str):
    """``benchmark/end_to_end/<metric>.py`` or ``benchmark/layer_metrics/
    <metric>.py``: ``read(ctx)`` -> a number, or None for nothing to read."""
    path = os.path.join(BENCH_DIR, READER_DIRS[kind], name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(bench: dict, kind: str, cell: str, ctx: dict) -> dict:
    out = {}
    for m in metrics_of(bench, kind, cell):
        value = load_reader(kind, m["name"]).read(ctx)
        if value is None:  # nothing to read: the metric is left out of the line
            continue
        value = float(value)
        out[m["name"]] = {"value": value if value == value and abs(value) != float("inf") else 1e12,
                          "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# load: closed and open loops over the socket
# ---------------------------------------------------------------------------


def run_closed(served, plans, t_open: float, seconds: float, records: list) -> list:
    """``len(plans)`` callers, each sending the next question of its plan when
    its last answer has arrived, until the plan is through or the window
    closes; a request is due the instant its caller is free. The plan is the
    work: it does not repeat, so no speed of the program lets a seed choose
    which questions are measured."""
    lock = threading.Lock()
    t_close = t_open + seconds

    def client(plan):
        for question in plan:
            due = time.monotonic()
            if due >= t_close:
                return
            rec = served.generate(question, due)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(p,), name=f"client-{i}")
               for i, p in enumerate(plans)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def run_open(served, plan, due_offsets, t_open: float, records: list) -> list:
    """Requests sent when they are due, whatever is still in flight, each
    timed from its due instant; ``late_ms`` is how late the sender ran."""
    lock = threading.Lock()
    jobs: queue.Queue = queue.Queue()

    def worker():
        while True:
            job = jobs.get()
            if job is None:
                return
            question, due = job
            rec = served.generate(question, due)
            rec["late_ms"] = (rec["start"] - due) * 1e3
            with lock:
                records.append(rec)

    workers = [threading.Thread(target=worker, name=f"sender-{i}") for i in range(OPEN_WORKERS)]
    for w in workers:
        w.start()
    for question, off in zip(plan, due_offsets):
        due = t_open + off
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        jobs.put((question, due))
    for _ in workers:
        jobs.put(None)
    for w in workers:
        w.join()
    return records  # the caller waits out what is left of the window


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; prints correct: false")
    args = ap.parse_args()
    rehearsal = args.allow_cpu_rehearsal

    bench, cell, cfg_entry = load_cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])
    chips = int(cell["chips"])
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={chips}")

    from benchmark.lib import serve, stats, trace, traffic

    cfg, family = serve.load_config(os.path.join(REPO, cfg_entry["file"]))
    reference = serve.load_reference(cfg["model_type"])
    mix = traffic.load_traffic(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    serving = cfg["serving"]
    if rehearsal:
        cfg.update(family.REHEARSAL_MODEL)
        serving = dict(serving, engine=dict(serving.get("engine", {}), **REHEARSAL["engine"]),
                       tokenizer_vocab=REHEARSAL["tokenizer_vocab"])
        cfg["serving"] = serving
        mix.update(REHEARSAL["traffic"])
    new_tokens = int(mix["max_new_tokens"])

    # tokenizers first: training touches no JAX, and the chip stays free
    t0 = time.monotonic()
    if rehearsal:
        serve.STATE_DIR = os.path.join(serve.STATE_DIR, "rehearsal")
    bpe_path, uni_path, trained = serve.ensure_tokenizers(
        int(serving["tokenizer_vocab"]),
        REHEARSAL["encoder_pieces"] if rehearsal else 250000,
        REHEARSAL["corpus_mb"] if rehearsal else 24.0)
    say("tokenizers", trained_now=trained, seconds=round(time.monotonic() - t0, 1))

    import jax

    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX sees {devices[0].platform}); there is no CPU "
              "fallback (--allow-cpu-rehearsal runs a tiny rehearsal)", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:chips]

    from rag_llm_k8s_tpu.core.compile_cache import cache_entry_count, ensure_compile_cache
    from rag_llm_k8s_tpu.core.config import DTypePolicy, EncoderConfig, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.native.build import load_library
    from rag_llm_k8s_tpu.server.main import assemble_service
    from rag_llm_k8s_tpu.tokenizer import load_tokenizer

    cache_dir = ensure_compile_cache()
    entries0 = cache_entry_count(cache_dir)
    say("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__, compile_cache=cache_dir,
        cache_entries_before=entries0)
    peaks = None if rehearsal else stats.load_peaks(devices[0].device_kind)

    counter, errors = serve.CompileCounter(), serve.ErrorLog()
    # importing server.main configures the root logger at INFO: an access line
    # per request on stderr is load the generator's own process would pay for
    logging.getLogger().setLevel(logging.WARNING)
    logging.getLogger("werkzeug").setLevel(logging.ERROR)
    logging.getLogger("rag_llm_k8s_tpu").addHandler(errors)

    # ---- weights, from the seed, on the device, in one call -----------------
    t0 = time.monotonic()
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=int(serving["tp"])), devices=devices)
    dtypes = DTypePolicy()
    model = family.model_config(cfg)
    # the weights are the configuration's, not the run's: with speculation an
    # answer's cost follows them, and a deployment serves one model
    weights_seed = int(serving["weights_seed"])
    params = family.make_params(
        model, dtypes, weights_seed, serving["weight_quant"], mesh, float(serving["recite_gain"]))
    enc_cfg = EncoderConfig.tiny(vocab_size=REHEARSAL["encoder_pieces"]) if rehearsal \
        else getattr(EncoderConfig, serving["encoder"])()
    enc_params = serve.make_encoder_params(enc_cfg, dtypes, weights_seed)
    jax.block_until_ready((params, enc_params))
    say("params", family=os.path.relpath(family.__file__, BENCH_DIR),
        model={k: cfg.get(k) for k in family.PUBLISHED_KEYS},
        weights=serving["weight_quant"], kv=serving["kv_quant"], tp=serving["tp"],
        seconds=round(time.monotonic() - t0, 1),
        hbm_in_use_gib=[round((d.memory_stats() or {}).get("bytes_in_use", 0) / GIB, 2)
                        for d in devices])

    llm_tok, enc_tok = load_tokenizer(bpe_path), load_tokenizer(uni_path)
    native = {n: load_library(n) is not None for n in ("bpe", "indexio")}
    if not all(native.values()):
        raise RuntimeError(f"native libraries fell back to Python: {native}")

    work = os.path.join(serve.STATE_DIR, "work")  # index, uploads, incident spool
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    attn = "xla" if rehearsal else serve.ATTN_IMPL
    config = serve.app_config(cfg, model, work, new_tokens, attn_impl=attn)
    if rehearsal:
        import dataclasses
        from rag_llm_k8s_tpu.core.config import GoodputConfig
        config = dataclasses.replace(
            config, encoder=enc_cfg,  # the CPU has no row in the program's table of peaks
            engine=dataclasses.replace(config.engine, goodput=GoodputConfig(enabled=False)),
            retrieval=dataclasses.replace(config.retrieval, embed_dim=enc_cfg.embed_dim,
                                          **REHEARSAL["retrieval"]))
    service = assemble_service(
        config, mesh, model, params, llm_tok, enc_params, enc_tok,
        encoder_attn_impl="xla" if rehearsal else "flash")
    del params

    mark = counter.mark()
    hits0, miss0 = counter.cache_hits, counter.cache_misses
    t0 = time.monotonic()
    service.warmup()
    built = counter.builds[mark:]
    say("warmup", seconds=round(time.monotonic() - t0, 1), executables=len(built),
        compile_seconds=round(sum(s for _, s in built), 1),
        cache_hits=counter.cache_hits - hits0, cache_misses=counter.cache_misses - miss0)
    if not service.ready:
        raise RuntimeError("service not ready after warmup()")

    served = serve.Served(service)
    result = None
    try:
        t0 = time.monotonic()
        mark = counter.mark()
        pdf = traffic.corpus_pdf(int(mix["content_seed"]), int(mix["corpus_pages"]),
                                 int(mix["words_per_page"]))
        info = served.upload(pdf)
        by_upload = counter.builds[mark:]
        say("upload_pdf", seconds=round(time.monotonic() - t0, 1), pdf_bytes=len(pdf),
            total_vectors=info.get("total_vectors"), executables_built=len(by_upload),
            compile_seconds=round(sum(s for _, s in by_upload), 1))

        # ---- lead-in: unmeasured, so speculative=auto has settled ----------
        n_clients = int(mix.get("clients", 1)) if mix["loop"] == "closed" else 1
        lead_plan = iter(traffic.question_plan(args.seed, mix, 16, stream=9999))
        t0 = time.monotonic()
        lead = [served.generate(next(lead_plan), time.monotonic())
                for _ in range(int(mix["lead_in_requests"]))]
        if mix["loop"] == "open" or n_clients > 1:
            # one concurrent group, so the batched path has run once too
            group = []
            ths = [threading.Thread(
                target=lambda q=next(lead_plan): group.append(served.generate(q, time.monotonic())))
                for _ in range(min(4, config.engine.max_batch_size))]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            lead += group
        bad = [r for r in lead if r["status"] != 200]
        if bad:
            raise RuntimeError(f"lead-in request failed: {bad[0]}")
        prompt_tokens = sorted(len(p() if callable(p) else p) for _, p in served.delivered)
        say("lead_in", requests=len(lead), seconds=round(time.monotonic() - t0, 1),
            latencies_s=[round(r["end"] - r["start"], 2) for r in lead],
            prompt_tokens_min=prompt_tokens[0], prompt_tokens_median=prompt_tokens[len(prompt_tokens) // 2],
            prompt_tokens_max=prompt_tokens[-1], prompt_bucket=max(config.engine.prompt_buckets),
            timings=lead[0]["timings"])
        if not rehearsal and prompt_tokens[-1] > max(config.engine.prompt_buckets):
            raise RuntimeError("a prompt is longer than the largest bucket: the set-up is wrong")

        # ---- the window ------------------------------------------------------
        served.delivered.clear()
        before = stats.parse_exposition(served.scrape())
        mark_window = counter.mark()
        n_err0 = len(errors.records)
        tracer = None
        trace_dir = os.path.join(serve.STATE_DIR, "trace")
        trace_wall = [0.0, 0.0]
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

            trace_s = min(max(TRACE_SECONDS_ONE_CHIP / chips, TRACE_SECONDS_FLOOR),
                          max(seconds - 2.0, 1.0))

            def trace_window():
                # the slice is the end of the work: the window's end, or the
                # plans' where a closed loop will be through them sooner
                while True:
                    now = time.monotonic() - t_open
                    end = seconds
                    if planned and records:
                        end = min(seconds, now * planned / len(records))
                    if end - now <= trace_s + 0.5:
                        break
                    time.sleep(0.05)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                trace_wall[0] = time.monotonic()
                time.sleep(trace_s)
                trace_wall[1] = time.monotonic()
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=trace_window, name="tracer")
        records, planned = [], 0
        if mix["loop"] == "closed":
            plans = [traffic.question_plan(args.seed, mix, int(mix["plan_requests"]), stream=i)
                     for i in range(n_clients)]
            planned = sum(len(p) for p in plans)
        else:
            due = traffic.open_schedule(args.seed, mix, seconds)
            plan = traffic.question_plan(args.seed, mix, len(due))
        t_open = time.monotonic()
        setup_s = t_open - T_START
        if tracer:
            tracer.start()
        if mix["loop"] == "closed":
            run_closed(served, plans, t_open, seconds, records)
        else:
            run_open(served, plan, due, t_open, records)
        t_drained = time.monotonic()
        # the window closes at --seconds; a closed loop that is through its
        # plans sooner has nothing more to offer and closes with its last answer
        t_close = t_open + seconds
        if planned and len(records) == planned:
            t_close = min(t_close, t_drained)
        time.sleep(max(0.0, t_close - t_drained))
        if tracer:
            tracer.join()
        after = stats.parse_exposition(served.scrape())
        in_window = counter.builds[mark_window:]
        for r in records:
            r["tokens"] = new_tokens if r["status"] == 200 else 0
        short = [len(e) for e, _ in served.delivered if len(e) != new_tokens]
        n_ok = sum(1 for r in records if r["status"] == 200)
        late = [r["late_ms"] for r in records if "late_ms" in r]
        # what the admission layer did, in every run (the per-layer readers
        # speak only in traced ones): a round of callers that was not
        # coalesced into one batch shows as more dispatches and a longer drain
        dispatches = stats.delta(before, after, "tpu_rag_engine_generate_calls")
        say("window", loop=mix["loop"], seconds=seconds, drained_after_s=round(t_drained - t_open - seconds, 2),
            attempted=len(records), planned=planned or None, ok=n_ok,
            output_tok_per_s=round(n_ok * new_tokens / (t_drained - t_open), 3),
            generate_dispatches=dispatches,
            latencies_ms=sorted(round((r["end"] - r["due"]) * 1e3) for r in records),
            delivered_streams=len(served.delivered),
            streams_not_of_budget=short, executables_built_in_window=in_window,
            generator_late_ms_max=round(max(late), 2) if late else None,
            generator_late_ms_mean=round(sum(late) / len(late), 3) if late else None,
            inflight_at_middle=sum(1 for r in records if r["due"] <= (t_open + t_close) / 2 < r["end"]),
            inflight_at_end=sum(1 for r in records if r["due"] <= t_close < r["end"]),
            errors=[r["error"] for r in records if r["error"]][:3])

        # ---- correct: a seeded sample of what was served, scored by the program's
        # exact path and by the family's plain float32 reference (references/) ---
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices),  # the peak of serving: the reference below is not the system
                  "memory_peak_bytes": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                                           for d in devices)}
        rng = random.Random(args.seed * 31 + 7)
        sample = [(list(p() if callable(p) else p), e) for e, p in
                  rng.sample(served.delivered, min(N_AUDITS, len(served.delivered)))]
        t0 = time.monotonic()
        exact = [service.engine.score_exact(p, e) for p, e in sample]
        refs = reference.score(service.engine.params, cfg, sample, devices[0])
        audits = [stats.judge_audit(x, e) for x, (_, e) in zip(exact, sample)]
        ref_gaps = [stats.half_gap_max(r) for r in refs]
        ref_errs = [stats.logit_err_max(x, r) for x, r in zip(exact, refs)]
        new_errors = errors.records[n_err0:]
        say("audit", reference=os.path.relpath(reference.__file__, BENCH_DIR), audits=len(audits),
            err=[round(a, 5) for a in audits], tolerance=serve.AUDIT_TOL,
            reference_half_gap=[round(g, 5) for g in ref_gaps], half_gap_tolerance=reference.HALF_GAP_TOL,
            reference_logit_err=[round(e, 5) for e in ref_errs], logit_tolerance=reference.LOGIT_TOL,
            seconds=round(time.monotonic() - t0, 1), errors_logged=new_errors[:3])
        failed = stats.n_failed(records, new_tokens) + len(short)
        correct = bool(
            audits and all(a <= serve.AUDIT_TOL for a in audits)
            and all(g <= reference.HALF_GAP_TOL for g in ref_gaps)
            and all(e <= reference.LOGIT_TOL for e in ref_errs)
            and failed == 0 and records and len(served.delivered) == n_ok
            and not in_window and not new_errors and not rehearsal)

        ctx = {
            "requests": records, "window": (t_open, t_close), "seconds": seconds,
            "new_tokens": new_tokens, "setup_s": setup_s, "before": before, "after": after,
            "config": cfg, "traffic": mix, "chips": chips, "peaks": peaks, "trace": None,
            "stats": stats, "prompt_tokens": None,
            "layer_loop_trips": family.layer_loop_trips(cfg),
        }
        result = {"correct": correct, "attempted": len(records), "failed": failed}
        if args.trace:
            t0 = time.monotonic()
            planes = trace.load_xplane(trace.find_xplane(trace_dir), cpu_as_device=rehearsal)
            reduced = trace.reduce_trace(planes, chips)
            reduced["window_s"] = max(reduced["window_s"], trace_wall[1] - trace_wall[0])
            ctx["trace"] = reduced
            ctx["prompt_tokens"] = [len(p() if callable(p) else p) for _, p in served.delivered]
            say("trace", seconds_to_reduce=round(time.monotonic() - t0, 1),
                planes={p: {ln: len(evs) for ln, evs in lines.items()} for p, lines in planes.items()},
                op_groups=reduced["device_op_groups"], busy_s_per_chip=reduced["busy_s_per_chip"],
                kernels=reduced["kernels"], idle_gaps=reduced["idle_gaps"])
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["metrics"] = read_metrics(bench, "per_layer", cell["name"], ctx)
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        else:
            result["metrics"] = read_metrics(bench, "end_to_end", cell["name"], ctx)
        result["device"] = device
        say("done", seconds=round(time.monotonic() - T_START, 1), setup_s=round(setup_s, 1),
            executables_built=len(counter.builds), cache_hits=counter.cache_hits,
            cache_misses=counter.cache_misses, cache_entries_before=entries0,
            cache_entries_after=cache_entry_count(cache_dir),
            peak_hbm_gib=[round((d.memory_stats() or {}).get("peak_bytes_in_use", 0) / GIB, 2)
                          for d in devices])
    finally:
        served.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
