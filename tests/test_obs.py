"""Structured tracing + metrics tests (DESIGN.md §15).

Covers the whole observability contract: the disabled-mode no-op fast path
(zero new objects, bounded overhead), deterministic span trees under the
``deterministic-ci`` profile, cross-process shard merging from a 2-worker
``compile_many``, Chrome/Perfetto trace-event schema validation via
``tools/trace_report.py``, the ``exact_s`` phase-accounting fix, the
two-layer cache counters, the metrics-block parity between the
in-process, batch, and pooled paths, and spans reaching a JAX profiler
trace while one collects.
"""

import importlib.util
import json
import os

import pytest

from repro import obs
from repro.api import Compiler, resolve_options
from repro.core import CGRA, running_example
from repro.core.benchsuite import load_suite
from repro.core.mapper import clear_mapping_cache, memory_cache_stats
from repro.core.service import CompileJob, compile_many

_TOOL = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "trace_report.py")


def _trace_report():
    spec = importlib.util.spec_from_file_location("trace_report", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ci_compiler(**overrides):
    return Compiler(CGRA(4, 4), resolve_options("deterministic-ci"),
                    **overrides)


def _traced_compile(dfg, **overrides):
    comp = _ci_compiler(**overrides)
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        result = comp.compile(dfg)
    return result, tracer


# ------------------------------------------------- disabled-mode contract

def test_disabled_span_is_shared_noop_singleton():
    """With no tracer installed, span() returns ONE shared no-op object —
    the zero-allocation contract that lets call sites live in hot loops."""
    assert not obs.enabled()
    s1 = obs.span("time.probe", ii=4)
    s2 = obs.span("space.probe", ii=9)
    assert s1 is s2 is obs._NULL_SPAN
    with s1 as s:
        s.set(found=True)           # no-op, returns self
    obs.event("cache.memory.hit")   # no-op
    obs.incr("anything")            # no-op
    assert obs.get_tracer() is None


def test_disabled_overhead_is_negligible():
    """50k disabled spans must cost well under half a second (they are a
    None check + a shared singleton; generous bound to stay CI-proof)."""
    import time

    t0 = time.perf_counter()
    for i in range(50_000):
        with obs.span("time.probe", ii=i):
            pass
    assert time.perf_counter() - t0 < 0.5


def test_untraced_compile_unaffected_by_instrumentation():
    """A traced and an untraced deterministic compile take the identical
    search path — instrumentation must never consume rng or change
    budgets."""
    dfg = running_example()
    plain = _ci_compiler().compile(dfg)
    traced, _ = _traced_compile(dfg)
    assert plain.ok and traced.ok
    assert plain.ii == traced.ii
    assert plain.mapping.t_abs == traced.mapping.t_abs
    assert plain.mapping.placement == traced.mapping.placement
    assert plain.metrics["solver"] == traced.metrics["solver"]


# ------------------------------------------------------ span-tree capture

def test_span_tree_deterministic():
    """Two deterministic-ci compiles of the same kernel record the same
    span/event name sequence with the same (ii, slack) attributes."""
    dfg = load_suite(names=["bitcount"])["bitcount"]

    def signature():
        _, tracer = _traced_compile(dfg)
        return [(e["name"], e["args"].get("ii"), e["args"].get("slack"))
                for e in tracer.events]

    sig1, sig2 = signature(), signature()
    assert sig1 == sig2
    names = [n for n, _, _ in sig1]
    for expected in ("compile", "time.probe", "space.probe",
                     "mapper.window.open", "mapper.round"):
        assert expected in names, expected


def test_span_covers_phase_total():
    """The root compile span must cover the phase-timing total (it wraps
    the whole mapper call), and not exceed it wildly."""
    dfg = load_suite(names=["fft"])["fft"]
    result, tracer = _traced_compile(dfg)
    assert result.ok
    span_s = tracer.span_totals()["compile"]
    total_s = result.phases.total_s
    assert span_s >= total_s * 0.9
    # the wrapper adds result construction only — sanity-bound the slack
    assert span_s <= total_s * 1.5 + 0.05


def test_trace_json_is_perfetto_schema_valid(tmp_path):
    """The written Chrome trace-event JSON passes trace_report --check."""
    dfg = running_example()
    out = tmp_path / "trace.json"
    comp = _ci_compiler()
    with obs.session(str(out)):
        res = comp.compile(dfg)
    assert res.ok and out.exists()
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    tr = _trace_report()
    assert tr.check(doc) == []
    # the summary renders without error and mentions the span table
    text = "\n".join(tr.summarize(doc))
    assert "time.probe" in text or "compile" in text


def test_trace_report_check_catches_malformed():
    tr = _trace_report()
    assert tr.check({}) != []                      # no traceEvents
    assert tr.check({"traceEvents": []}) != []     # empty
    bad = {"traceEvents": [{"ph": "X", "name": "a", "ts": 0, "dur": -1,
                            "pid": 1, "tid": 1, "args": {}}]}
    assert any("dur" in v for v in tr.check(bad))


# ------------------------------------------------- cross-process shards

def test_two_worker_shard_merge(tmp_path):
    """compile_many with 2 pool workers writes per-pid span shards that
    merge onto one timeline with worker-pid attribution."""
    suite = load_suite(names=["bitcount", "fft"])
    cgra = CGRA(4, 4)
    batch = [CompileJob(d, cgra) for d in suite.values()]
    report = compile_many(batch, jobs=2, deterministic=True,
                          use_cache=False, trace_dir=str(tmp_path))
    assert report.ok and report.num_workers == 2
    events, counters = obs.merge_shards(str(tmp_path))
    assert events, "workers wrote no span shards"
    pids = {e["pid"] for e in events}
    assert os.getpid() not in pids          # all spans came from workers
    job_spans = [e for e in events if e["name"] == "job"]
    assert {e["args"]["kernel"] for e in job_spans} == set(suite)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in job_spans)


def test_batch_compile_adopts_worker_shards(tmp_path):
    """Compiler.compile_batch merges worker shards into the active tracer
    so one trace file holds the whole cross-process timeline."""
    suite = load_suite(names=["bitcount", "fft"])
    comp = _ci_compiler(jobs=2)
    out = tmp_path / "batch.json"
    with obs.session(str(out)):
        batch = comp.compile_batch(list(suite.values()))
    assert batch.ok
    doc = json.loads(out.read_text())
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert len(pids) >= 2, "expected spans from at least two processes"
    assert _trace_report().check(doc) == []


def test_merge_shards_tolerates_torn_shard(tmp_path):
    good = [{"name": "job", "ph": "X", "ts": 1.0, "dur": 2.0,
             "pid": 1, "tid": 1, "args": {}}]
    obs.append_shard(str(tmp_path), good, {"jobs": 1})
    (tmp_path / "shard-9999.jsonl").write_text('{"name": "torn', "utf-8")
    events, counters = obs.merge_shards(str(tmp_path))
    assert events == good
    assert counters == {"jobs": 1}


# ------------------------------------------------------- metrics + phases

def test_exact_s_phase_accounted():
    """Satellite 1: certification wall time lands in phases.exact_s and is
    included in total_s (and the metrics block mirrors the final phases)."""
    dfg = running_example()
    comp = _ci_compiler(exact_check=True, exact_budget_s=10.0)
    res = comp.compile(dfg)
    assert res.ok and res.certificate is not None
    assert res.phases.exact_s > 0.0
    row = res.as_dict()
    assert row["phases"]["exact_s"] == pytest.approx(res.phases.exact_s,
                                                     abs=1e-6)
    assert res.phases.total_s >= res.phases.exact_s
    non_exact = (res.phases.time_s + res.phases.space_s
                 + res.phases.validate_s)
    assert res.phases.total_s >= non_exact + res.phases.exact_s - 1e-6
    assert res.metrics["phases"] == row["phases"]


def test_metrics_block_parity_across_paths():
    """The metrics block has the same schema — and, deterministically, the
    same solver counters — from compile(), compile_batch jobs=1, and
    compile_batch jobs=2 (pooled)."""
    dfg = load_suite(names=["bitcount"])["bitcount"]
    single = _ci_compiler().compile(dfg)
    inline = _ci_compiler(jobs=1).compile_batch([dfg]).results[0]
    pooled = _ci_compiler(jobs=2).compile_batch([dfg, dfg],
                                                names=["a", "b"]).results[0]

    def schema(d, prefix=""):
        keys = []
        for k in sorted(d):
            keys.append(prefix + k)
            if isinstance(d[k], dict):
                keys.extend(schema(d[k], prefix + k + "."))
        return keys

    assert schema(single.metrics) == schema(inline.metrics)
    assert schema(single.metrics) == schema(pooled.metrics)
    assert single.metrics["solver"] == inline.metrics["solver"]
    assert single.metrics["solver"] == pooled.metrics["solver"]
    # the serialized row carries the same block (CLI report path)
    assert single.as_dict()["metrics"]["solver"] == single.metrics["solver"]


def test_memory_cache_counters_and_hit_rate():
    """Satellite 2: the in-memory LRU layer counts hits/misses like the
    disk layer, and the per-compile metrics expose the hit rate."""
    clear_mapping_cache()
    base = memory_cache_stats()
    assert (base.hits, base.misses) == (0, 0)
    dfg = running_example()
    comp = Compiler(CGRA(4, 4), resolve_options(), use_cache=True,
                    cache_dir=None, time_budget_s=60.0)
    cold = comp.compile(dfg)
    warm = comp.compile(dfg)
    assert cold.ok and warm.ok and warm.source == "memory"
    st = memory_cache_stats()
    assert st.hits >= 1 and st.writes >= 1
    assert st.hit_rate is not None and 0.0 < st.hit_rate <= 1.0
    assert st.as_dict()["hits"] == st.hits
    mem = warm.metrics["cache"]["memory"]
    assert mem == {"lookups": 1, "hits": 1, "hit_rate": 1.0}
    assert cold.metrics["cache"]["memory"]["hits"] == 0
    clear_mapping_cache()
    fresh = memory_cache_stats()
    assert (fresh.hits, fresh.misses, fresh.writes) == (0, 0, 0)


def test_batch_metrics_aggregates_rows():
    suite = load_suite(names=["bitcount", "fft"])
    comp = _ci_compiler(jobs=1)
    batch = comp.compile_batch(list(suite.values()))
    assert batch.ok
    agg = batch.metrics
    per_row = [r.metrics["solver"] for r in batch.results]
    for key in ("rounds", "windows_opened", "time_steps",
                "space_nodes_visited"):
        assert agg["solver"][key] == sum(m[key] for m in per_row)
    assert batch.as_dict()["metrics"] == agg


# ----------------------------------------------------- solver telemetry

def test_time_probe_spans_carry_steps():
    dfg = load_suite(names=["fft"])["fft"]
    result, tracer = _traced_compile(dfg)
    probes = [e for e in tracer.events if e["name"] == "time.probe"]
    assert probes
    assert all("backend" in e["args"] and "found" in e["args"]
               for e in probes)
    steps = sum(e["args"].get("steps", 0) for e in probes)
    assert steps == result.metrics["solver"]["time_steps"] > 0


def test_anneal_emits_energy_curve_events():
    """Satellite 6: the annealing backend samples its energy curve and
    per-restart accept rates as instant events."""
    dfg = running_example()
    comp = _ci_compiler(space_backend="anneal")
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        res = comp.compile(dfg)
    assert res.ok
    restarts = [e for e in tracer.events
                if e["name"] == "space.anneal.restart"]
    assert restarts
    for e in restarts:
        assert {"energy", "accepts", "proposals",
                "accept_rate"} <= set(e["args"])
        ar = e["args"]["accept_rate"]
        assert ar is None or 0.0 <= ar <= 1.0


def test_anneal_place_spans_carry_search_work():
    """Each annealing ``place`` call is one ``space.anneal`` span whose
    moves, restarts and kicks add up to the engine's own counters."""
    comp = _ci_compiler(space_backend="anneal")
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        res = comp.compile(load_suite(names=["gsm"])["gsm"])
    assert res.ok
    spans = [e for e in tracer.events if e["name"] == "space.anneal"]
    probes = [e for e in tracer.events if e["name"] == "space.probe"]
    assert spans and len(spans) >= len(probes)
    for e in spans:
        assert e["args"]["pes"] == 16
        assert e["args"]["restarts"] >= 1
        assert e["args"]["moves"] >= 0 and e["args"]["kicks"] >= 0
    assert (sum(e["args"]["moves"] for e in spans)
            == sum(e["args"]["nodes"] for e in probes))


def test_session_env_gate(monkeypatch, tmp_path):
    """REPRO_TRACE enables a session with no explicit flag; unset leaves
    the fast path alone."""
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    with obs.session() as t:
        assert t is None and not obs.enabled()
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert obs.env_enabled()
    with obs.session() as t:
        assert t is not None and obs.enabled()
    assert not obs.enabled()


# ------------------------------------------------------ profiler sink
# (last in the file: these import jax, whose threads make the pool tests'
# fork unsafe)

def test_span_lands_in_profiler_trace(profiled):
    """Inside a JAX profiler trace, with no tracer installed, a span and its
    post-hoc attributes land on the profiler's host timeline, and an event
    lands as a zero-length annotation."""
    assert not obs.enabled()
    with profiled() as events:
        assert obs.recording() and not obs.enabled()
        with obs.span("obs.outer", ii=4, kernel="fft") as sp:
            with obs.span("obs.inner"):
                pass
            sp.set(found=True, nodes=12)
        obs.event("obs.instant", hit=1)
    assert not obs.recording()
    got = {name: (start, end, stats) for name, start, end, stats in events
           if name.startswith("obs.")}
    assert set(got) == {"obs.outer", "obs.inner", "obs.instant"}
    outer, inner = got["obs.outer"], got["obs.inner"]
    assert outer[2] == {"ii": 4, "kernel": "fft", "found": 1, "nodes": 12}
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    start, end, stats = got["obs.instant"]
    assert stats == {"hit": 1} and end - start < 1e6


def test_no_profiler_no_tracer_is_null_span(profiled):
    """Once a profiler trace has stopped (jax imported, no tracer), span()
    is back on the shared no-op singleton."""
    with profiled():
        assert obs.span("x") is not obs._NULL_SPAN
    assert obs.span("x", ii=1) is obs._NULL_SPAN
    assert not obs.recording()


def test_profiler_leaves_chrome_json_unchanged(profiled):
    """A tracer records the same events, with the same attributes, whether
    or not a profiler trace collects at the same time."""
    dfg = running_example()

    def signature():
        _, tracer = _traced_compile(dfg)
        return [(e["name"], e["ph"], sorted(e["args"].items()))
                for e in tracer.events]

    plain = signature()
    with profiled() as events:
        both = signature()
    assert plain == both
    # the same spans reached the profiler, space probes included
    names = {name for name, *_ in events}
    assert {"compile", "time.probe", "space.probe"} <= names


def test_anneal_span_lands_in_profiler_trace(profiled):
    """With no tracer installed, ``space.anneal`` spans and their search
    counters land on the profiler's host timeline."""
    comp = _ci_compiler(space_backend="anneal")
    with profiled() as events:
        assert comp.compile(running_example()).ok
    spans = [stats for name, _, _, stats in events if name == "space.anneal"]
    assert spans
    assert all({"pes", "moves", "restarts", "kicks"} <= set(s) for s in spans)
