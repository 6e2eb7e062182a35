"""Telemetry plane: span tracer, metrics registry, solver instrumentation.

Covers the observability contracts end to end:

  * disabled tracing is a true no-op (allocation spy + shared singleton),
  * concurrent span recording is thread-safe and lossless under the cap,
  * histogram percentiles agree with the numpy oracle within one bucket
    ratio,
  * Chrome trace export round-trips through ``json.loads`` and preserves
    nesting by interval containment,
  * a single flush with tracing enabled produces the full nested span set
    (pipeline stages, hierarchy levels, cache lookups, batched solve),
  * ``stats()`` reports per-config PCG convergence histograms, is a deep
    copy (mutating the return must not corrupt live counters), and
    per-service metrics are isolated between services.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import mesh2d
from repro.obs import (Counter, Gauge, Histogram, Metrics, get_metrics,
                       get_tracer)
from repro.obs import trace as trace_mod
from repro.obs.device import install_compile_listener
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.solver import SolveRequest, SolverService
from repro.solver.cache import content_fingerprint


def _rhs(g, k=1, seed=0):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((g.n, k)).astype(np.float32)
    return b - b.mean(axis=0)


@pytest.fixture
def traced():
    """Enable the process-wide tracer for one test, restoring prior state."""
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    yield tr
    tr.clear()
    tr.enabled = was


# -- tracer ------------------------------------------------------------------


def test_disabled_span_is_true_noop(monkeypatch):
    """A disabled tracer must not allocate, lock, or record — the warm-solve
    path is instrumented unconditionally, so this is the <2% contract."""
    calls = {"n": 0}
    real_span = trace_mod._Span

    class Spy(real_span):
        def __init__(self, *a, **kw):
            calls["n"] += 1
            real_span.__init__(self, *a, **kw)

    monkeypatch.setattr(trace_mod, "_Span", Spy)
    tr = Tracer(enabled=False)
    spans = [tr.span(f"s{i}", i=i) for i in range(50)]
    assert calls["n"] == 0, "disabled span() constructed a live span"
    assert all(s is NOOP_SPAN for s in spans), (
        "disabled span() must return the shared singleton")
    with tr.span("x") as sp:
        sp.set(result=1)        # must be accepted and discarded
    tr.complete("marker", 0, 1)
    assert tr.events() == []
    tr.enable()
    with tr.span("y"):
        pass
    assert calls["n"] == 1 and tr.span_names() == ["y"]


def test_nested_spans_record_depth_and_containment(traced):
    with traced.span("outer", who="test") as outer:
        with traced.span("inner"):
            pass
        outer.set(children=1)
    evs = {ev["name"]: ev for ev in traced.events()}
    assert evs["inner"]["depth"] == 1 and evs["outer"]["depth"] == 0
    assert evs["outer"]["args"] == {"who": "test", "children": 1}
    # the child exits first but its interval nests inside the parent's
    o, i = evs["outer"], evs["inner"]
    assert o["ts_ns"] <= i["ts_ns"]
    assert i["ts_ns"] + i["dur_ns"] <= o["ts_ns"] + o["dur_ns"]


def test_concurrent_span_recording_is_thread_safe():
    tr = Tracer(enabled=True)
    n_threads, n_spans = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for j in range(n_spans):
            with tr.span(f"t{i}", j=j):
                with tr.span(f"t{i}.child"):
                    pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * n_spans * 2 and tr.dropped == 0
    # per-thread nesting depths never bled across threads
    for ev in evs:
        assert ev["depth"] == (1 if ev["name"].endswith(".child") else 0)
    assert len({ev["tid"] for ev in evs}) == n_threads


def test_event_buffer_is_bounded():
    tr = Tracer(enabled=True, max_events=10)
    for i in range(25):
        with tr.span("s"):
            pass
    assert len(tr.events()) == 10 and tr.dropped == 15
    assert tr.to_chrome()["otherData"]["dropped_events"] == 15
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_chrome_export_roundtrips_through_json(tmp_path, traced):
    with traced.span("parent", n=np.int64(3), f=np.float32(0.5),
                     arr=np.arange(2)):
        with traced.span("child"):
            pass
    traced.complete("mark", 0, 5, note="hi")
    path = tmp_path / "trace.json"
    traced.export_chrome(str(path))
    doc = json.loads(path.read_text())      # strict round-trip
    evs = {ev["name"]: ev for ev in doc["traceEvents"]}
    assert evs["parent"]["ph"] == "X" and evs["child"]["ph"] == "X"
    assert evs["mark"]["ph"] == "X" and evs["mark"]["args"] == {"note": "hi"}
    # numpy attrs degraded to plain JSON scalars/strings
    assert evs["parent"]["args"]["n"] == 3
    assert evs["parent"]["args"]["f"] == pytest.approx(0.5)
    assert isinstance(evs["parent"]["args"]["arr"], str)
    # microsecond containment survives the export
    p, c = evs["parent"], evs["child"]
    assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    assert all(ev["pid"] == p["pid"] for ev in doc["traceEvents"])


def test_jsonl_export_one_object_per_line(tmp_path, traced):
    for i in range(3):
        with traced.span("s", i=i):
            pass
    path = tmp_path / "trace.jsonl"
    traced.export_jsonl(str(path))
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 3
    assert [json.loads(ln)["args"]["i"] for ln in lines] == [0, 1, 2]


# -- metrics -----------------------------------------------------------------


def test_counter_gauge_basics():
    m = Metrics()
    m.inc("a.count")
    m.inc("a.count", 4)
    m.set_gauge("a.level", 7.5)
    assert m.counter("a.count").value == 5
    assert m.gauge("a.level").value == 7.5
    with pytest.raises(TypeError):
        m.gauge("a.count")          # type conflict must be loud
    snap = m.snapshot()
    assert snap == {"a.count": 5, "a.level": 7.5}
    snap["a.count"] = 999           # snapshot is detached
    assert m.counter("a.count").value == 5


def test_histogram_percentiles_match_numpy_oracle():
    rng = np.random.default_rng(42)
    data = rng.lognormal(mean=1.0, sigma=1.5, size=5000)
    h = Histogram()
    h.observe_many(data)
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["min"] == pytest.approx(float(data.min()))
    assert snap["max"] == pytest.approx(float(data.max()))
    assert snap["sum"] == pytest.approx(float(data.sum()), rel=1e-9)
    # bounded buckets guarantee at most one bucket ratio (~26%) of error
    for p in (50, 90, 99):
        assert h.percentile(p) == pytest.approx(
            float(np.percentile(data, p)), rel=0.26)
    # endpoints are exact
    assert h.percentile(0) == pytest.approx(float(data.min()))
    assert h.percentile(100) == pytest.approx(float(data.max()))


def test_histogram_concurrent_observe():
    h = Histogram()

    def worker(seed):
        rng = np.random.default_rng(seed)
        h.observe_many(rng.uniform(0.1, 100.0, size=500))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 2000


def test_content_hash_mirrors_into_global_metrics():
    before = get_metrics().counter("store.hash_events").value
    g = mesh2d(4, 4, seed=3)
    content_fingerprint(g)
    content_fingerprint(g)          # memoized: no second hash event
    assert get_metrics().counter("store.hash_events").value == before + 1


# -- solver-depth instrumentation -------------------------------------------


@pytest.fixture(scope="module")
def traffic():
    """One traced flush through a service whose hierarchy has real levels."""
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    g = mesh2d(14, 14, seed=0)      # n=196 > coarse_n: multilevel chain
    svc = SolverService(alpha=0.1)
    h = svc.register(g)
    ticket = svc.submit(SolveRequest(graph=h, b=_rhs(g, k=3)))
    svc.flush()
    resp = ticket.result()
    events = tr.events()
    tr.clear()
    tr.enabled = was
    return g, svc, resp, events


def test_flush_produces_nested_solver_spans(traffic, tmp_path):
    g, svc, resp, events = traffic
    assert resp.converged
    names = {ev["name"] for ev in events}
    for required in ("pipeline.prepare", "pipeline.tree", "pipeline.scores",
                     "pipeline.recovery", "hierarchy.build",
                     "hierarchy.level", "hierarchy.sparsify",
                     "hierarchy.contract", "cache.get", "cache.build",
                     "solver.flush", "solver.group", "solver.artifacts",
                     "solver.solve"):
        assert required in names, f"missing span {required}"
    # the whole stack nests under the flush: Chrome containment check
    tr = Tracer(enabled=True)
    tr._events = list(events)       # re-export the captured buffer
    doc = json.loads(json.dumps(tr.to_chrome()))
    evs = {ev["name"]: ev for ev in doc["traceEvents"]}
    flush = evs["solver.flush"]
    for inner in ("solver.group", "solver.solve", "hierarchy.build",
                  "cache.get"):
        ev = evs[inner]
        assert flush["ts"] <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= flush["ts"] + flush["dur"]
    # hierarchy levels carry sizes; one span per fine level
    levels = [ev for ev in events if ev["name"] == "hierarchy.level"]
    assert len(levels) >= 1
    assert levels[0]["args"]["n"] == g.n


def test_stats_reports_convergence_telemetry(traffic):
    _, svc, resp, _ = traffic
    st = svc.stats()
    assert st["convergence"], "no convergence telemetry recorded"
    conv = st["convergence"][resp.config]
    assert conv["iters"]["count"] == 3          # one sample per RHS column
    assert conv["iters"]["max"] >= resp.iters.max()
    assert conv["relres"]["count"] == 3
    assert conv["relres"]["p99"] <= 2e-5        # converged to tol
    assert conv["solve_ms"]["count"] == 1       # one flush group
    assert conv["solve_ms"]["p50"] > 0
    m = st["metrics"]
    assert m["solver.flushes"] == 1
    assert m["solver.requests_solved"] == 1
    assert m["cache.misses"] == 1
    assert m[f"solver.pcg.iters.{resp.config}"]["count"] == 3


def test_stats_returns_a_deep_copy(traffic):
    """Satellite regression: mutating the returned dict must never corrupt
    the service's live counters."""
    _, svc, resp, _ = traffic
    st = svc.stats()
    st["scheduler"]["flushes"] = 10_000
    st["timing"]["solve_ms"] = -1.0
    st["metrics"].clear()
    st["convergence"][resp.config]["iters"]["count"] = 0
    st["solves_by_config"].clear()
    st2 = svc.stats()
    assert st2["scheduler"]["flushes"] == 1
    assert st2["timing"]["solve_ms"] > 0
    assert st2["metrics"]["solver.flushes"] == 1
    assert st2["convergence"][resp.config]["iters"]["count"] == 3
    assert st2["solves_by_config"] == {resp.config: 1}


def test_service_metrics_are_isolated(traffic):
    """Two services must not share solver/cache instruments."""
    _, busy, _, _ = traffic
    fresh = SolverService(alpha=0.1)
    st = fresh.stats()
    assert st["metrics"].get("solver.flushes", 0) == 0
    assert st["metrics"].get("cache.misses", 0) == 0
    assert st["convergence"] == {}
    assert busy.stats()["metrics"]["solver.flushes"] == 1
    # explicit sharing is still possible by injecting one registry
    shared = Metrics()
    a = SolverService(alpha=0.1, metrics=shared)
    b = SolverService(alpha=0.1, metrics=shared)
    assert a.metrics is b.metrics is shared


def test_warm_solve_records_no_spans_when_disabled(traffic):
    """Instrumented hot path stays silent with the tracer off."""
    g, svc, _, _ = traffic
    tr = get_tracer()
    assert not tr.enabled
    tr.clear()
    resp = svc.solve(svc.register(g), _rhs(g, k=2, seed=1))
    assert resp.converged and resp.cache == "mem"
    assert tr.events() == []


# -- phases of a solve group -------------------------------------------------


def _children(events, parent):
    """Direct children of ``parent`` on its thread, in time order."""
    p0, p1 = parent["ts_ns"], parent["ts_ns"] + parent["dur_ns"]
    return sorted((e for e in events if e["tid"] == parent["tid"]
                   and e["depth"] == parent["depth"] + 1
                   and p0 <= e["ts_ns"]
                   and e["ts_ns"] + e["dur_ns"] <= p1),
                  key=lambda e: e["ts_ns"])


@pytest.fixture(scope="module")
def refined():
    """One traced group at tol 1e-6, below the f32 pass's floor of 1e-5,
    so it takes at least one refinement pass."""
    g = mesh2d(14, 14, seed=0)
    svc = SolverService(alpha=0.1, max_refine=3)
    h = svc.register(g)
    svc.solve(h, _rhs(g, k=3), tol=1e-6)        # build and compile
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    ticket = svc.submit(SolveRequest(graph=h, b=_rhs(g, k=3, seed=4),
                                     tol=1e-6))
    svc.flush()
    events = tr.events()
    tr.clear()
    tr.enabled = was
    return ticket, ticket.result(), events


def test_group_names_every_phase_in_order(refined):
    ticket, resp, events = refined
    assert resp.converged and resp.refinements >= 1
    group = next(e for e in events if e["name"] == "solver.group")
    assert group["args"]["tickets"] == [int(ticket)]
    kids = _children(events, group)
    names = [e["name"].split(".", 1)[1] for e in kids]
    assert names == (["artifacts", "stack", "solve", "residual"]
                     + ["refine", "residual"] * resp.refinements
                     + ["resolve"])
    calls = [e for e in kids if e["name"] in ("solver.solve",
                                              "solver.refine")]
    assert [c["args"]["pass"] for c in calls] == list(
        range(resp.refinements + 1))
    assert [e["args"]["pass"] for e in kids
            if e["name"] == "solver.residual"] == list(
        range(resp.refinements + 1))
    for c in calls:
        assert {"pass", "k", "k_pad", "loops"} <= set(c["args"])
        assert (c["args"]["k"], c["args"]["k_pad"]) == (3, 4)
    # the loops of the calls add up to the slowest column's iterations
    assert calls[0]["args"]["loops"] >= 1
    assert sum(c["args"]["loops"] for c in calls) >= int(resp.iters.max())


def test_tree_span_ends_on_the_tree(monkeypatch, traced):
    """With the tracer on, ``pipeline.tree`` waits for the tree stage's
    outputs before it ends; with it off, nothing waits."""
    import jax

    from repro.pipeline import Pipeline, pdgrass_config

    real, waits = jax.block_until_ready, []

    def block_until_ready(x):
        out = real(x)
        waits.append(time.perf_counter_ns())
        return out

    monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
    g = mesh2d(8, 8, seed=2)
    Pipeline(pdgrass_config(alpha=0.1)).prepare(g)
    tree = next(e for e in traced.events() if e["name"] == "pipeline.tree")
    assert any(tree["ts_ns"] <= t <= tree["ts_ns"] + tree["dur_ns"]
               for t in waits)
    traced.disable()
    del waits[:]
    Pipeline(pdgrass_config(alpha=0.1)).prepare(g)
    assert waits == []


class _ReadClock:
    """An answer array that notes the time it is read into numpy."""

    def __init__(self, arr, reads):
        self._arr, self._reads = arr, reads

    def __array__(self, dtype=None, copy=None):
        self._reads.append(time.perf_counter_ns())
        return np.asarray(self._arr, dtype=dtype)


def test_device_call_spans_end_on_the_answer(monkeypatch, traced):
    """Each solve and refine span holds the read-back of its answer."""
    import repro.solver.service as service

    real, reads = service.make_solver, []

    def make_solver(*a, **k):
        fn = real(*a, **k)

        def solve(b, tol=1e-5, maxiter=2000):
            res = fn(b, tol=tol, maxiter=maxiter)
            return res._replace(x=_ReadClock(res.x, reads))

        return solve

    monkeypatch.setattr(service, "make_solver", make_solver)
    g = mesh2d(14, 14, seed=0)
    svc = SolverService(alpha=0.1, max_refine=3)
    resp = svc.solve(svc.register(g), _rhs(g, k=2, seed=5), tol=1e-6)
    assert resp.converged and resp.refinements >= 1
    calls = [e for e in traced.events()
             if e["name"] in ("solver.solve", "solver.refine")]
    assert [e["name"] for e in calls].count("solver.refine") == \
        resp.refinements
    assert len(reads) == len(calls)
    for e, t in zip(sorted(calls, key=lambda e: e["ts_ns"]), reads):
        assert e["ts_ns"] <= t <= e["ts_ns"] + e["dur_ns"], e["name"]


def _fresh_jit(scale):
    """A function JAX has not compiled before in this process."""
    import jax

    def scaled_shift(x):
        return x * scale + 1.0

    return jax.jit(scaled_shift)


def test_compile_is_a_span_under_the_open_one(traced):
    assert install_compile_listener() and install_compile_listener()
    compiles = get_metrics().counter("jax.compiles")
    before = compiles.value
    with traced.span("outer"):
        _fresh_jit(3.0)(np.ones(7, np.float32)).block_until_ready()
    evs = traced.events()
    spans = [e for e in evs if e["name"] == "jax.compile"]
    assert len(spans) == 1 and compiles.value == before + 1
    sp, outer = spans[0], next(e for e in evs if e["name"] == "outer")
    assert sp["args"] == {"fun": "jit(scaled_shift)", "cache_hit": False}
    assert sp["depth"] == 1 and sp["tid"] == outer["tid"]
    assert outer["ts_ns"] <= sp["ts_ns"] and \
        sp["ts_ns"] + sp["dur_ns"] <= outer["ts_ns"] + outer["dur_ns"]


def test_compile_counts_but_records_nothing_when_disabled():
    install_compile_listener()
    tr = get_tracer()
    assert not tr.enabled
    tr.clear()
    compiles = get_metrics().counter("jax.compiles")
    before = compiles.value
    _fresh_jit(5.0)(np.ones(9, np.float32)).block_until_ready()
    assert compiles.value == before + 1
    assert tr.events() == []


def test_counter_and_gauge_types_exported():
    assert isinstance(Metrics().counter("x"), Counter)
    assert isinstance(Metrics().gauge("y"), Gauge)


# -- sampled always-on tracing ----------------------------------------------


def test_sampled_tracer_records_every_nth_root_span():
    tr = Tracer(enabled=True, sample_rate=0.5)     # period 2
    for i in range(10):
        with tr.span(f"root{i}"):
            pass
    assert tr.span_names() == [f"root{i}" for i in range(0, 10, 2)]
    assert tr.sampled_out == 5


def test_sampling_decision_covers_the_whole_root_tree():
    """A dropped root suppresses everything beneath it — nested spans and
    completed spans never sample independently, so recorded trees stay
    complete."""
    tr = Tracer(enabled=True, sample_rate=0.5)
    for i in range(4):
        with tr.span(f"root{i}") as root:
            root.set(i=i)
            with tr.span("child") as c:
                c.set(deep=True)
                with tr.span("grandchild"):
                    pass
            tr.complete(f"marker{i}", 0, 1)
    names = tr.span_names()
    # roots 0 and 2 recorded with their full subtrees; 1 and 3 vanish whole
    assert names.count("child") == 2 == names.count("grandchild")
    assert [n for n in names if n.startswith("root")] == ["root0", "root2"]
    assert [n for n in names if n.startswith("marker")] == \
        ["marker0", "marker2"]
    # nesting depth survived sampling
    evs = {ev["name"]: ev for ev in tr.events()}
    assert evs["child"]["depth"] == 1 and evs["grandchild"]["depth"] == 2


def test_sample_rate_one_is_the_default_full_firehose():
    tr = Tracer(enabled=True)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.span_names()) == 5 and tr.sampled_out == 0


def test_invalid_sample_rate_rejected():
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="sample_rate"):
            Tracer(sample_rate=bad)
        with pytest.raises(ValueError, match="sample_rate"):
            Tracer().set_sample_rate(bad)


def test_set_sample_rate_restarts_counter():
    tr = Tracer(enabled=True, sample_rate=0.25)
    with tr.span("a"):                  # seq 0: recorded
        pass
    tr.set_sample_rate(0.5)             # counter restarts
    with tr.span("b"):                  # seq 0 again: recorded
        pass
    with tr.span("c"):                  # seq 1: dropped
        pass
    assert tr.span_names() == ["a", "b"]


def test_disabled_sampled_tracer_is_still_allocation_free(monkeypatch):
    """sample_rate must not cost anything while tracing is off — the
    always-on production config is (enabled later, sampled forever)."""
    calls = {"n": 0}
    real_span = trace_mod._Span

    class Spy(real_span):
        def __init__(self, *a, **kw):
            calls["n"] += 1
            real_span.__init__(self, *a, **kw)

    monkeypatch.setattr(trace_mod, "_Span", Spy)
    tr = Tracer(enabled=False, sample_rate=0.01)
    spans = [tr.span(f"s{i}") for i in range(20)]
    assert calls["n"] == 0
    assert all(s is NOOP_SPAN for s in spans)
    assert tr.events() == [] and tr.sampled_out == 0


def test_enable_tracing_reconfigures_sample_rate():
    tr = get_tracer()
    was_enabled, was_rate = tr.enabled, tr.sample_rate
    try:
        from repro.obs import enable_tracing
        enable_tracing(sample_rate=0.5)
        assert tr.enabled and tr.sample_rate == 0.5
        for i in range(4):
            with tr.span(f"g{i}"):
                pass
        assert tr.sampled_out >= 2
    finally:
        tr.set_sample_rate(was_rate)
        tr.enabled = was_enabled
        tr.clear()
        tr.sampled_out = 0
