"""Async serving runtime: background flusher, tenant fairness, shutdown.

The contracts under test:

  * a submitted ticket resolves via ``result(timeout=...)`` with **no
    explicit flush() anywhere** — the background flusher's deadline/size
    triggers drive everything,
  * per-group failure isolation survives the thread boundary (one
    (graph, config) group's exception fails only its own tickets),
  * multi-tenant fairness: per-tenant budgets reject with tenant context,
    batch selection is starvation-free (every tenant with queued work is
    in every flush window) and weight-proportional,
  * shutdown is deterministic: ``close(drain=True)`` settles everything,
    ``close(drain=False)`` fails everything queued with
    ``DaemonShutdownError`` — never a hang,
  * N producer threads racing one deadline flusher lose no tickets and
    corrupt no queue accounting,
  * SLO breach counting and the ``serve.*`` telemetry surface.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import grid2d
from repro.obs import get_tracer
from repro.serve import DaemonShutdownError, SolverDaemon, TenantConfig
from repro.solver import (AdmissionError, DeadlineExceededError,
                          SolveRequest, SolverService)
from repro.pipeline import fegrass_config

DELAY_MS = 40.0


def _rhs(n, k=1, seed=0):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((n, k)).astype(np.float32)
    return b[:, 0] if k == 1 else b


@pytest.fixture(scope="module")
def svc():
    """One warm service for the whole module: artifacts built and every
    small pow2 RHS bucket jit-compiled, so daemon tests time serving, not
    compilation."""
    service = SolverService(alpha=0.1)
    g = grid2d(6, 6, seed=0)
    h = service.register(g)
    service.warmup(h, widths=[1, 2, 4, 8, 16, 32])
    return service, h


def test_ticket_resolves_without_flush(svc):
    service, h = svc
    flushes_before = service.stats()["scheduler"]["flushes"]
    with SolverDaemon(service, max_batch_delay_ms=DELAY_MS) as d:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=1)))
        t0 = time.perf_counter()
        res = t.result(timeout=30.0)
        elapsed = time.perf_counter() - t0
    assert res.converged
    assert t.done()
    # The deadline trigger fired: resolution took ~max_batch_delay_ms plus
    # a warm solve, nowhere near the 30s timeout.
    assert elapsed < 10.0
    # And nothing ever called service.flush() — the daemon hands batches
    # straight to the group scheduler.
    assert service.stats()["scheduler"]["flushes"] == flushes_before
    assert d.stats()["daemon"]["triggers"]["deadline"] >= 1


def test_done_is_nonblocking_and_result_timeout(svc):
    service, h = svc
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0, autostart=True)
    try:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=2)))
        assert not t.done()          # deadline is a minute out
        with pytest.raises(TimeoutError):
            t.result(timeout=0.05)
        assert not t.done()
    finally:
        d.close(drain=True)
    assert t.result(timeout=1.0).converged   # drain settled it


def test_size_trigger_fires_before_deadline(svc):
    service, h = svc
    with SolverDaemon(service, max_batch_delay_ms=60_000.0,
                      max_batch_columns=4) as d:
        tickets = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=10 + i)))
                   for i in range(4)]
        for t in tickets:
            assert t.result(timeout=30.0).converged
        assert d.stats()["daemon"]["triggers"]["size"] >= 1


def test_group_failure_isolation_across_thread_boundary(svc, monkeypatch):
    """One (graph, config) group's exception must fail only that group's
    tickets; the other group still resolves — from the flusher thread."""
    service, h = svc
    fe = fegrass_config(alpha=0.1)
    real = service._solve_group

    def poisoned(entries, config, key):
        if config.fingerprint() == fe.fingerprint():
            raise RuntimeError("poisoned group")
        return real(entries, config, key)

    monkeypatch.setattr(service, "_solve_group", poisoned)
    with SolverDaemon(service, max_batch_delay_ms=DELAY_MS) as d:
        ok = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=3)))
        bad = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=4),
                                    pipeline=fe))
        assert ok.result(timeout=30.0).converged
        with pytest.raises(RuntimeError, match="poisoned group"):
            bad.result(timeout=30.0)
        assert bad.done() and bad.error() is not None
        assert d.stats()["tenants"]["default"]["failed"] == 1


def test_tenant_budget_rejects_with_tenant_context(svc):
    service, h = svc
    with SolverDaemon(
            service, max_batch_delay_ms=60_000.0,
            tenants={"free": TenantConfig(max_pending_columns=2)}) as d:
        d.submit(SolveRequest(graph=h, b=_rhs(h.n, k=2, seed=5)),
                 tenant="free")
        with pytest.raises(AdmissionError) as ei:
            d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=6)),
                     tenant="free")
        assert ei.value.tenant == "free"
        assert "free" in str(ei.value)
        assert ei.value.budget == 2 and ei.value.pending == 2
        # another tenant is not blocked by free's budget
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=7)),
                     tenant="paid")
        stats = d.stats()["tenants"]
        assert stats["free"]["rejected"] == 1
        assert stats["paid"]["submitted"] == 1
        d.close(drain=True)
        assert t.result(timeout=1.0).converged


def test_starvation_free_selection_under_flood(svc):
    """A heavy tenant floods the queue; the light tenant still lands its
    oldest entry in EVERY size-bounded flush window."""
    service, h = svc
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0,
                     max_batch_columns=3,
                     tenants={"heavy": TenantConfig(weight=8.0),
                              "light": TenantConfig(weight=1.0)},
                     autostart=False)
    heavy = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=20 + i)),
                      tenant="heavy") for i in range(9)]
    light = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=40 + i)),
                      tenant="light") for i in range(3)]
    windows = []
    while True:
        with d._cond:
            if not d._queue:
                break
            batch = d._select_batch_locked()
        windows.append([e.tenant for e in batch])
        d._run_cycle(batch, "size")
    # starvation-freedom: every window formed while 'light' had queued work
    # contains a 'light' entry, flood notwithstanding
    light_remaining = len(light)
    for window in windows:
        if light_remaining > 0:
            assert "light" in window, f"light starved in window {window}"
        light_remaining -= window.count("light")
    assert light_remaining == 0
    # the heavy (weight 8) tenant drains more columns overall
    flat = [t for w in windows for t in w]
    assert flat.count("heavy") == 9 and flat.count("light") == 3
    d.close(drain=True)
    for t in heavy + light:
        assert t.result(timeout=1.0).converged


def test_weighted_fill_prefers_heavier_lane(svc):
    """With equal backlogs, the weighted deficit fill gives the heavier
    lane more slots per window (beyond the one-each starvation floor)."""
    service, h = svc
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0,
                     max_batch_columns=6,
                     tenants={"a": TenantConfig(weight=4.0),
                              "b": TenantConfig(weight=1.0)},
                     autostart=False)
    for i in range(8):
        d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=60 + i)), tenant="a")
        d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=80 + i)), tenant="b")
    with d._cond:
        batch = d._select_batch_locked()
    first = [e.tenant for e in batch]
    assert first.count("a") > first.count("b") >= 1
    d._run_cycle(batch, "size")
    d.close(drain=True)


def test_shutdown_drain_resolves_everything(svc):
    service, h = svc
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0)
    tickets = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=100 + i)))
               for i in range(5)]
    assert not any(t.done() for t in tickets)
    d.close(drain=True)
    for t in tickets:
        assert t.done()
        assert t.result(timeout=1.0).converged
    assert not d.running
    assert d.stats()["daemon"]["triggers"]["drain"] >= 1


def test_shutdown_without_drain_fails_deterministically(svc):
    service, h = svc
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0)
    tickets = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=120 + i)))
               for i in range(3)]
    d.close(drain=False)
    for t in tickets:
        assert t.done()
        with pytest.raises(DaemonShutdownError):
            t.result(timeout=1.0)
    with pytest.raises(RuntimeError, match="closed"):
        d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=130)))
    d.close()   # idempotent


def test_multithreaded_submit_result_race(svc):
    """N producer threads x the deadline flusher: every ticket resolves to
    ITS OWN request's solution (no cross-wiring), queue accounting lands
    on zero, and nothing deadlocks."""
    service, h = svc
    n_threads, per_thread = 4, 5
    with SolverDaemon(service, max_batch_delay_ms=10.0) as d:
        results = {}
        errors = []

        def producer(tid):
            try:
                for i in range(per_thread):
                    seed = 1000 + tid * 100 + i
                    b = _rhs(h.n, seed=seed)
                    t = d.submit(SolveRequest(graph=h, b=b),
                                 tenant=f"t{tid}")
                    res = t.result(timeout=60.0)
                    results[(tid, i)] = (b, res)
            except Exception as e:   # pragma: no cover - failure reporting
                errors.append(e)

        threads = [threading.Thread(target=producer, args=(tid,))
                   for tid in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120.0)
        assert not errors, errors
        assert len(results) == n_threads * per_thread
        # every response solves its own rhs: L x = b (mean-removed)
        g = h.graph
        for (tid, i), (b, res) in results.items():
            assert res.converged, (tid, i)
            bc = b.astype(np.float64)
            bc = bc - bc.mean()
            x = np.asarray(res.x, dtype=np.float64)
            r = bc - g.laplacian_matvec(x)
            assert np.linalg.norm(r) <= 1e-4 * np.linalg.norm(bc), (tid, i)
    # after close() the flusher has joined: accounting is quiescent
    stats = d.stats()
    assert stats["daemon"]["pending_columns"] == 0
    assert stats["daemon"]["queue_depth"] == 0
    lanes = stats["tenants"]
    for tid in range(n_threads):
        assert lanes[f"t{tid}"]["solved"] == per_thread
        assert lanes[f"t{tid}"]["pending_columns"] == 0


def test_slo_violation_counter(svc):
    """An impossible SLO budget marks every flushed group as a breach; the
    counter shows up in daemon stats AND the service metrics registry.

    The flusher resolves the ticket before it books the cycle's latencies,
    so the counters are read after ``close()`` has joined it."""
    service, h = svc
    before = service.metrics.counter("serve.slo_violations").value
    with SolverDaemon(service, max_batch_delay_ms=20.0,
                      slo_budget_ms=1e-9) as d:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=200)))
        assert t.result(timeout=30.0).converged
    assert d.stats()["daemon"]["slo_violations"] >= 1
    after = service.metrics.counter("serve.slo_violations").value
    assert after - before >= 1
    mstats = service.stats()["metrics"]
    assert mstats["serve.slo_violations"] >= 1


def test_slo_budget_derives_from_delay_knob(svc):
    service, _ = svc
    d = SolverDaemon(service, max_batch_delay_ms=25.0, autostart=False)
    assert d.slo_budget_ms == pytest.approx(100.0)
    d.close()
    d2 = SolverDaemon(service, max_batch_delay_ms=25.0, slo_budget_ms=80.0,
                      autostart=False)
    assert d2.slo_budget_ms == 80.0
    d2.close()


def test_serve_metrics_surface(svc):
    """Queue-depth gauge + latency histograms land in the service metrics
    under the serve.* namespace."""
    service, h = svc
    with SolverDaemon(service, max_batch_delay_ms=10.0) as d:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=300)))
        assert t.result(timeout=30.0).converged
    m = service.stats()["metrics"]
    assert m["serve.queue_depth"] == 0
    assert m["serve.queue_wait_ms"]["count"] >= 1
    assert m["serve.e2e_ms"]["count"] >= 1
    assert m["serve.e2e_ms"]["p50"] > 0
    assert m["serve.cycles"] >= 1


def test_flush_cycle_span_emitted(svc):
    service, h = svc
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    try:
        with SolverDaemon(service, max_batch_delay_ms=10.0) as d:
            t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=400)))
            assert t.result(timeout=30.0).converged
        names = tr.span_names()
        assert "serve.flush_cycle" in names
        assert "solver.group" in names     # nested: the scheduler ran inside
        cycle = next(e for e in tr.events()
                     if e["name"] == "serve.flush_cycle")
        assert cycle["args"]["requests"] == 1
        assert cycle["args"]["trigger"] in ("deadline", "size", "drain")
    finally:
        tr.clear()
        tr.enabled = was


def test_flush_cycle_spans_carry_ticket_ids_and_waits(svc):
    """Every request can be followed from its cycle to its group by ticket
    id; the flusher's wait for a batch deadline is a span of its own."""
    service, h = svc
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    try:
        with SolverDaemon(service, max_batch_delay_ms=DELAY_MS) as d:
            tickets = [d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=s)))
                       for s in range(410, 414)]
            for t in tickets:
                assert t.result(timeout=30.0).converged
        evs = tr.events()
    finally:
        tr.clear()
        tr.enabled = was
    cycles = [e for e in evs if e["name"] == "serve.flush_cycle"]
    seen = []
    for c in cycles:
        ids, waits = c["args"]["tickets"], c["args"]["waits_ms"]
        assert len(ids) == len(waits) == c["args"]["requests"]
        assert all(w >= 0 for w in waits)
        seen += ids
    assert sorted(seen) == sorted(int(t) for t in tickets)
    grouped = [t for e in evs if e["name"] == "solver.group"
               for t in e["args"]["tickets"]]
    assert sorted(grouped) == sorted(seen)
    waits = [e for e in evs if e["name"] == "serve.batch_wait"]
    assert waits and all(e["depth"] == 0 for e in waits)


def test_disabled_tracer_allocates_no_span_in_a_cycle(svc, monkeypatch):
    """The daemon's and the group's spans stay free with the tracer off."""
    from repro.obs import trace as trace_mod

    made = {"n": 0}
    real_span = trace_mod._Span

    class Spy(real_span):
        def __init__(self, *a, **kw):
            made["n"] += 1
            real_span.__init__(self, *a, **kw)

    monkeypatch.setattr(trace_mod, "_Span", Spy)
    service, h = svc
    assert not get_tracer().enabled
    with SolverDaemon(service, max_batch_delay_ms=10.0) as d:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, 2, seed=420)))
        assert t.result(timeout=30.0).converged
    assert made["n"] == 0


def test_constructor_validation(svc):
    service, _ = svc
    with pytest.raises(ValueError, match="max_batch_delay_ms"):
        SolverDaemon(service, max_batch_delay_ms=0.0)
    with pytest.raises(ValueError, match="max_batch_columns"):
        SolverDaemon(service, max_batch_columns=0)
    with pytest.raises(TypeError, match="TenantConfig"):
        SolverDaemon(service, tenants={"a": {"weight": 2.0}},
                     autostart=False)
    with pytest.raises(ValueError, match="weight"):
        TenantConfig(weight=0.0)


# ---------------------------------------------------------------------------
# Queue-side TTL: SolveRequest(deadline_ms=...) expiry
# ---------------------------------------------------------------------------

def test_expiry_manual_clock_fails_only_deadlined_ticket(svc):
    """Deterministic TTL: with an injected clock, an entry whose
    ``deadline_ms`` has lapsed is expired at the next sweep — the
    drain path included — while deadline-free neighbors still solve."""
    service, h = svc
    now = [0.0]
    d = SolverDaemon(service, max_batch_delay_ms=60_000.0,
                     autostart=False, clock=lambda: now[0])
    doomed = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=200),
                                   deadline_ms=50.0))
    safe = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=201)))
    now[0] = 0.2                       # 200 ms later: 50 ms TTL long gone
    d.close(drain=True)                # drain sweeps expiries first
    with pytest.raises(DeadlineExceededError) as ei:
        doomed.result(timeout=1.0)
    err = ei.value
    assert err.deadline_ms == 50.0
    assert err.waited_ms >= 50.0
    assert safe.result(timeout=1.0).converged
    st = d.stats()["daemon"]
    assert st["expired"] == 1


def test_expiry_fires_from_live_flusher_before_batch_deadline(svc):
    """The flusher's wait is min(batch deadline, earliest TTL): a 30 ms
    TTL inside a 500 ms batch window expires in ~30 ms, not 500."""
    service, h = svc
    with SolverDaemon(service, max_batch_delay_ms=500.0) as d:
        t = d.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=210),
                                  deadline_ms=30.0), tenant="default")
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededError):
            t.result(timeout=5.0)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.45          # did NOT wait out the batch window
        st = d.stats()
        assert st["daemon"]["expired"] == 1
        assert st["tenants"]["default"]["expired"] == 1
    m = service.stats()["metrics"]
    assert m["serve.expired"] >= 1          # module-scoped service: >=
    assert m["serve.tenant.default.expired"] >= 1


def test_deadline_ms_validation_and_sync_path(svc):
    service, h = svc
    with pytest.raises(ValueError, match="deadline_ms"):
        service.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=220),
                                    deadline_ms=-5.0))
    # the sync service accepts but ignores queue TTLs (no background
    # queue to age in): the solve just runs
    t = service.submit(SolveRequest(graph=h, b=_rhs(h.n, seed=221),
                                    deadline_ms=1e-3))
    service.flush()
    assert t.result().converged
