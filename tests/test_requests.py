"""Serving request plane: GraphStore/GraphHandle memoized fingerprints,
SolveTicket futures, per-request PipelineConfig overrides and the
mixed-config scheduler, warmup prefetch, bounded disk cache tier."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mesh2d
from repro.core.graph import build_graph
from repro.obs import get_tracer
from repro.pipeline import (PipelineConfig, TreeConfig, fegrass_config,
                            pdgrass_config)
from repro.solver import (GraphHandle, GraphStore, LRUCache, SolveRequest,
                          SolverService, graph_fingerprint, make_solver)
from repro.solver import cache as cache_mod


def _rhs(g, k=1, seed=0):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((g.n, k)).astype(np.float32)
    return b - b.mean(axis=0)


def _rebase(x):
    x = np.asarray(x, dtype=np.float64)
    return x - x[0]


def _copy_graph(g):
    """A structurally identical but distinct Graph object."""
    return build_graph(g.n, g.src.copy(), g.dst.copy(), g.weight.copy())


# -- fingerprint memoization -------------------------------------------------

def test_content_hash_computed_once_per_graph_object():
    g = mesh2d(9, 9, seed=1)
    before = cache_mod.HASH_EVENTS
    fp1 = graph_fingerprint(g)
    fp2 = graph_fingerprint(g, extra=("alpha", 0.05))
    fp3 = graph_fingerprint(g, extra=("alpha", 0.1))
    assert cache_mod.HASH_EVENTS == before + 1  # one O(m) pass, three keys
    assert len({fp1, fp2, fp3}) == 3


def test_store_dedupes_by_content_and_handles_key_dicts():
    g = mesh2d(8, 8, seed=2)
    store = GraphStore()
    h1 = store.register(g)
    h2 = store.register(g)                  # same object: memo lookup
    h3 = store.register(_copy_graph(g))     # equal content: same handle
    assert h1 is h2 and h1 is h3
    assert len(store) == 1
    assert g in store and h1 in store and h1.fingerprint in store
    assert store.get(h1.fingerprint) is h1
    other = store.register(mesh2d(8, 8, seed=3))
    assert other != h1 and len(store) == 2
    assert len({h1, h3, other}) == 2        # handles hash by fingerprint
    with pytest.raises(TypeError, match="Graph or GraphHandle"):
        store.register("not a graph")


def test_registered_traffic_never_rehashes():
    g = mesh2d(10, 10, seed=4)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    b = _rhs(g, seed=5)[:, 0]
    svc.solve(h, b)
    before = cache_mod.HASH_EVENTS
    svc.submit(SolveRequest(graph=h, b=b))
    svc.submit(SolveRequest(graph=h, b=b))
    svc.flush()
    svc.solve(h, b)
    assert cache_mod.HASH_EVENTS == before
    assert svc.stats()["store"]["graphs"] == 1


def test_fingerprinted_arrays_are_frozen_against_silent_mutation():
    g = mesh2d(8, 8, seed=22)
    GraphStore().register(g)
    # the memoized digest must never desync from the content: the hashed
    # arrays become read-only, so an in-place edit raises instead of
    # silently cache-hitting the wrong hierarchy
    with pytest.raises(ValueError, match="read-only"):
        g.weight[0] = 99.0
    assert g.weight.flags.writeable is False


def test_store_counts_only_its_own_hash_events():
    g = mesh2d(8, 8, seed=23)
    store = GraphStore()
    store.register(g)
    store.register(g)
    store.register(_copy_graph(g))
    assert store.stats == {"graphs": 1, "hash_events": 2}  # g + its copy
    other = GraphStore()
    other.register(store.get(content_fingerprint_of(g)))
    assert other.hash_events == 0          # handle path: no hashing


def content_fingerprint_of(g):
    return g.__dict__["_content_fp"]


# -- tickets -----------------------------------------------------------------

def test_tickets_are_stable_across_flushes_and_resolve_out_of_order():
    g = mesh2d(9, 9, seed=6)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    b = _rhs(g, k=3, seed=7)
    t0 = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    out0 = svc.flush()
    t1 = svc.submit(SolveRequest(graph=h, b=b[:, 1]))
    t2 = svc.submit(SolveRequest(graph=h, b=b[:, 2]))
    out1 = svc.flush()
    # v1 handed out per-flush list indices (t1 would collide with t0);
    # v2 ids are service-wide monotonic
    assert (int(t0), int(t1), int(t2)) == (0, 1, 2)
    assert t0 in out0 and t1 in out1 and t2 in out1
    # futures resolve in any order, long after their flush
    assert t2.done() and t1.done()
    r2, r1 = t2.result(), t1.result()
    assert r1.converged and r2.converged
    np.testing.assert_array_equal(r1.x, out1[t1].x)


def test_ticket_result_triggers_flush_lazily():
    g = mesh2d(9, 9, seed=8)
    svc = SolverService(alpha=0.05, precond="none")
    t = svc.submit(SolveRequest(graph=g, b=_rhs(g, seed=9)[:, 0]))
    assert not t.done()
    res = t.result()                        # flushes the owning service
    assert t.done() and res.converged
    assert svc.stats()["scheduler"]["pending"] == 0


def test_v1_int_indexing_still_works():
    g = mesh2d(9, 9, seed=10)
    svc = SolverService(alpha=0.05, precond="none")
    t = svc.submit(SolveRequest(graph=g, b=_rhs(g, seed=11)[:, 0]))
    out = svc.flush()
    assert out[t].converged                 # ticket object as key
    assert out[int(t)].converged            # bare int (v1 callers)


# -- request validation ------------------------------------------------------

def test_non_finite_rhs_is_rejected_with_clear_error():
    g = mesh2d(8, 8, seed=12)
    svc = SolverService(alpha=0.05)
    b = _rhs(g, seed=13)[:, 0]
    for bad in (np.nan, np.inf, -np.inf):
        poisoned = b.copy()
        poisoned[3] = bad
        with pytest.raises(ValueError, match="non-finite"):
            svc.submit(SolveRequest(graph=g, b=poisoned))
        with pytest.raises(ValueError, match="non-finite"):
            svc.solve(g, poisoned)


def test_bad_pipeline_override_is_rejected():
    g = mesh2d(8, 8, seed=14)
    svc = SolverService(alpha=0.05)
    b = _rhs(g, seed=15)[:, 0]
    with pytest.raises(TypeError, match="PipelineConfig"):
        svc.submit(SolveRequest(graph=g, b=b, pipeline="pdgrass"))
    bogus = PipelineConfig(tree=TreeConfig(kind="no_such_stage"))
    with pytest.raises(ValueError, match="unknown tree stage"):
        svc.submit(SolveRequest(graph=g, b=b, pipeline=bogus))


def test_f64_rhs_overflowing_f32_is_rejected():
    g = mesh2d(8, 8, seed=33)
    svc = SolverService(alpha=0.05)
    b = np.zeros(g.n, np.float64)
    b[0], b[1] = 1e300, -1e300      # finite in f64, inf after the f32 cast
    with pytest.raises(ValueError, match="f32"):
        svc.solve(g, b)


# -- mixed-config scheduler --------------------------------------------------


def test_group_failure_is_isolated_to_its_config_group(monkeypatch):
    g = mesh2d(10, 10, seed=30)
    pd = pdgrass_config(alpha=0.05, chunk=128)
    fe = fegrass_config(alpha=0.05, chunk=128)
    svc = SolverService(pipeline=pd)
    h = svc.register(g)
    boom = RuntimeError("hierarchy build exploded")
    real_artifacts = svc.artifacts

    def flaky(graph, key=None, pipeline=None):
        if pipeline is not None and pipeline.recovery.kind == "multipass":
            raise boom
        return real_artifacts(graph, key=key, pipeline=pipeline)

    monkeypatch.setattr(svc, "artifacts", flaky)
    b = _rhs(g, k=2, seed=31)
    t_ok = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    t_bad = svc.submit(SolveRequest(graph=h, b=b[:, 1], pipeline=fe))
    out = svc.flush()
    # the pd group solved and resolved despite the fe group's failure
    assert t_ok in out and out[t_ok].converged and t_ok.result().converged
    # the fe group's ticket settled with the failure, resolvable any time
    assert t_bad not in out and t_bad.done()
    assert t_bad.error() is boom
    with pytest.raises(RuntimeError, match="exploded"):
        t_bad.result()
    sched = svc.stats()["scheduler"]
    assert sched["group_failures"] == 1 and sched["requests_solved"] == 1


def test_solve_surfaces_its_groups_failure(monkeypatch):
    g = mesh2d(9, 9, seed=32)
    svc = SolverService(alpha=0.05)

    def explode(graph, key=None, pipeline=None):
        raise RuntimeError("no artifacts for you")

    monkeypatch.setattr(svc, "artifacts", explode)
    with pytest.raises(RuntimeError, match="no artifacts"):
        svc.solve(g, _rhs(g, seed=33)[:, 0])

def test_mixed_config_flush_groups_and_matches_single_config_services():
    g = mesh2d(12, 12, seed=16)
    pd = pdgrass_config(alpha=0.05, chunk=128)
    fe = fegrass_config(alpha=0.05, chunk=128)
    b = _rhs(g, k=2, seed=17)
    svc = SolverService(pipeline=pd)
    h = svc.register(g)
    assert svc._key(h, pd) != svc._key(h, fe)   # distinct cache keys

    t_pd = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    t_fe = svc.submit(SolveRequest(graph=h, b=b[:, 1], pipeline=fe))
    out = svc.flush()
    # two (graph, config) groups: both built this flush, separately
    assert svc.cache.stats["misses"] == 2
    assert svc.stats()["scheduler"]["groups"] == 2
    assert out[t_pd].config != out[t_fe].config
    assert out[t_pd].converged and out[t_fe].converged

    # equivalence: each request got the same answer a dedicated
    # single-config service produces
    r_pd = SolverService(pipeline=pd).solve(g, b[:, 0])
    r_fe = SolverService(pipeline=fe).solve(g, b[:, 1])
    np.testing.assert_allclose(_rebase(out[t_pd].x), _rebase(r_pd.x),
                               atol=1e-8)
    np.testing.assert_allclose(_rebase(out[t_fe].x), _rebase(r_fe.x),
                               atol=1e-8)
    np.testing.assert_array_equal(out[t_pd].iters, r_pd.iters)
    np.testing.assert_array_equal(out[t_fe].iters, r_fe.iters)

    # repeat flush: 100% artifact cache hit, zero re-fingerprinting
    before = cache_mod.HASH_EVENTS
    t3 = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    t4 = svc.submit(SolveRequest(graph=h, b=b[:, 1], pipeline=fe))
    out2 = svc.flush()
    assert out2[t3].cache == "mem" and out2[t4].cache == "mem"
    assert svc.cache.stats["misses"] == 2       # nothing rebuilt
    assert cache_mod.HASH_EVENTS == before
    counts = svc.stats()["solves_by_config"]
    assert counts == {pd.digest(): 2, fe.digest(): 2}


def test_warmup_prefetches_artifacts_for_each_config():
    g = mesh2d(10, 10, seed=18)
    pd = pdgrass_config(alpha=0.05, chunk=128)
    fe = fegrass_config(alpha=0.05, chunk=128)
    svc = SolverService(pipeline=pd)
    h = svc.register(g)
    sources = svc.warmup(h, configs=[pd, fe])
    assert sources == {pd.digest(): "miss", fe.digest(): "miss"}
    # traffic after warmup only ever hits memory
    b = _rhs(g, k=2, seed=19)
    t1 = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    t2 = svc.submit(SolveRequest(graph=h, b=b[:, 1], pipeline=fe))
    out = svc.flush()
    assert out[t1].cache == "mem" and out[t2].cache == "mem"
    assert svc.warmup(h, configs=[fe]) == {fe.digest(): "mem"}


def test_config_digest_is_stable_and_discriminating():
    pd, fe = pdgrass_config(alpha=0.05), fegrass_config(alpha=0.05)
    assert pd.digest() == pdgrass_config(alpha=0.05).digest()
    assert pd.digest() != fe.digest()
    assert pd.digest() != pdgrass_config(alpha=0.06).digest()
    assert len(pd.digest()) == 12


# -- slot-batch padding invariant --------------------------------------------

def test_padded_batch_columns_are_inert_by_construction():
    """Padding columns carry tol=inf / maxiter=0, so they can never drive
    the batched PCG loop (0 iterations from the start) nor the refinement
    pass — independent of the zero-RHS short-circuit.  Previously pads
    inherited the group's *strictest* tol and *largest* maxiter, which was
    only benign by accident."""
    g = mesh2d(9, 9, seed=40)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    inner = {}
    real_solver_for = svc._solver_for

    def spying(key, artifacts):
        fn = real_solver_for(key, artifacts)

        def spy(b, tol=1e-5, maxiter=2000):
            res = fn(b, tol=tol, maxiter=maxiter)
            # capture the FIRST (main) solve call; refinement passes reuse
            # the closure with per-column remaining budgets
            inner.setdefault("tol", np.asarray(tol))
            inner.setdefault("maxiter", np.asarray(maxiter))
            inner.setdefault("iters", np.asarray(res.iters))
            return res

        return spy

    svc._solver_for = spying
    b = _rhs(g, k=3, seed=41)
    # three 1-column requests with distinct contracts -> k=3, k_pad=4
    tickets = [svc.submit(SolveRequest(graph=h, b=b[:, j], tol=t, maxiter=m))
               for j, (t, m) in enumerate([(1e-5, 2000), (1e-3, 50),
                                           (1e-6, 3000)])]
    out = svc.flush()
    assert all(out[t].converged for t in tickets)
    # the real columns kept their own contracts ...
    assert np.allclose(inner["tol"][:3],
                       np.maximum([1e-5, 1e-3, 1e-6], 1e-5))
    assert list(inner["maxiter"][:3]) == [2000, 50, 3000]
    # ... and the padding column is inert: tol=inf, maxiter=0, 0 iterations
    assert np.isinf(inner["tol"][3])
    assert inner["maxiter"][3] == 0
    assert inner["iters"][3] == 0


# -- width floor: a lone column runs as a width-2 device call ---------------

def _record_calls(svc):
    """Record every device call ``svc`` makes: the right-hand side's width
    and the call's solution and per-column iterations, in call order."""
    calls = []
    real_solver_for = svc._solver_for

    def solver_for(key, artifacts):
        fn = real_solver_for(key, artifacts)

        def spy(b, tol=1e-5, maxiter=2000):
            res = fn(b, tol=tol, maxiter=maxiter)
            calls.append({"width": int(b.shape[1]), "x": np.asarray(res.x),
                          "iters": np.asarray(res.iters)})
            return res

        return spy

    svc._solver_for = solver_for
    return calls


@pytest.fixture(scope="module")
def lone_column():
    """One traced single-column request at tol 1e-6, below the f32 pass's
    floor of 1e-5, so its group makes refinement passes too."""
    g = mesh2d(12, 12, seed=60)
    svc = SolverService(alpha=0.05, max_refine=3)
    h = svc.register(g)
    calls = _record_calls(svc)
    b = _rhs(g, k=1, seed=61)[:, 0]
    tr = get_tracer()
    was = tr.enabled
    tr.enable()
    tr.clear()
    try:
        ticket = svc.submit(SolveRequest(graph=h, b=b, tol=1e-6))
        svc.flush()
        events = tr.events()
    finally:
        tr.clear()
        tr.enabled = was
    return svc, h, b, ticket.result(), calls, events


def test_lone_column_runs_one_width_two_pass0_call(lone_column):
    _, _, _, resp, calls, events = lone_column
    assert resp.converged and resp.refinements >= 1
    # one pass-0 call and one per refinement pass, every one two wide
    assert [c["width"] for c in calls] == [2] * (1 + resp.refinements)
    solves = [e for e in events if e["name"] == "solver.solve"]
    assert len(solves) == 1
    assert (solves[0]["args"]["k"], solves[0]["args"]["k_pad"]) == (1, 2)
    refines = [e for e in events if e["name"] == "solver.refine"]
    assert len(refines) == resp.refinements
    assert all((e["args"]["k"], e["args"]["k_pad"]) == (1, 2)
               for e in refines)
    group = next(e for e in events if e["name"] == "solver.group")
    assert (group["args"]["k"], group["args"]["k_pad"]) == (1, 2)


def test_lone_column_pad_runs_no_iterations_in_any_pass(lone_column):
    _, _, _, resp, calls, _ = lone_column
    assert len(calls) >= 2                     # pass 0 and a refinement
    assert all(int(c["iters"][1]) == 0 for c in calls)
    assert np.all(calls[0]["x"][:, 1] == 0)
    # the response carries the real column alone
    assert resp.x.shape == (144,) and resp.iters.shape == (1,)


def test_lone_column_matches_a_width_one_solve(lone_column):
    """The widened call runs the same mathematics on the real column: a
    direct width-1 solve of the same column takes as many iterations and
    lands on the same solution."""
    svc, h, b, resp, calls, _ = lone_column
    _, (idx, val, hier), _ = svc.artifacts(h)
    direct = make_solver(idx, val, hierarchy=hier, precond=svc.precond)
    b1 = (b - b.mean())[:, None].astype(np.float32)
    res = direct(jnp.asarray(b1), tol=jnp.asarray([1e-5], jnp.float32),
                 maxiter=jnp.asarray([2000], jnp.int32))
    assert int(np.asarray(res.iters)[0]) == int(calls[0]["iters"][0])
    x1 = _rebase(np.asarray(res.x)[:, 0])
    x2 = _rebase(calls[0]["x"][:, 0])
    np.testing.assert_allclose(x2, x1, rtol=0,
                               atol=1e-5 * np.abs(x1).max())
    assert resp.relres[0] <= 1e-6


def test_lone_column_counts_one_width_floor_group(lone_column):
    svc = lone_column[0]
    assert svc.stats()["metrics"]["solver.width_floor_groups"] == 1


def test_three_column_group_pads_to_four_without_the_floor():
    g = mesh2d(9, 9, seed=62)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    calls = _record_calls(svc)
    b = _rhs(g, k=3, seed=63)
    tickets = [svc.submit(SolveRequest(graph=h, b=b[:, j]))
               for j in range(3)]
    out = svc.flush()
    assert all(out[t].converged for t in tickets)
    assert calls and all(c["width"] == 4 for c in calls)
    assert svc.stats()["metrics"].get("solver.width_floor_groups", 0) == 0


# -- bounded disk tier -------------------------------------------------------

def _disk_keys(path):
    return sorted(f[:-len(".pkl")] for f in os.listdir(path)
                  if f.endswith(".pkl"))


def test_mem_lru_eviction_order_is_recency_not_insertion():
    cache = LRUCache(capacity=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == (1, "mem")     # refresh a's recency
    cache.put("c", 3)                       # evicts b, the LRU entry
    assert cache.get("b") == (None, "miss")
    assert cache.get("a") == (1, "mem") and cache.get("c") == (3, "mem")
    assert cache.evictions == 1


def test_disk_round_trip_and_atomic_writes(tmp_path):
    cache = LRUCache(capacity=1, disk_dir=str(tmp_path))
    payload = {"idx": np.arange(5), "val": np.ones(3)}
    cache.put("k0", payload)
    cache.put("k1", 1)                      # k0 falls out of memory
    got, src = cache.get("k0")
    assert src == "disk"
    np.testing.assert_array_equal(got["idx"], payload["idx"])
    # atomic-write path: only whole pickles in the dir, never .tmp litter
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    # a torn concurrent write (leftover tmp) is invisible to the cache
    (tmp_path / "torn.tmp").write_bytes(b"\x80garbage")
    fresh = LRUCache(capacity=1, disk_dir=str(tmp_path))
    assert fresh.get("k1") == (1, "disk")
    assert "disk_entries" in fresh.stats and fresh.stats["disk_entries"] == 2
    # a torn/concurrently-evicted pickle reads as a miss, never a crash
    (tmp_path / "torn2.pkl").write_bytes(b"\x80garbage")
    assert fresh.get("torn2") == (None, "miss")


def test_disk_tier_caps_entries_with_oldest_mtime_eviction(tmp_path):
    cache = LRUCache(capacity=8, disk_dir=str(tmp_path), disk_max_entries=2)
    cache.put("k0", 0)
    cache.put("k1", 1)
    # deterministic ages regardless of filesystem timestamp resolution
    os.utime(tmp_path / "k0.pkl", (100, 100))
    os.utime(tmp_path / "k1.pkl", (200, 200))
    cache.put("k2", 2)                      # over cap: k0 (oldest) evicted
    assert _disk_keys(tmp_path) == ["k1", "k2"]
    assert cache.disk_evictions == 1
    stats = cache.stats
    assert stats["disk_entries"] == 2 and stats["disk_max_entries"] == 2


def test_disk_hit_refreshes_recency_for_eviction(tmp_path):
    cache = LRUCache(capacity=1, disk_dir=str(tmp_path), disk_max_entries=2)
    cache.put("k0", 0)
    cache.put("k1", 1)
    os.utime(tmp_path / "k0.pkl", (100, 100))
    os.utime(tmp_path / "k1.pkl", (200, 200))
    assert cache.get("k0")[1] == "disk"     # refreshes k0's mtime to now
    cache.put("k2", 2)                      # k1 is now the oldest: evicted
    assert _disk_keys(tmp_path) == ["k0", "k2"]


def test_disk_tier_caps_bytes_but_never_evicts_fresh_write(tmp_path):
    cache = LRUCache(capacity=8, disk_dir=str(tmp_path), disk_max_bytes=1)
    big = np.zeros(1024)
    cache.put("k0", big)                    # alone over the cap: kept
    assert _disk_keys(tmp_path) == ["k0"]
    os.utime(tmp_path / "k0.pkl", (100, 100))
    cache.put("k1", big)                    # k0 evicted, k1 (fresh) kept
    assert _disk_keys(tmp_path) == ["k1"]
    assert cache.stats["disk_bytes"] > 0


def test_service_surfaces_disk_caps_in_stats(tmp_path):
    g = mesh2d(8, 8, seed=20)
    svc = SolverService(alpha=0.05, precond="none", disk_dir=str(tmp_path),
                        disk_max_entries=4)
    svc.solve(g, _rhs(g, seed=21)[:, 0])
    stats = svc.stats()
    assert stats["cache"]["disk_max_entries"] == 4
    assert stats["cache"]["disk_entries"] == 1


def test_stale_ticket_result_raises_clear_error_without_flushing_others():
    """Regression: ``result()`` on an unresolved ticket that is NOT in its
    service's pending queue used to flush anyway — pointlessly solving
    unrelated pending work and then failing with a baffling "was it
    submitted to this service?" message.  It must diagnose the stale
    ticket immediately and leave other queued work untouched."""
    g = mesh2d(9, 9, seed=20)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    b = _rhs(g, k=2, seed=21)
    stale = svc.submit(SolveRequest(graph=h, b=b[:, 0]))
    # Simulate the race the bug shipped under: the queue drained without
    # this ticket ever resolving (a consumer dropped its entry).
    with svc._lock:
        svc._pending.clear()
        svc._pending_columns = 0
    live = svc.submit(SolveRequest(graph=h, b=b[:, 1]))
    flushes = svc.stats()["scheduler"]["flushes"]
    with pytest.raises(RuntimeError, match="stale .*or belongs to another"):
        stale.result()
    assert not stale.done()
    # the diagnosis came WITHOUT flushing the unrelated live ticket
    assert svc.stats()["scheduler"]["flushes"] == flushes
    assert not live.done()
    assert live.result().converged          # the live path is unharmed
