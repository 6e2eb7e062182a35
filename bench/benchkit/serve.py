"""Served cells: a ``SolverDaemon`` answering open-loop solve requests.

Set-up loads what a restarted deployment loads: the graph store and the
artifact tier from ``disk_dir`` (built and persisted by the first run of a
cell in a checkout), then warms every solve width the window can use.
The window submits the seeded schedule on time, never waiting for
answers; after it closes every ticket is awaited (a minute past the close
at most) and every answer is checked against the float64 reference.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchkit import devtrace, graphs, layout, reference, stats, traffic
from benchkit.result import Run

RESULT_WAIT_S = 60.0


def service(config: dict, service_kwargs=None, **extra):
    """The ``SolverService`` the configuration states; ``service_kwargs``
    switch on one of the program's own paths (the control's
    ``max_refine=0``)."""
    from repro.pipeline import pdgrass_config
    from repro.solver import SolverService

    solver = config["solver"]
    kwargs = dict(pipeline=pdgrass_config(alpha=float(solver["alpha"])),
                  precond=solver["precond"],
                  max_refine=int(solver["max_refine"]), **extra)
    kwargs.update(service_kwargs or {})
    return SolverService(**kwargs)


class _CallRecorder:
    """Wraps the service's solve closures (traced runs only) to record,
    per device solve call, its host span, width and loop iterations: what
    the V-cycle roofline needs to count the work of each call.  The call
    is already followed by a read of its result in the service, so waiting
    for ``iters`` here moves no work."""

    def __init__(self, svc):
        import jax

        self.calls = []
        inner = svc._solver_for

        def solver_for(key, artifacts):
            fn = inner(key, artifacts)

            def solve(b, tol=1e-5, maxiter=2000):
                name = f"bench.solve_call.{len(self.calls)}"
                with jax.profiler.TraceAnnotation(name):
                    t0 = time.perf_counter_ns()
                    res = fn(b, tol=tol, maxiter=maxiter)
                    iters = np.asarray(res.iters)
                    t1 = time.perf_counter_ns()
                self.calls.append({"t0": t0, "t1": t1, "k": int(b.shape[1]),
                                   "loops": int(iters.max(initial=0))})
                return res

            return solve

        svc._solver_for = solver_for


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        disk_dir: str, compiles, service_kwargs=None, profile_dir=None):
    import jax

    from repro.core.graph import build_graph
    from repro.obs import get_tracer
    from repro.serve import SolverDaemon
    from repro.solver import SolveRequest

    config, mix = cell.config, cell.traffic
    n, src, dst, w = graphs.generate(config["graph"])
    g = build_graph(n, src, dst, w)
    schedule = traffic.open_schedule(mix, seed, seconds)
    rhs = [traffic.rhs(seed, i, n, a.width) for i, a in enumerate(schedule)]
    tol, maxiter = float(mix["tol"]), int(mix["maxiter"])

    svc = service(config, service_kwargs, disk_dir=disk_dir)
    recorder = _CallRecorder(svc) if trace else None
    handle = svc.register(g)
    svc.warmup(handle, widths=traffic.buckets(traffic.max_group_columns(mix)))
    daemon = SolverDaemon(svc, max_batch_delay_ms=float(
        mix["max_batch_delay_ms"]), max_batch_columns=int(
        mix["max_batch_columns"]))
    # one real group of each request width through the daemon, so the
    # first timed request meets nothing that has not run once
    warm = [daemon.submit(SolveRequest(graph=handle, b=traffic.rhs(
        seed, i, n, int(wd), warmup=True), tol=tol, maxiter=maxiter))
        for i, (wd, _) in enumerate(mix["widths"])]
    for t in warm:
        t.result(timeout=600)

    tracer = get_tracer()
    if trace:
        tracer.clear()
        tracer.enable()
    wait_hist = svc.metrics.histogram("serve.queue_wait_ms")
    wait0 = wait_hist.snapshot()
    profile = devtrace.Window(profile_dir) if trace else None
    compiles.open()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if profile is not None:
        profile.start_after(t0 + float(mix["trace_start_share"]) * seconds,
                            float(mix["trace_seconds"]))
    late = []
    tickets = []
    for i, a in enumerate(schedule):
        due = t0 + a.t
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        late.append(time.perf_counter() - due)
        try:
            tickets.append(daemon.submit(SolveRequest(
                graph=handle, b=rhs[i], tol=tol, maxiter=maxiter)))
        except Exception as e:      # refused: a miss, counted as failed
            tickets.append(e)
    t_close = t0 + seconds
    now = time.perf_counter()
    if now < t_close:
        time.sleep(t_close - now)
    answers, failures = [], 0
    deadline = t_close + RESULT_WAIT_S
    for i, t in enumerate(tickets):
        if isinstance(t, Exception):
            answers.append(None)
            failures += 1
            continue
        try:
            resp = t.result(timeout=max(0.0, deadline - time.perf_counter()))
            answers.append((resp, t._resolved_at))
        except Exception:
            answers.append(None)
            failures += 1
    compiles.close()
    trace_plain = None
    if profile is not None:
        profile.join()
        trace_plain = profile.load()
    wait1 = wait_hist.snapshot()
    spans = tracer.events() if trace else []
    if trace:
        tracer.disable()
    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    cycles = daemon.stats()["daemon"]
    daemon.close()
    levels = [(int(lv.n), layout.slab_shapes(lv))
              for lv in layout.hierarchy(svc, handle).levels]
    calls = recorder.calls if recorder is not None else []
    del svc, daemon, handle, recorder
    gc.collect()

    # -- after the window: latency, throughput and the reference check ----
    lap = reference.laplacian(n, src, dst, w)
    latencies, worst, good_cols, cols_in_window = [], 0.0, 0, 0
    iters = []
    for i, (a, ans) in enumerate(zip(schedule, answers)):
        if ans is None:
            latencies.append(math.inf)
            continue
        resp, resolved_at = ans
        latencies.append((resolved_at - (t0 + a.t)) * 1e3)
        rel = reference.relres(lap, rhs[i], resp.x)
        worst = max(worst, float(rel.max()))
        iters.extend(int(k) for k in np.atleast_1d(resp.iters))
        if resolved_at <= t_close:
            cols_in_window += a.width
            good_cols += a.width if bool(np.all(rel <= tol)) else 0
    p95 = stats.percentile(latencies, 95)
    third = max(1, len(latencies) // 3)
    first, last = latencies[:third], latencies[-third:]

    out = Run(setup_s=setup_s, attempted=len(schedule), failed=failures,
              memory_peak_bytes=memory_peak)
    out.e2e = {"setup_s": setup_s,
               "latency_p95_ms": p95 if math.isfinite(p95) else
               (deadline - t0) * 1e3,
               "solves_per_s": good_cols / seconds}
    out.compare("worst_relres", worst, tol)
    out.compare("unanswered_requests", failures, 0)
    late_ms = sorted(x * 1e3 for x in late)
    widths = {int(w_): int(c) for w_, c in zip(*np.unique(
        [a.width for a in schedule], return_counts=True))}
    out.notes += [
        f"requests {len(schedule)} in {seconds} s, widths {widths}",
        f"columns resolved in the window {cols_in_window}, correct "
        f"{good_cols}",
        f"generator lateness p50 {stats.percentile(late_ms, 50):.3f} ms "
        f"max {late_ms[-1]:.3f} ms",
        f"latency p50 {stats.percentile(latencies, 50):.1f} ms p95 "
        f"{p95:.1f} ms max {max(latencies):.1f} ms; mean of the first "
        f"third {sum(first) / len(first):.1f} ms, of the last third "
        f"{sum(last) / len(last):.1f} ms",
        f"daemon cycles {cycles['cycles']} triggers {cycles['triggers']}",
    ]
    out.ctx = {
        "latencies": latencies,
        "spans": spans,
        "iters": iters,
        "queue_wait": (wait1["sum"] - wait0["sum"],
                       wait1["count"] - wait0["count"]),
        "calls": calls,
        "levels": levels,
        "trace_plain": trace_plain,
        "sync_pc_ns": profile.sync_pc_ns if profile is not None else None,
        "trace": (devtrace.summarize(trace_plain, spans, profile.sync_pc_ns)
                  if trace_plain is not None else None),
    }
    return out
