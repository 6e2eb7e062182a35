"""Job cells: batch sparsify-and-solve jobs, one after another.

A job is what a batch user runs on a graph nobody has prepared: a fresh
``SolverService`` registers it, sparsifies it with pdGRASS, builds the
multilevel hierarchy and solves a block of right-hand sides with float64
refinement.  Nothing is cached between jobs except what the process keeps
by nature (compiled programs).  Set-up runs one job, so every program a
job needs is compiled or loaded before the window opens.  The window is a
closed loop of one client: the next job starts when the last returned;
the job running when the window closes is finished and counted.
"""
from __future__ import annotations

import gc
import time

from benchkit import devtrace, graphs, layout, reference, traffic
from benchkit.result import Run
from benchkit.serve import service


def _job(g, b, config, mix, service_kwargs):
    svc = service(config, service_kwargs)
    resp = svc.solve(g, b, tol=float(mix["tol"]), maxiter=int(mix["maxiter"]))
    # the sparsifier the build produced: level 0 of the hierarchy
    return resp, layout.laplacian_entries(layout.hierarchy(svc, g).levels[0])


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        compiles, service_kwargs=None, profile_dir=None, **_):
    import jax

    from repro.core.graph import build_graph
    from repro.obs import get_tracer

    config, mix = cell.config, cell.traffic
    n, src, dst, w = graphs.generate(config["graph"])
    g = build_graph(n, src, dst, w)
    cols = int(mix["columns"])

    _job(g, traffic.rhs(seed, 0, n, cols, warmup=True), config, mix, service_kwargs)
    gc.collect()

    tracer = get_tracer()
    if trace:
        tracer.clear()
        tracer.enable()
    profile = devtrace.Window(profile_dir) if trace else None
    jobs = []
    compiles.open()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    t_close = t0 + seconds
    while not jobs or time.perf_counter() < t_close:
        b = traffic.rhs(seed, len(jobs), n, cols)
        if profile is not None and len(jobs) == 0:
            profile.start()
        t_job = time.perf_counter()
        try:
            resp, entries = _job(g, b, config, mix, service_kwargs)
            jobs.append((b, resp, entries, time.perf_counter() - t_job))
        except Exception as e:     # a failed job: counted, never compared
            jobs.append((b, e, None, time.perf_counter() - t_job))
        if profile is not None and len(jobs) == 1:
            profile.stop()
    t_end = time.perf_counter()
    compiles.close()
    spans = tracer.events() if trace else []
    trace_plain = profile.load() if profile is not None else None
    if trace:
        tracer.disable()
    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    gc.collect()

    lap = reference.laplacian(n, src, dst, w)
    worst, failed, iters = 0.0, 0, []
    faults = {}
    for b, resp, entries, _ in jobs:
        if isinstance(resp, Exception):
            failed += 1
            continue
        worst = max(worst, float(reference.relres(lap, b, resp.x).max()))
        iters.extend(int(k) for k in resp.iters)
        for k, v in reference.sparsifier_faults(
                n, src, dst, w, *entries,
                float(config["solver"]["alpha"])).items():
            faults[k] = max(faults.get(k, 0), v)

    out = Run(setup_s=setup_s, attempted=len(jobs), failed=failed,
              memory_peak_bytes=memory_peak)
    out.e2e = {"setup_s": setup_s, "job_s": (t_end - t0) / len(jobs)}
    out.compare("worst_relres", worst, float(mix["tol"]))
    for k, v in faults.items():
        out.compare(k, v, 0)
    out.notes += [
        f"jobs {len(jobs)} in {t_end - t0:.3f} s (window {seconds} s): "
        + " ".join(f"{j[3]:.3f}" for j in jobs),
        f"iterations per column min {min(iters, default=0)} max "
        f"{max(iters, default=0)}",
    ]
    out.ctx = {"spans": spans, "iters": iters, "jobs": len(jobs),
               "trace_plain": trace_plain,
               "sync_pc_ns": profile.sync_pc_ns if profile else None,
               "trace": (devtrace.summarize(trace_plain, spans,
                                            profile.sync_pc_ns)
                         if trace_plain is not None else None)}
    return out
