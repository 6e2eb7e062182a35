"""A run of each cell, on the CPU at a tiny size, with the timed path
broken underneath: every fault the cells can have must read as
``correct: false``, and the unbroken run as ``correct: true``."""
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchkit import spec  # noqa: E402


def _load_run():
    """``bench/run.py`` under a name no other module takes."""
    import importlib.util

    mod_spec = importlib.util.spec_from_file_location(
        "bench_run_cli", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


bench_run = _load_run()

PEAKS = {"hbm_bytes_per_s": 819e9}


def tiny(cell_name, **traffic):
    cell = spec.resolve(cell_name, ROOT)
    g = cell.config["graph"]
    if g["family"] == "mesh2d":
        g["side"] = 20
    else:
        g["n"] = 300
    cell.traffic.update(traffic)
    return cell


SERVE = dict(rate_hz=6.0, widths=[[1, 0.5], [2, 0.5]], max_batch_columns=2)


def measure(cell, cache, seconds=1.5):
    run, _, _ = bench_run.measure(cell, 2**31 + 11, seconds, False, PEAKS,
                                  cache=str(cache),
                                  t_start=time.perf_counter())
    return run


def _zero_state(res):
    """The solve returns its starting state: no iteration ran."""
    return res._replace(x=jnp.zeros_like(res.x),
                        iters=jnp.zeros_like(res.iters))


def _half_batch(res):
    """Half of the batch's columns are left out of the answer."""
    k = res.x.shape[1]
    return res._replace(x=res.x.at[:, k // 2:].set(0.0))


SOLVE_FAULTS = {"zero_state": _zero_state, "half_batch": _half_batch}


def _break_solver(monkeypatch, fault):
    import repro.solver.service as service

    real = service.make_solver

    def make_solver(*a, **k):
        fn = real(*a, **k)

        def solve(b, tol=1e-5, maxiter=2000):
            return fault(fn(b, tol=tol, maxiter=maxiter))

        solve._cache_size = fn._cache_size
        return solve

    monkeypatch.setattr(service, "make_solver", make_solver)


def _alter_answers(monkeypatch):
    """Every answer is altered where the service produces it."""
    import repro.solver.service as service

    real = service.SolveResponse

    def response(x, **kw):
        x = np.array(x, dtype=np.float64)
        x.flat[0] += 1e-3 * np.abs(x).max()
        return real(x=x, **kw)

    monkeypatch.setattr(service, "SolveResponse", response)


@pytest.mark.parametrize("cell_name,traffic", [
    ("fem_mesh.serve", SERVE), ("social_ba.job", {})])
def test_unbroken_run_is_correct(tmp_path, cell_name, traffic):
    run = measure(tiny(cell_name, **traffic), tmp_path)
    assert run.correct, run.compared
    assert run.failed == 0 and run.attempted > 0


@pytest.mark.parametrize("fault", sorted(SOLVE_FAULTS))
@pytest.mark.parametrize("cell_name,traffic", [
    ("fem_mesh.serve", SERVE), ("social_ba.job", {})])
def test_broken_solve_is_not_correct(tmp_path, monkeypatch, cell_name,
                                     traffic, fault):
    _break_solver(monkeypatch, SOLVE_FAULTS[fault])
    run = measure(tiny(cell_name, **traffic), tmp_path)
    assert not run.correct
    assert run.compared["worst_relres"][0] > run.compared["worst_relres"][1]


@pytest.mark.parametrize("cell_name,traffic", [
    ("fem_mesh.serve", SERVE), ("social_ba.job", {})])
def test_altered_answer_is_not_correct(tmp_path, monkeypatch, cell_name,
                                       traffic):
    _alter_answers(monkeypatch)
    run = measure(tiny(cell_name, **traffic), tmp_path)
    assert not run.correct
    assert run.compared["worst_relres"][0] > run.compared["worst_relres"][1]


def test_altered_sparsifier_is_not_correct(tmp_path, monkeypatch):
    """A sparsifier edge whose weight differs from the graph's: one
    off-diagonal entry of level 0's Laplacian, as the harness reads it
    through ``layout`` (whatever the program's slab layout), scaled."""
    from benchkit import layout

    real = layout.laplacian_entries

    def laplacian_entries(level):
        rows, cols, vals = (np.array(a) for a in real(level))
        vals[np.flatnonzero(rows < cols)[0]] *= 1.25
        return rows, cols, vals

    monkeypatch.setattr(layout, "laplacian_entries", laplacian_entries)
    run = measure(tiny("social_ba.job"), tmp_path)
    assert not run.correct
    assert run.compared["sparsifier_edges_not_in_graph"][0] >= 1
