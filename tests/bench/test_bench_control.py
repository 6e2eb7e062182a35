"""The control of each cell: the program's own lower-precision path (the
float32 device solve without its float64 refinement, ``max_refine=0``)
must read as ``correct: false`` where the program reads ``correct:
true``.  On the CPU, at the smallest mesh where the float32 solve alone
misses the served ``tol`` of 1e-5 (side 64; at side 32 it still meets
it); the job cell asks for 1e-6, which no float32 solve of this program
meets."""
import os
import sys
import time

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

CONTROL = {"max_refine": 0}
SERVE = dict(rate_hz=8.0, widths=[[1, 0.5], [2, 0.5]], max_batch_columns=2)


def small(cell_name, **traffic):
    cell = spec.resolve(cell_name, ROOT)
    g = cell.config["graph"]
    g["side" if g["family"] == "mesh2d" else "n"] = \
        64 if g["family"] == "mesh2d" else 1024
    cell.traffic.update(traffic)
    return cell


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_cache"))


@pytest.mark.parametrize("cell_name,traffic,seconds", [
    ("fem_mesh.serve", SERVE, 2.0), ("social_ba.job", {}, 1.0)])
@pytest.mark.parametrize("control", [False, True])
def test_control_fails_where_the_program_passes(cache, cell_name, traffic,
                                                seconds, control):
    run, _, _ = bench_run.measure(
        small(cell_name, **traffic), 2**31 + 21, seconds, False,
        {"hbm_bytes_per_s": 819e9}, cache=cache,
        t_start=time.perf_counter(),
        service_kwargs=CONTROL if control else None)
    worst, limit = run.compared["worst_relres"]
    assert run.failed == 0
    if control:
        assert not run.correct and worst > limit
    else:
        assert run.correct and worst <= limit
