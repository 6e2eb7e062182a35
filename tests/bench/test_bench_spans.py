"""The readers of the program's spans: each returns its exact value on a
window of hand-built spans and nothing on spans of a program that lacks
them; and on a served run the program's device-call spans are the calls
the harness's own recorder sees, one for one."""
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

MS = 1_000_000      # ns


def _span(name, t0_ms, dur_ms, tid=1, depth=0, **args):
    ev = {"name": name, "ts_ns": t0_ms * MS, "dur_ns": dur_ms * MS,
          "tid": tid, "depth": depth}
    if args:
        ev["args"] = args
    return ev


def _window():
    """Two cycles, three groups, five requests; a device call on another
    thread that no group owns."""
    return [
        _span("serve.flush_cycle", 0, 100, tickets=[0, 1],
              waits_ms=[10.0, 30.0]),
        _span("solver.group", 1, 40, depth=1, tickets=[0]),
        _span("solver.solve", 2, 20, depth=2, **{"pass": 0}, loops=10),
        _span("solver.refine", 25, 10, depth=2, **{"pass": 1}, loops=5),
        _span("solver.group", 45, 50, depth=1, tickets=[1]),
        _span("solver.solve", 46, 30, depth=2, **{"pass": 0}, loops=0),
        _span("serve.batch_wait", 150, 25),
        _span("serve.flush_cycle", 200, 60, tickets=[2, 3],
              waits_ms=[5.0, 74.0]),
        _span("solver.group", 201, 50, depth=1, tickets=[2, 3]),
        _span("solver.solve", 202, 40, depth=2, **{"pass": 0}, loops=20),
        _span("solver.solve", 203, 1, tid=2, **{"pass": 0}, loops=7),
    ]


def read(metric, spans):
    return spec.layer_reader(metric)({"spans": spans})


def test_device_call_iter_ms_counts_only_calls_that_looped():
    # (20 + 10 + 40 + 1) ms over (10 + 5 + 20 + 7) trips; the call with
    # no trip is left out
    assert read("device_call_iter_ms.serve", _window()) == pytest.approx(
        71.0 / 42.0)


def test_group_host_ms_takes_the_calls_on_the_groups_thread_out():
    # (40 - 30) + (50 - 30) + (50 - 40) over three groups
    assert read("group_host_ms.serve", _window()) == pytest.approx(
        40.0 / 3.0)


def test_tail_queue_ms_is_the_mean_wait_at_the_tail():
    # latencies 10 + 41, 30 + 95, 5 + 51, 74 + 51: the tail is 125 ms,
    # reached by tickets 1 and 3
    assert read("tail_queue_ms.serve", _window()) == pytest.approx(52.0)


def _job_window():
    """Two jobs: each a group that builds its artifacts (sparsify, then
    contract, per level) and then solves."""
    spans = []
    for t0 in (0, 5000):
        spans += [
            _span("solver.group", t0, 4000, k=8, k_pad=8),
            _span("solver.artifacts", t0 + 10, 3000, depth=1),
            _span("pipeline.prepare", t0 + 20, 500, depth=4),
            _span("pipeline.tree", t0 + 30, 100, depth=5),
            _span("pipeline.recovery", t0 + 600, 300, depth=4),
            _span("hierarchy.contract", t0 + 1000, 200, depth=3),
            _span("pipeline.prepare", t0 + 1300, 250, depth=4),
            _span("pipeline.recovery", t0 + 1600, 150, depth=4),
            _span("hierarchy.contract", t0 + 1800, 100, depth=3),
            _span("solver.solve", t0 + 3100, 700, depth=1, loops=90),
        ]
    return spans


@pytest.mark.parametrize("metric,seconds", [
    ("prepare_s.job", 0.75), ("recovery_s.job", 0.45),
    ("contract_s.job", 0.3), ("solve_s.job", 1.0)])
def test_job_readers_give_seconds_per_job(metric, seconds):
    ctx = {"spans": _job_window(), "jobs": 2}
    assert spec.layer_reader(metric)(ctx) == pytest.approx(seconds)
    assert spec.layer_reader(metric)({"spans": [], "jobs": 2}) is None


NEW = ["device_call_iter_ms.serve", "group_host_ms.serve",
       "tail_queue_ms.serve"]


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_on_a_program_without_the_spans(metric):
    """The spans as a program without device-call loops or ticket ids
    records them: the metric is left out, and nothing raises."""
    bare = [dict(e, args={k: v for k, v in e.get("args", {}).items()
                          if k not in ("loops", "tickets", "waits_ms")})
            for e in _window()]
    assert read(metric, bare) is None


def _load_run():
    """``bench/run.py`` under a name no other module takes."""
    import importlib.util

    mod_spec = importlib.util.spec_from_file_location(
        "bench_run_cli", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_device_call_spans_match_the_recorded_calls(tmp_path):
    """A traced served run at a CPU size: each ``solver.solve`` and
    ``solver.refine`` span holds exactly one call the harness recorded,
    with the recorder's width as its ``k_pad`` and its loops."""
    cell = spec.resolve("fem_mesh.serve", ROOT)
    cell.config["graph"]["side"] = 20
    cell.traffic.update(rate_hz=6.0, widths=[[1, 0.5], [2, 0.5]],
                        max_batch_columns=2, trace_seconds=0.5)
    run, metrics, _ = _load_run().measure(
        cell, 2**31 + 13, 1.5, True, {"hbm_bytes_per_s": 819e9},
        cache=str(tmp_path), t_start=time.perf_counter())
    assert run.correct and run.failed == 0
    spans = run.ctx["spans"]
    dev = sorted((e for e in spans
                  if e["name"] in ("solver.solve", "solver.refine")),
                 key=lambda e: e["ts_ns"])
    traced_from = min(e["ts_ns"] for e in spans)
    calls = [c for c in run.ctx["calls"] if c["t0"] >= traced_from]
    assert dev and len(calls) == len(dev)
    for e, c in zip(dev, calls):
        assert e["ts_ns"] <= c["t0"] and c["t1"] <= e["ts_ns"] + e["dur_ns"]
        assert (e["args"]["k_pad"], e["args"]["loops"]) == (c["k"],
                                                            c["loops"])
    for metric in NEW:
        assert metrics[metric]["value"] > 0


def test_traced_job_run_reads_every_program_reader(tmp_path):
    """A traced job run at a CPU size: every reader of the job cell that
    reads the program's spans or responses finds something; the device's
    idle share needs a TPU's trace and reads nothing here."""
    cell = spec.resolve("social_ba.job", ROOT)
    cell.config["graph"]["n"] = 300
    run, metrics, _ = _load_run().measure(
        cell, 2**31 + 17, 1.0, True, {"hbm_bytes_per_s": 819e9},
        cache=str(tmp_path), t_start=time.perf_counter())
    assert run.correct and run.failed == 0
    names = {m["name"] for m in cell.per_layer}
    assert names == {"recovery_s.job", "prepare_s.job", "contract_s.job",
                     "solve_s.job", "pcg_iters.job", "idle_share.job"}
    assert set(metrics) == names - {"idle_share.job"}
    assert all(m["value"] > 0 for m in metrics.values())
