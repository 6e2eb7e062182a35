"""The benchmark's yardstick on the CPU: cells found by name, seeded
schedules, the percentile, the float64 reference, the trace reduction and
the command's refusal to run without a TPU."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchkit import devtrace, graphs, reference, spec, stats, traffic  # noqa: E402
from benchkit.roofline import peaks, vcycle_level_bytes  # noqa: E402

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = spec.resolve(cell, ROOT)
    assert c.config["name"] == cell.split(".")[0]
    assert c.traffic["loop"] in ("open", "closed")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(spec.layer_reader(m["name"]))


def test_every_per_layer_metric_has_a_reader_and_cells():
    cells = set(CELLS)
    for m in BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        assert set(m["workloads"]) <= cells


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.resolve("no_such.cell", ROOT)


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH,
                                                         "layer_metrics"))
                 if f.endswith(".py"))


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_in_an_empty_run(metric):
    """A reader with nothing to read returns ``None``, never 0."""
    assert spec.layer_reader(metric)({}) is None


CONFIG_FILES = sorted(f"bench/configs/{f}" for f in os.listdir(
    os.path.join(BENCH, "configs")) if f.endswith(".json"))


@pytest.mark.parametrize("config", CONFIG_FILES)
def test_config_graph_matches_its_stated_scale(config):
    cfg = json.load(open(os.path.join(ROOT, config)))
    n, src, dst, w = graphs.generate(cfg["graph"])
    assert (n, len(src)) == (cfg["num_vertices"], cfg["num_edges"])
    assert np.all(src < dst) and w.min() >= 1.0 and w.max() <= 10.0
    again = graphs.generate(cfg["graph"])
    assert all(np.array_equal(a, b) for a, b in zip((src, dst, w),
                                                    again[1:]))


MIX = {"rate_hz": 20.0, "widths": [[1, 0.75], [8, 0.25]],
       "max_batch_columns": 16}


def test_schedule_repeats_byte_for_byte_per_seed():
    a = traffic.open_schedule(MIX, 2**31 + 5, 10.0)
    b = traffic.open_schedule(MIX, 2**31 + 5, 10.0)
    assert a == b
    assert a != traffic.open_schedule(MIX, 2**31 + 6, 10.0)
    r1 = traffic.rhs(2**31 + 5, 3, 50, 8)
    assert r1.tobytes() == traffic.rhs(2**31 + 5, 3, 50, 8).tobytes()
    assert r1.dtype == np.float32 and r1.shape == (50, 8)
    assert traffic.rhs(-7, 0, 50, 1).shape == (50,)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.open_schedule(MIX, 1, 10.0)
    b = traffic.open_schedule(MIX, 99, 10.0)
    assert len(a) == len(b) == 200
    assert sorted(x.width for x in a) == sorted(x.width for x in b)
    assert sum(x.width == 8 for x in a) == 50
    gaps = lambda s: sorted(np.diff([x.t for x in s]).round(12))  # noqa: E731
    assert a[0].t == 0.0 and a[-1].t < 10.0
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.5


def test_warm_buckets_cover_the_overshooting_group():
    assert traffic.max_group_columns(MIX) == 23
    assert traffic.buckets(23) == [1, 2, 4, 8, 16, 32]


def test_percentile_covers_every_request_and_misses_are_inf():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    six_missing = xs[:94] + [math.inf] * 6
    assert stats.percentile(six_missing, 95) == math.inf
    five_missing = xs[:95] + [math.inf] * 5
    assert stats.percentile(five_missing, 95) == 95
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_the_quartile_gap_over_the_median():
    assert stats.spread([10.0, 10.0, 10.0, 10.0]) == 0.0
    assert stats.spread([9, 10, 10, 11]) == pytest.approx(
        (10.75 - 9.25) / 10.0)


@pytest.fixture(scope="module")
def small_mesh():
    n, src, dst, w = graphs.generate({"family": "mesh2d", "side": 12,
                                      "graph_seed": 3})
    return n, src, dst, w, reference.laplacian(n, src, dst, w)


def test_reference_accepts_a_solution_and_rejects_a_perturbed_one(small_mesh):
    import scipy.sparse.linalg as spla

    n, src, dst, w, lap = small_mesh
    b = np.random.default_rng(0).standard_normal((n, 3))
    bc = b - b.mean(axis=0)
    x = np.zeros_like(bc)
    x[1:] = spla.splu(lap[1:, 1:].tocsc()).solve(bc[1:])
    assert reference.relres(lap, b, x).max() < 1e-10
    bad = x.copy()
    bad[5, 1] += 1e-3 * np.abs(x).max()
    rel = reference.relres(lap, b, bad)
    assert rel[1] > 1e-5 and rel[0] < 1e-10
    assert np.all(reference.relres(lap, b, x[:, :2]) == np.inf)


def _entries(n, s, d, wt):
    """``(rows, cols, vals)`` of the Laplacian of the edges ``s, d, wt``:
    ``-w`` both ways and the weighted degree on the diagonal."""
    deg = np.bincount(np.concatenate([s, d]), np.concatenate([wt, wt]),
                      minlength=n)
    rows = np.concatenate([s, d, np.arange(n)])
    cols = np.concatenate([d, s, np.arange(n)])
    vals = np.concatenate([-wt, -wt, deg]).astype(np.float32)
    return rows, cols, vals


def test_sparsifier_check_passes_a_spanning_tree_and_catches_faults(
        small_mesh):
    from scipy.sparse.csgraph import minimum_spanning_tree
    import scipy.sparse as sp

    n, src, dst, w, _ = small_mesh
    t = minimum_spanning_tree(sp.coo_matrix((w, (src, dst)),
                                            shape=(n, n))).tocoo()
    keep = {(min(a, b), max(a, b)) for a, b in zip(t.row, t.col)}
    mask = np.array([(a, b) in keep for a, b in zip(src, dst)])
    s, d, wt = src[mask], dst[mask], w[mask]
    ok = reference.sparsifier_faults(n, src, dst, w,
                                     *_entries(n, s, d, wt), alpha=0.05)
    assert set(ok.values()) == {0}
    wrong_w = wt.copy()
    wrong_w[0] *= 1.5
    assert reference.sparsifier_faults(
        n, src, dst, w, *_entries(n, s, d, wrong_w),
        alpha=0.05)["sparsifier_edges_not_in_graph"] == 1
    cut = reference.sparsifier_faults(
        n, src, dst, w, *_entries(n, s[1:], d[1:], wt[1:]), 0.05)
    assert cut["sparsifier_extra_components"] == 1
    full = reference.sparsifier_faults(n, src, dst, w,
                                       *_entries(n, src, dst, w), 0.05)
    assert full["sparsifier_edges_over_budget"] > 0


def test_peaks_table_knows_the_v5e_and_refuses_others():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")


def test_vcycle_byte_model_counts_four_contractions():
    one = vcycle_level_bytes(100, ((100, 7),), 1)
    assert one == 4 * (100 * 7 * 8 + 100 * 7 * 4 + 100 * 4) + \
        2 * (100 * 4 + 2 * 100 * 4)
    assert vcycle_level_bytes(100, ((100, 7),), 16) > one


RECORDED = os.path.join(BENCH, "testdata", "trace_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_busy_idle_and_top_ops(recorded):
    trace = recorded["trace"]
    lo, hi = trace["window_ns"]
    busy = devtrace.busy_ns(trace)
    assert 0 < busy <= hi - lo
    summary = devtrace.summarize(trace, recorded["spans"],
                                 recorded["sync_pc_ns"])
    assert summary["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert summary["busy_s"] == pytest.approx(busy / 1e9)
    assert recorded["expected"]["busy_s"] == pytest.approx(
        summary["busy_s"], rel=1e-9)
    ops = summary["breakdown"]["device_ops"]
    gaps = summary["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo - busy) / 1e9, rel=1e-6)
    assert [g[0] for g in gaps] == [g[0] for g in
                                    recorded["expected"]["idle_gaps"]]


def test_union_merges_overlaps_and_clips_to_the_window():
    merged = devtrace.union_ns([(0, 10), (5, 20), (30, 40), (35, 36),
                                (90, 120)], (2, 100))
    assert merged == [(2, 20), (30, 40), (90, 100)]
    trace = {"window_ns": [0, 100], "host": [],
             "devices": {"d0": [["a", "s/x", 10, 10], ["b", "s/vcycle.L0.y",
                                                       50, 20]]}}
    assert devtrace.busy_ns(trace) == 30
    gaps = devtrace.idle_gaps(trace, [("solver.refine", 20, 50)])
    assert gaps == [("no program span", 10), ("solver.refine", 30),
                    ("no program span", 30)]
    assert devtrace.scoped_time_ns(trace, "vcycle.L0.", 0, 100) == 20


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
