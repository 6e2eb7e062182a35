"""The harness reads the solver's hierarchy only through
``benchkit.layout``: the program's accessors where it has them, else the
one-slab layout.  So a change of the slab layout that keeps the accessors
true needs no edit of the benchmark, and the sparsifier check and the byte
model read the same numbers from any split of a level's entries and
blocks.

No test here reads the program's own storage either: the one-slab
fallback is tested on levels built by hand, and the program's hierarchy
only through ``layout``, by properties that hold for any layout."""
import ast
import glob
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchkit import graphs, layout, reference, spec  # noqa: E402
from benchkit.roofline import (  # noqa: E402
    ell_contract_bytes, vcycle_level_bytes)

LAYOUT_NAMES = {"idx", "val", "artifacts"}
HARNESS = sorted(
    os.path.relpath(p, ROOT) for p in
    glob.glob(os.path.join(BENCH, "*.py"))
    + glob.glob(os.path.join(BENCH, "benchkit", "*.py"))
    + glob.glob(os.path.join(BENCH, "layer_metrics", "*.py"))
    + glob.glob(os.path.join(ROOT, "tests", "bench", "*.py"))
    if os.path.basename(p) != "layout.py")


def _layout_reads(path):
    """``(name, line)`` of every attribute ``.idx``, ``.val`` or
    ``.artifacts`` in ``path``, and of every ``getattr`` of one."""
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read(), path)
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in LAYOUT_NAMES:
            found.append((node.attr, node.lineno))
        elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
              and node.func.id in ("getattr", "hasattr")
              and len(node.args) > 1
              and isinstance(node.args[1], ast.Constant)
              and node.args[1].value in LAYOUT_NAMES):
            found.append((node.args[1].value, node.lineno))
    return found


@pytest.mark.parametrize("path", HARNESS)
def test_harness_reads_no_slab_layout_outside_layout_py(path):
    """Neither the harness nor its tests read ``.idx``, ``.val`` or
    ``.artifacts``: a layout change edits ``layout.py``'s fallback at most,
    and not even that where the program defines the accessors."""
    assert _layout_reads(path) == []


def test_the_walk_covers_the_harness_and_its_tests():
    assert "bench/benchkit/job.py" in HARNESS
    assert "tests/bench/test_bench_faults.py" in HARNESS
    assert "tests/bench/test_bench_layout.py" in HARNESS


def test_the_layout_walk_sees_each_kind_of_read():
    reads = {name for name, _ in _layout_reads("bench/benchkit/layout.py")}
    assert reads == LAYOUT_NAMES


GRAPHS = {
    "mesh2d": {"family": "mesh2d", "side": 16, "graph_seed": 0},
    "barabasi_albert": {"family": "barabasi_albert", "n": 300, "attach": 3,
                        "graph_seed": 0},
}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def built(request):
    """A service of the job cell's configuration holding the hierarchy of
    a small graph of each family."""
    from benchkit.serve import service
    from repro.core.graph import build_graph

    n, src, dst, w = graphs.generate(GRAPHS[request.param])
    svc = service(spec.resolve("social_ba.job", ROOT).config)
    handle = svc.register(build_graph(n, src, dst, w))
    return svc, handle, (n, src, dst, w)


def _one_slab(n, src, dst, w, pad=2):
    """A level in the one-slab layout, built by hand from an edge list:
    row ``i`` holds vertex ``i``'s diagonal and its ``-w`` entries, then
    zero-valued padding up to the widest row plus ``pad``."""
    nbrs = [[] for _ in range(n)]
    for s, d, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        nbrs[s].append((d, -x))
        nbrs[d].append((s, -x))
    width = 1 + max(len(r) for r in nbrs) + pad
    idx = np.zeros((n, width), np.int32)
    val = np.zeros((n, width), np.float32)
    for r, row in enumerate(nbrs):
        idx[r, 0], val[r, 0] = r, -sum(x for _, x in row)
        for j, (c, x) in enumerate(row, start=1):
            idx[r, j], val[r, j] = c, x
    return types.SimpleNamespace(n=n, idx=idx, val=val), idx, val


def _slab_edges(idx, val):
    """The sparsifier's edges as the harness read them from one ELL slab
    before it read through ``layout``: off-diagonal nonzero slots of each
    row, ``src < dst``, and whether both directions agree."""
    idx, val = np.asarray(idx), np.asarray(val)
    fwd, bwd = {}, {}
    for r in range(idx.shape[0]):
        for c, v in zip(idx[r].tolist(), val[r].tolist()):
            if c != r and v != 0:
                (fwd if r < c else bwd)[(min(r, c), max(r, c))] = -v
    keys = sorted(fwd)
    return ([k[0] for k in keys], [k[1] for k in keys],
            [fwd[k] for k in keys], fwd == bwd)


def test_hierarchy_is_the_one_the_artifacts_hold():
    """Without the program's ``hierarchy`` accessor the chain is the third
    item of the artifacts' pair; with it, the accessor's answer."""
    hier = types.SimpleNamespace(levels=())
    old = types.SimpleNamespace(
        artifacts=lambda g: ("key", (None, None, hier), "src"))
    assert layout.hierarchy(old, "g") is hier
    mine = types.SimpleNamespace(levels=())
    both = types.SimpleNamespace(
        artifacts=lambda g: ("key", (None, None, hier), "src"),
        hierarchy=lambda g: mine)
    assert layout.hierarchy(both, "g") is mine


@pytest.mark.parametrize("family", sorted(GRAPHS))
def test_one_slab_levels_read_as_the_slab_read_did(family):
    n, src, dst, w = graphs.generate(GRAPHS[family])
    slab, idx, val = _one_slab(n, src, dst, w)
    assert layout.slab_shapes(slab) == (idx.shape,)
    rows, cols, vals = layout.laplacian_entries(slab)
    assert len(vals) == n + 2 * len(src) and np.all(vals != 0)
    s, d, wt, symmetric = reference.sparsifier_edges(rows, cols, vals)
    want = _slab_edges(idx, val)
    assert (s.tolist(), d.tolist(), wt.tolist(), symmetric) == want
    assert symmetric
    order = np.argsort(src.astype(np.int64) * n + dst)
    assert np.array_equal(s, src[order]) and np.array_equal(d, dst[order])
    assert np.array_equal(wt.astype(np.float32),
                          w[order].astype(np.float32))


def test_the_program_s_hierarchy_reads_as_a_sound_sparsifier(built):
    """Read through ``layout`` alone, whatever the program's layout: level
    0 is a sparsifier of the graph with no fault, every level's Laplacian
    is symmetric, and its ELL blocks have a slot for each off-diagonal
    entry."""
    svc, handle, (n, src, dst, w) = built
    levels = layout.hierarchy(svc, handle).levels
    assert levels and levels[0].n == n
    for lv in levels:
        rows, cols, vals = layout.laplacian_entries(lv)
        assert reference.sparsifier_edges(rows, cols, vals)[3]
        slots = sum(r * c for r, c in layout.slab_shapes(lv))
        assert slots >= int((rows != cols).sum())
    faults = reference.sparsifier_faults(
        n, src, dst, w, *layout.laplacian_entries(levels[0]), alpha=0.05)
    assert set(faults.values()) == {0}


class _SplitLevel:
    """A level that stores its hub rows in an ELL block of their own, as
    a layout change might: only its accessors tell the harness so."""

    def __init__(self, n, entries, blocks):
        self.n = n
        self._entries, self._blocks = entries, blocks

    def laplacian_entries(self):
        return self._entries

    def slab_shapes(self):
        return self._blocks


def _split_hubs(rows, cols, vals, hubs):
    """The same Laplacian with the rows of ``hubs`` stored last, the other
    rows reversed, and one hub entry stored as two halves."""
    hub = np.isin(rows, hubs)
    rest = np.flatnonzero(~hub)[::-1]
    mine = np.flatnonzero(hub)
    one = mine[rows[mine] != cols[mine]][0]
    order = np.concatenate([rest, mine, [one]])
    rows, cols, vals = rows[order], cols[order], vals[order].copy()
    vals[len(rest) + np.flatnonzero(mine == one)[0]] /= 2
    vals[-1] /= 2
    return rows, cols, vals


def test_accessors_of_the_program_are_taken_as_they_stand(built):
    svc, handle, (n, src, dst, w) = built
    lv = layout.hierarchy(svc, handle).levels[0]
    rows, cols, vals = layout.laplacian_entries(lv)
    degree = np.bincount(rows, minlength=lv.n)
    hubs = np.argsort(-degree, kind="stable")[:3]
    split = _SplitLevel(lv.n, _split_hubs(rows, cols, vals, hubs),
                        ((lv.n, 6), (3, int(degree.max()))))
    assert layout.slab_shapes(split) == ((lv.n, 6), (3, int(degree.max())))
    got = layout.laplacian_entries(split)
    assert all(np.array_equal(a, b) for a, b in
               zip(got, split.laplacian_entries()))
    want = reference.sparsifier_edges(rows, cols, vals)
    have = reference.sparsifier_edges(*got)
    assert all(np.array_equal(a, b) for a, b in zip(want[:3], have[:3]))
    assert have[3] and reference.sparsifier_faults(
        n, src, dst, w, *got, alpha=0.05) == reference.sparsifier_faults(
        n, src, dst, w, rows, cols, vals, alpha=0.05)

    hier = types.SimpleNamespace(levels=(split,))
    own = types.SimpleNamespace(hierarchy=lambda g: hier)
    assert layout.hierarchy(own, handle) is hier


def test_byte_model_sums_the_blocks_and_one_block_is_the_slab():
    n, k = 1000, 3
    transfer = 2 * (n * 4 + 2 * n * k * 4)
    assert vcycle_level_bytes(n, ((n, 7),), k) == \
        4 * ell_contract_bytes(n, 7, k) + transfer
    assert vcycle_level_bytes(n, ((n, 7), (5, 40)), k) == \
        4 * (ell_contract_bytes(n, 7, k) + ell_contract_bytes(5, 40, k)) + \
        transfer


def test_vcycle_l0_roofline_reads_what_it_read_before():
    """Two recorded calls inside the window, each with level-0 ops of its
    own; a level-1 op and a call outside the window count for nothing.
    The expected value is the one-slab byte model as it stood before the
    blocks, term for term."""
    ms = 1_000_000
    trace = {"window_ns": [0, 10 * ms],
             "host": [["bench.solve_call.0", 1 * ms, 3 * ms],
                      ["bench.solve_call.1", 5 * ms, 4 * ms],
                      ["bench.solve_call.2", 9 * ms, 2 * ms]],
             "devices": {"d0": [
                 ["gather", "pcg/vcycle.L0.down/gather", 1.5 * ms, 1 * ms],
                 ["gather", "pcg/vcycle.L0.up/gather", 6 * ms, 2 * ms],
                 ["gather", "pcg/vcycle.L1.down/gather", 7 * ms, 1 * ms],
                 ["gather", "pcg/vcycle.L0.up/gather", 9.5 * ms, 0.4 * ms],
             ]}}
    calls = [{"k": 2, "loops": 10}, {"k": 8, "loops": 20},
             {"k": 16, "loops": 5}]
    ctx = {"trace_plain": trace, "calls": calls,
           "levels": [(16384, ((16384, 13),)), (8192, ((8192, 11),))],
           "peaks": {"hbm_bytes_per_s": 819e9}}

    def slab_bytes(n, width, k):
        return 4 * (n * width * 8 + n * width * k * 4 + n * k * 4) + \
            2 * (n * 4 + 2 * n * k * 4)

    moved = 11 * slab_bytes(16384, 13, 2) + 21 * slab_bytes(16384, 13, 8)
    want = 100.0 * moved / 3e-3 / 819e9
    got = spec.layer_reader("vcycle_l0_roofline.serve")(ctx)
    assert got == pytest.approx(want, rel=1e-12)
