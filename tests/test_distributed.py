"""Distributed recovery tests.

The 8-device equivalence suite runs in a subprocess (XLA locks the device
count at first init, and the rest of the suite must see a single device);
the regression tests for the inner engine's static shard count and the
per-dtype pad fills run in-process on a 1-device mesh.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import grid2d, prepare
from repro.core.distributed import (build_outer_shards, pad_fill_value,
                                    partition_subtasks, recover_mixed)
from repro.core.recovery import recover_serial

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    # regression guard: the engines must never rely on jax.lax.axis_size
    # (the shard count is passed statically from the mesh) — delete it so
    # any reintroduced dynamic-axis-size fallback fails loudly here
    if hasattr(jax.lax, "axis_size"):
        delattr(jax.lax, "axis_size")
    from repro.core import grid2d, barabasi_albert, star_hub, prepare
    from repro.core.recovery import recover_serial
    from repro.core.distributed import recover_mixed, partition_subtasks

    mesh = jax.make_mesh((8,), ("data",))
    cases = [
        ("grid", grid2d(15, 15, seed=1), None),
        ("ba", barabasi_albert(400, 3, seed=3), None),
        ("star-giant", star_hub(300, extra=250, seed=5), 50),
    ]
    for name, g, cutoff in cases:
        prep = prepare(g, chunk=256)
        st_serial = recover_serial(prep.problem)
        st_mixed = recover_mixed(prep, mesh, chunk=256, cutoff=cutoff)
        assert np.array_equal(st_serial, st_mixed), name
        shard_of, giants, load = partition_subtasks(
            prep.subtask_sizes, 8, cutoff=cutoff)
        if name == "star-giant":
            assert len(giants) >= 1      # hub subtask went to the inner engine
    print("DISTRIBUTED-OK")
""")


@pytest.mark.slow
def test_mixed_distributed_equals_serial():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "DISTRIBUTED-OK" in out.stdout, out.stdout + out.stderr


# -- inner engine: static shard count ----------------------------------------

def test_inner_engine_works_without_jax_lax_axis_size(monkeypatch):
    """Regression for the n_sh derivation bug: the engine used a
    ``jax.lax.psum(1, axis)`` fallback on jax builds without
    ``jax.lax.axis_size``, which yields a *traced* value — and
    ``jnp.arange(n_sh)`` then fails to trace inside the round loop.  The
    shard count now arrives statically from the ``recover_inner`` wrapper
    (it knows ``mesh.shape[axis]``), so the engine must run with the
    attribute entirely absent."""
    monkeypatch.delattr(jax.lax, "axis_size", raising=False)
    assert not hasattr(jax.lax, "axis_size")
    g = grid2d(9, 9, seed=2)
    prep = prepare(g, chunk=128)
    mesh = jax.make_mesh((1,), ("data",))
    # cutoff=1 routes every subtask through the inner engine
    st_mixed = recover_mixed(prep, mesh, chunk=128, cutoff=1)
    np.testing.assert_array_equal(recover_serial(prep.problem), st_mixed)


# -- pad fills: per-dtype sentinels ------------------------------------------

def test_pad_fill_value_per_dtype():
    assert pad_fill_value(np.float32, lowest=True) == -np.inf
    assert pad_fill_value(np.int32, lowest=True) == np.iinfo(np.int32).min
    assert pad_fill_value(np.int64, lowest=True) == np.iinfo(np.int64).min
    assert pad_fill_value(np.int32) == -1
    assert pad_fill_value(np.float32) == -1.0
    with pytest.raises(TypeError, match="unsigned"):
        pad_fill_value(np.uint32)
    with pytest.raises(TypeError, match="unsigned"):
        pad_fill_value(np.uint8, lowest=True)


def _int_score_prep(g, chunk=128):
    """A Prepared whose problem carries an *integer* score array (rank
    order preserved, so the pre-sorted recovery order is unchanged)."""
    prep = prepare(g, chunk=chunk)
    score = np.asarray(prep.problem.score)
    int_score = np.argsort(np.argsort(score)).astype(np.int32)
    return dataclasses.replace(
        prep, problem=prep.problem._replace(score=int_score))


def test_outer_shards_accept_integer_scores():
    """``np.full(..., -np.inf, dtype=int32)`` raised before the per-dtype
    fill fix; integer-score problems must shard with ``iinfo.min`` pads."""
    g = grid2d(9, 9, seed=3)
    prep = _int_score_prep(g)
    shard_of, giants, _ = partition_subtasks(prep.subtask_sizes, 2)
    sharded = build_outer_shards(prep.problem, prep.subtask_sizes,
                                 shard_of, 2, chunk=128)
    score = np.asarray(sharded.score)
    assert score.dtype == np.int32
    pad = np.asarray(sharded.seg) < 0
    assert pad.any()
    assert (score[pad] == np.iinfo(np.int32).min).all()


def test_recover_mixed_equals_serial_on_integer_scores():
    g = grid2d(9, 9, seed=4)
    prep = _int_score_prep(g)
    mesh = jax.make_mesh((1,), ("data",))
    st_mixed = recover_mixed(prep, mesh, chunk=128)
    np.testing.assert_array_equal(recover_serial(prep.problem), st_mixed)
