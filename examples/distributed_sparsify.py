"""Multi-device pdGRASS + solver service: the paper's mixed parallel strategy
on a JAX mesh, feeding the sparsifier-preconditioned solve.

Runs with 8 emulated host devices (set before jax import) — subtasks are
LPT-packed onto devices (outer parallelism); subtasks above the cutoff go
through the cross-device inner engine (one all_gather of candidates per
round).  Verifies bit-identical output vs the serial oracle, then routes a
batch of right-hand sides through a ``SolverService(mesh=...)`` on the SAME
mesh — the sharded solve plane: mesh-contracted hierarchy, row-sharded
batched PCG + V-cycle — and spot-checks parity against the single-device
solver.  One mesh, end to end.

    PYTHONPATH=src python examples/distributed_sparsify.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core import barabasi_albert  # noqa: E402
from repro.core.distributed import partition_subtasks  # noqa: E402
from repro.pipeline import Pipeline, pdgrass_config  # noqa: E402
from repro.solver import SolverService  # noqa: E402


def main():
    g = barabasi_albert(3000, 4, seed=0)
    print(f"graph: |V|={g.n} |E|={g.m}, devices={jax.device_count()}")

    # the distributed engine is just another recovery stage; the mesh is
    # runtime context (not config), passed through Pipeline.run
    dist_pipe = Pipeline(pdgrass_config(alpha=0.05, chunk=512,
                                        engine="distributed",
                                        stop_at_target=False))
    serial_pipe = Pipeline(pdgrass_config(alpha=0.05, chunk=512,
                                          engine="serial"))
    prep = dist_pipe.prepare(g)   # shared steps 1-3 for both engines
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    shard_of, giants, load = partition_subtasks(
        prep.subtask_sizes, jax.device_count())
    print(f"subtasks={prep.n_subtasks} giants={len(giants)} "
          f"outer load per device={load.tolist()}")
    sp = dist_pipe.run(g, prepared=prep, mesh=mesh)
    ref = serial_pipe.run(g, prepared=prep)
    assert np.array_equal(sp.recovered_mask, ref.recovered_mask), \
        "distributed != serial!"
    print(f"recovered={sp.stats['n_recovered']} on "
          f"{sp.stats['n_shards']} shards — "
          f"bit-identical to the serial oracle. OK")

    # downstream: serve solves on the SAME mesh — the sharded solve plane
    # (row-sharded PCG + V-cycle, mesh-sharded hierarchy contraction), so
    # sparsify + precondition + solve all run on one set of devices
    svc = SolverService(alpha=0.05, mesh=mesh)
    rng = np.random.default_rng(1)
    B = rng.standard_normal((g.n, 4)).astype(np.float32)
    B -= B.mean(axis=0)
    cold = svc.solve(g, B)
    warm = svc.solve(g, B)
    print(f"sharded solver service ({jax.device_count()} devices, "
          f"contraction={svc.contraction}): cold cache={cold.cache} "
          f"iters={int(cold.iters.max())} relres={cold.relres.max():.2e}; "
          f"warm cache={warm.cache} ({warm.solve_ms:.0f} ms for 4 RHS)")

    # parity spot-check against a single-device service
    ref = SolverService(alpha=0.05).solve(g, B)
    drift = np.abs((warm.x - warm.x[0]) - (ref.x - ref.x[0])).max()
    d_it = int(np.abs(np.asarray(warm.iters, np.int64)
                      - np.asarray(ref.iters, np.int64)).max())
    print(f"parity vs single-device: max rebased drift={drift:.1e}, "
          f"iteration-count delta={d_it}")
    # f32 reduction order differs across shard counts; on this 3000-vertex
    # graph the counts land within a few iterations of each other
    assert d_it <= 4
    assert drift <= 1e-4


if __name__ == "__main__":
    main()
