"""Sharded solve plane parity suite — 8 forced host devices in a subprocess
(XLA locks the device count at first init; the rest of the suite must see a
single device).

One subprocess covers the whole acceptance surface of the mesh knob:

  * sharded vs single-device batched PCG — re-based solutions within
    tolerance, per-column iteration counts within +-2;
  * sharded vs device hierarchy build — identical level sizes AND
    bit-identical per-level matchings/aggregations (the strict total order
    survives the collectives);
  * ``SolverService(mesh=...)`` end to end, including the v6 cache key
    separating mesh and single-device artifacts;
  * ``recover_mixed`` equivalence on a star-hub graph whose giant subtask
    exercises the inner round engine (static-shard-count path) on the same
    mesh the solve plane uses.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    if hasattr(jax.lax, "axis_size"):
        delattr(jax.lax, "axis_size")   # engines must not rely on it
    from repro.core import mesh2d, barabasi_albert, star_hub, prepare
    from repro.core.distributed import recover_mixed
    from repro.core.recovery import recover_serial
    from repro.pipeline import pdgrass_config
    from repro.solver import SolverService, build_hierarchy

    assert jax.device_count() == 8
    mesh = jax.make_mesh((8,), ("data",))
    cfg = pdgrass_config(alpha=0.05, chunk=256)
    rebase = lambda x: np.asarray(x, np.float64) - np.asarray(x, np.float64)[0]

    for name, g in [("mesh2d-16", mesh2d(16, 16, seed=0)),
                    ("ba-300", barabasi_albert(300, 3, seed=1))]:
        # --- hierarchy build parity: sharded vs device contraction -------
        h_dev = build_hierarchy(g, config=cfg, contraction="device")
        h_sh = build_hierarchy(g, config=cfg, contraction="sharded",
                               mesh=mesh)
        assert h_sh.level_sizes == h_dev.level_sizes, (
            name, h_sh.level_sizes, h_dev.level_sizes)
        assert h_sh.depth == h_dev.depth
        for ld, ls in zip(h_dev.levels, h_sh.levels):
            assert np.array_equal(np.asarray(ld.agg), np.asarray(ls.agg)), (
                name, "aggregation drifted between device and sharded")

        # --- solve parity: SolverService(mesh=...) vs single-device ------
        svc_sh = SolverService(pipeline=cfg, mesh=mesh)
        svc_sd = SolverService(pipeline=cfg)
        h = svc_sh.register(g)
        svc_sd.register(h)
        rng = np.random.default_rng(7)
        B = rng.standard_normal((g.n, 4)).astype(np.float32)
        B -= B.mean(axis=0)
        r_sh = svc_sh.solve(h, B)
        r_sd = svc_sd.solve(h, B)
        assert r_sh.converged and r_sd.converged, name
        np.testing.assert_allclose(rebase(r_sh.x), rebase(r_sd.x),
                                   atol=1e-4)
        d_it = np.abs(np.asarray(r_sh.iters, np.int64)
                      - np.asarray(r_sd.iters, np.int64))
        assert d_it.max() <= 2, (name, r_sh.iters, r_sd.iters)

        # --- v6 cache keys: mesh and single-device never alias -----------
        assert svc_sh._key(h, cfg) != svc_sd._key(h, cfg)
        assert svc_sh.stats()["mesh"]["descriptor"] == ("mesh", "data", 8)
        assert svc_sh.stats()["hierarchy"]["contraction"] == "sharded"
        # warm path stays warm on the mesh too
        assert svc_sh.solve(h, B).cache == "mem"

    # --- unpreconditioned sharded PCG parity (isolates the matvec) -------
    g = mesh2d(12, 12, seed=3)
    svc_sh = SolverService(alpha=0.05, precond="none", mesh=mesh,
                           contraction="device")
    svc_sd = SolverService(alpha=0.05, precond="none")
    rng = np.random.default_rng(9)
    b = rng.standard_normal((g.n, 2)).astype(np.float32)
    b -= b.mean(axis=0)
    r_sh, r_sd = svc_sh.solve(g, b), svc_sd.solve(g, b)
    np.testing.assert_allclose(rebase(r_sh.x), rebase(r_sd.x), atol=1e-4)
    assert np.abs(np.asarray(r_sh.iters, np.int64)
                  - np.asarray(r_sd.iters, np.int64)).max() <= 2

    # --- recovery on the same mesh: giant subtask -> fixed inner engine --
    g = star_hub(300, extra=250, seed=5)
    prep = prepare(g, chunk=256)
    st = recover_mixed(prep, mesh, chunk=256, cutoff=50)
    np.testing.assert_array_equal(recover_serial(prep.problem), st)
    print("SHARDED-PLANE-OK")
""")


@pytest.mark.slow
def test_sharded_plane_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARDED-PLANE-OK" in out.stdout, out.stdout + out.stderr
