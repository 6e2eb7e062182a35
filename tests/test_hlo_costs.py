"""Unit tests for the trip-count-aware HLO cost analyzer — the §Roofline
numbers depend on it, so it gets closed-form validation of its own."""
import numpy as np

import jax
import jax.numpy as jnp

from repro.launch import hlo_costs


def _costs(fn, *sds):
    comp = jax.jit(fn).lower(*sds).compile()
    return hlo_costs.analyze_hlo(comp.as_text())


def test_matmul_flops_exact():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = _costs(lambda a, b: a @ b, x, w)
    assert abs(c.flops - 2 * 128 * 256 * 512) / (2 * 128 * 256 * 512) < 0.01


def test_scan_trip_count_multiplied():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), None
        y, _ = jax.lax.scan(body, a, None, length=17)
        return y

    c = _costs(f, x, w)
    per = 2 * 64 * 64 * 64
    assert 17 * per <= c.flops <= 17 * per * 1.2  # + elementwise tanh
    assert c.dynamic_whiles == 0


def test_nested_scan_trips_compose():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(a, b):
        def outer(c, _):
            def inner(d, _):
                return d @ b, None
            d, _ = jax.lax.scan(inner, c, None, length=5)
            return d, None
        y, _ = jax.lax.scan(outer, a, None, length=3)
        return y

    c = _costs(f, x, w)
    per = 2 * 32 * 32 * 32
    assert 15 * per <= c.flops <= 15 * per * 1.3


def test_dynamic_while_flagged_not_zeroed():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a):
        def cond(s):
            return jnp.sum(s) > 0  # data-dependent: no static trip count
        def body(s):
            return s @ s * 0.9
        return jax.lax.while_loop(cond, body, a)

    c = _costs(f, x)
    assert c.dynamic_whiles >= 1
    assert c.flops >= 2 * 64 * 64 * 64  # body counted at least once


def test_collective_bytes_counted():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_costs
        mesh = jax.make_mesh((8,), ("data",))
        def f(x):
            return jax.lax.psum(x, "data")
        fn = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
        with jax.set_mesh(mesh):
            comp = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
        c = hlo_costs.analyze_hlo(comp.as_text())
        assert c.coll.get("all-reduce", 0) > 0, c.coll
        print("COLL-OK", c.coll)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "COLL-OK" in out.stdout, out.stdout + out.stderr
