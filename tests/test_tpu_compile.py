"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: each case lowers a program with shapes at the scale of the
one-chip smoke graph (2^20 vertices, 3.1M edges) and compiles it with the
TPU compiler for a v5e that is described, not attached.  That finds what
interpret mode cannot: programs the TPU compiler refuses, and programs
whose temporaries outgrow the chip.  The Pallas kernels are recorded as
strict expected failures: Mosaic refuses their in-kernel gathers today,
so a change that makes one compile flips its case.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers import every file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

N = 1 << 20          # vertices (mesh2d(1024, 1024))
M = 3_141_633        # its edges
L = 8                # ELL width of a mesh-class sparsifier level
K = 8                # RHS block width the service warms up
GiB = 1 << 30


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, static_argnums=()):
    return jax.jit(fn, static_argnums=static_argnums).lower(*args).compile()


def test_ref_batched_matvec_compiles(one_chip):
    from repro.solver.device_pcg import make_matvec

    def mv(idx, val, x):
        return make_matvec(idx, val, "ref")(x)

    c = _compile(mv, _sds(one_chip, (N, L), jnp.int32),
                 _sds(one_chip, (N, L), jnp.float32),
                 _sds(one_chip, (N, K), jnp.float32))
    # the row-minor gather keeps temporaries near the [k, L, n] gather
    # itself (256 MiB); an [n, L, k] gather pads k to 128 lanes (4.5 GiB)
    assert c.memory_analysis().temp_size_in_bytes < GiB


def test_batched_pcg_jacobi_compiles(one_chip):
    from repro.solver.device_pcg import batched_pcg, make_jacobi, make_matvec

    def pcg(idx, val, b):
        diag = jnp.sum(val * (idx == jnp.arange(N)[:, None]), axis=1)
        return batched_pcg(make_matvec(idx, val, "ref"), b,
                           make_jacobi(diag), tol=1e-5, maxiter=2000)

    c = _compile(pcg, _sds(one_chip, (N, L), jnp.int32),
                 _sds(one_chip, (N, L), jnp.float32),
                 _sds(one_chip, (N, K), jnp.float32))
    assert c.memory_analysis().temp_size_in_bytes < 4 * GiB


def test_bfs_dist_compiles(one_chip):
    from repro.core.spanning_tree import bfs_dist

    c = _compile(bfs_dist, N, _sds(one_chip, (2 * M,), jnp.int32),
                 _sds(one_chip, (2 * M,), jnp.int32),
                 _sds(one_chip, (), jnp.int32), static_argnums=0)
    assert c.memory_analysis().temp_size_in_bytes < 4 * GiB


def test_boruvka_max_st_compiles(one_chip):
    from repro.core.spanning_tree import boruvka_max_st

    c = _compile(boruvka_max_st, N, _sds(one_chip, (M,), jnp.int32),
                 _sds(one_chip, (M,), jnp.int32),
                 _sds(one_chip, (M,), jnp.float32), static_argnums=0)
    assert c.memory_analysis().temp_size_in_bytes < 4 * GiB


# -- Pallas kernels: Mosaic's refusals, recorded --------------------------
# Small shapes (n = 4096), so the refusal is the kernel's own and not the
# size of a level held in VMEM.

NK = 4096


def _spmv_batched(s):
    from repro.kernels.vcycle_fused import spmv_ell_batched

    return _compile(
        lambda idx, val, x: spmv_ell_batched(idx, val, x, interpret=False),
        _sds(s, (NK, L), jnp.int32), _sds(s, (NK, L), jnp.float32),
        _sds(s, (NK, K), jnp.float32))


def _spmv(s):
    from repro.kernels.spmv_ell import spmv_ell

    return _compile(
        lambda idx, val, x: spmv_ell(idx, val, x, interpret=False),
        _sds(s, (NK, L), jnp.int32), _sds(s, (NK, L), jnp.float32),
        _sds(s, (NK,), jnp.float32))


def _fused_chebyshev(s):
    from repro.kernels.vcycle_fused import make_fused_chebyshev

    return _compile(
        lambda idx, val, diag, r: make_fused_chebyshev(
            idx, val, diag, 2.0, degree=2, interpret=False)(r),
        _sds(s, (NK, L), jnp.int32), _sds(s, (NK, L), jnp.float32),
        _sds(s, (NK,), jnp.float32), _sds(s, (NK, K), jnp.float32))


def _fused_restrict(s):
    from repro.kernels.vcycle_fused import make_fused_restrict_residual

    return _compile(
        lambda idx, val, agg, r, z: make_fused_restrict_residual(
            idx, val, agg, NK // 2, interpret=False)(r, z),
        _sds(s, (NK, L), jnp.int32), _sds(s, (NK, L), jnp.float32),
        _sds(s, (NK,), jnp.int32), _sds(s, (NK, K), jnp.float32),
        _sds(s, (NK, K), jnp.float32))


def _similarity(s):
    from repro.kernels.similarity import similarity_mark

    c1, kc = 9, 128
    return _compile(
        lambda *a: similarity_mark(*a, tile_m=512, interpret=False),
        _sds(s, (kc, c1), jnp.int32), _sds(s, (kc, c1), jnp.int32),
        _sds(s, (kc,), jnp.int32), _sds(s, (kc,), jnp.int32),
        _sds(s, (NK, c1), jnp.int32), _sds(s, (NK, c1), jnp.int32),
        _sds(s, (NK,), jnp.int32))


@pytest.mark.parametrize("build", [
    pytest.param(_spmv_batched, id="spmv_ell_batched"),
    pytest.param(_spmv, id="spmv_ell"),
    pytest.param(_fused_chebyshev, id="fused_chebyshev"),
    pytest.param(_fused_restrict, id="fused_restrict_residual"),
    pytest.param(_similarity, id="similarity_mark"),
])
@pytest.mark.xfail(strict=True, reason="Mosaic refuses the kernel's "
                   "in-kernel gather (or, for similarity_mark, its "
                   "vector<128xi1> shape cast)")
def test_pallas_kernel_compiles(one_chip, build):
    build(one_chip)
