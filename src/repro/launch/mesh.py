"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax initialization.

Topology (TPU v5e): 16x16 = 256 chips per pod; multi-pod adds a leading
'pod' axis over the DCN (2 pods = 512 chips).
  * 'model' — tensor/expert parallel (intra-pod ICI ring).
  * 'data'  — data parallel + FSDP (intra-pod).
  * 'pod'   — data parallel + FSDP across pods (DCN; gradient compression
              applies here — see dist.compress).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in auto sharding mode, which the
    sharding-constraint annotations of the model stack expect."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_par: int = None):
    """Elastic helper: best (data, model) mesh for whatever devices exist."""
    if model_par is None:
        model_par = min(16, n_devices)
    while n_devices % model_par:
        model_par //= 2
    return _auto_mesh((n_devices // model_par, model_par),
                            ("data", "model"))
