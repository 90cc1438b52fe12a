"""Production mesh factories (charter: MULTI-POD DRY-RUN step 1).

Functions, not module-level constants — importing this module never
touches jax device state.  The single-pod mesh is 16x16 = 256 chips
(v5e pod); multi-pod adds a leading ``pod`` axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh) -> Mesh:
    """``mesh`` with every axis in Auto mode.  ``jax.make_mesh`` makes
    Explicit axes by default, under which every gather and vmap of the
    model code would need a per-op output sharding; in Auto mode arrays
    carry their NamedShardings and the compiler propagates them."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def activate_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def cost_analysis_dict(compiled) -> dict:
    return dict(compiled.cost_analysis() or {})


def data_axes(mesh) -> tuple:
    """Axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def client_axes(mesh) -> tuple:
    """Axes the round engine's *stacked client* dimension shards over:
    the dedicated ``pod`` axis on multi-pod meshes (one simulated client
    per pod slice), else the ``data`` axis.  launch/sharding.py builds
    the explicit client-axis NamedShardings from this."""
    return ("pod",) if "pod" in mesh.axis_names else ("data",)


def client_axis_size(mesh) -> int:
    size = 1
    for a in client_axes(mesh):
        size *= mesh.shape[a]
    return size


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def n_edges(mesh) -> int:
    """Edge-aggregator count of the two-hop client -> edge -> server
    hierarchy: one edge per pod on a multi-pod mesh, else a single
    (degenerate, flat) edge.  The cohort-streaming driver derives its
    hierarchical ledger accounting — and the compiled round its
    hierarchical client-axis reduce — from this."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("pod", 1))
