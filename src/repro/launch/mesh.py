"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Single pod : (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips
The "pod" axis carries only data-parallel gradient reduction (DCN-friendly);
"model" carries TP/EP/sequence-sharded KV (ICI).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh with Auto axes. The model code states layouts as sharding hints
    and leaves propagation to the compiler; ``jax.make_mesh`` defaults to
    Explicit axes, which type-check every op's sharding instead. Enter it
    with ``jax.set_mesh`` so the hints see its axis names."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh for CPU smoke tests through the same code path."""
    return make_mesh((1, 1), ("data", "model"))


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n
