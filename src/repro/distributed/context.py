"""Process-global island-mesh context for the HTAP plane.

The mesh placement tier (``core.backend.MeshBackend``) needs one 1-D
`jax.Mesh` over ``sharding.ISLAND_AXIS`` per island count. `HTAPSession`
installs its backend's mesh here when the session opens, so every later
backend resolution in the same process (ad-hoc `get_backend` calls,
nested drivers) reuses the installed mesh instead of re-deriving device
assignments — one process, one island→device mapping.

``island_mesh(n)`` is the resolution entry point: it returns the
installed mesh when the axis size matches, else builds (and caches) a
mesh over the first ``n`` local devices. Fewer than ``n`` devices is an
actionable error naming the CPU emulation escape hatch
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, or
``REPRO_HOST_DEVICES=N`` through ``benchmarks/run.sh``) — a mesh axis
cannot be larger than the device count.

The module also keeps the layer-level ``constrain`` partitioning hook
(used by the neural layers under ``repro.nn``/``repro.models``): model
code stays mesh-agnostic and applies ``with_sharding_constraint`` only
when a partitioning context is installed.
"""

from __future__ import annotations

import contextlib
import weakref

import jax

from repro.distributed.sharding import ISLAND_AXIS

# ---------------------------------------------------------------------------
# Island mesh (HTAP plane)
# ---------------------------------------------------------------------------

# Installed island meshes, oldest first: (owner, mesh), the owner a weak
# reference to the session that installed it, or None for an install
# that only `clear_island_mesh` removes. The newest entry whose owner is
# alive is the process's mesh, so a session that is dropped unfinished
# never leaves its mesh behind.
_installed: list[tuple[weakref.ref | None, object]] = []
_mesh_cache: dict[int, object] = {}  # built meshes by island count


def install_island_mesh(mesh, owner=None) -> None:
    """Install `mesh` as the process's island mesh (HTAPSession does this,
    as its ``owner``, for its lifetime: `release_island_mesh`).

    The mesh must carry exactly the ``ISLAND_AXIS`` axis — installing an
    arbitrary LM-style mesh here would silently misplace shard arrays.
    """
    if tuple(mesh.axis_names) != (ISLAND_AXIS,):
        raise ValueError(
            f"island mesh must have exactly one axis {ISLAND_AXIS!r}, got "
            f"axes {tuple(mesh.axis_names)}")
    _installed.append((None if owner is None else weakref.ref(owner), mesh))


def release_island_mesh(owner) -> None:
    """Remove every mesh ``owner`` installed; the one installed before it,
    by an owner still alive, is the process's mesh again."""
    _installed[:] = [(ref, m) for ref, m in _installed
                     if ref is None or ref() is not owner]


def current_island_mesh():
    """The installed island mesh, or None."""
    _installed[:] = [(ref, m) for ref, m in _installed
                     if ref is None or ref() is not None]
    return _installed[-1][1] if _installed else None


def clear_island_mesh() -> None:
    _installed.clear()


def island_mesh(n_islands: int):
    """Resolve the mesh for `n_islands` analytical islands.

    Prefers the installed process mesh when its island axis matches;
    otherwise builds a 1-D mesh over the first `n_islands` devices and
    caches it (meshes are immutable and hashable — every backend with the
    same island count shares one).
    """
    n_islands = int(n_islands)
    if n_islands < 1:
        raise ValueError(f"n_islands must be >= 1, got {n_islands}")
    installed = current_island_mesh()
    if installed is not None and installed.shape[ISLAND_AXIS] == n_islands:
        return installed
    mesh = _mesh_cache.get(n_islands)
    if mesh is None:
        have = jax.device_count()
        if have < n_islands:
            raise RuntimeError(
                f"mesh placement needs {n_islands} devices (one per "
                f"analytical island) but this process has {have}; run on "
                f"real multi-device hardware, or emulate on CPU with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_islands} set before jax imports (benchmarks/run.sh "
                f"does this via REPRO_HOST_DEVICES={n_islands}), or use "
                f"the stacked placement (e.g. 'pallas@{n_islands}')")
        mesh = jax.make_mesh((n_islands,), (ISLAND_AXIS,))
        _mesh_cache[n_islands] = mesh
    return mesh


# ---------------------------------------------------------------------------
# Layer-level partitioning hook (neural layers; mesh-agnostic model code)
# ---------------------------------------------------------------------------

_CTX: dict | None = None


def set_partitioning(mesh, dp_axes: tuple, model_axis: str = "model") -> None:
    global _CTX
    _CTX = {"mesh": mesh, "dp": dp_axes, "model": model_axis}


def clear_partitioning() -> None:
    global _CTX
    _CTX = None


@contextlib.contextmanager
def partitioning(mesh, dp_axes: tuple, model_axis: str = "model"):
    set_partitioning(mesh, dp_axes, model_axis)
    try:
        yield
    finally:
        clear_partitioning()


def constrain(x, *spec):
    """with_sharding_constraint if a partitioning context is installed.

    spec entries: "dp" -> the data axes, "model" -> model axis, None -> none.
    """
    if _CTX is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _CTX["mesh"]
    resolved = []
    for s in spec:
        if s == "dp":
            dp = _CTX["dp"]
            resolved.append(dp if len(dp) > 1 else dp[0])
        elif s == "model":
            resolved.append(_CTX["model"])
        else:
            resolved.append(s)
    # drop axes that don't divide
    dims = x.shape
    fixed = []
    for dim, ax in zip(dims, resolved):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        fixed.append(ax if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))
