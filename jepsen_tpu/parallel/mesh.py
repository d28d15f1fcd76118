"""Device mesh construction for checker sharding.

The reference's parallel axis is JVM threads under `bounded-pmap`
(independent.clj:346-367); ours is a `jax.sharding.Mesh` whose "keys"
axis carries independent per-key searches.  One mesh axis suffices:
per-key WGL has no cross-key communication, so any physical topology
(v5e-8 ring, multi-host DCN) works — XLA never inserts collectives into
the hot loop.
"""

from __future__ import annotations

from typing import Optional

_mesh_cache: dict = {}


def default_mesh(n_devices: Optional[int] = None, axis: str = "keys"):
    """A 1-D mesh over (the first n) local devices.  Memoized: device
    kernel caches key on mesh identity, so repeated checks must see the
    same Mesh object."""
    key = (n_devices, axis)
    mesh = _mesh_cache.get(key)
    if mesh is not None:
        return mesh

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    mesh = Mesh(np.asarray(devs), (axis,))
    _mesh_cache[key] = mesh
    return mesh


def multihost_init(coordinator: str, num_processes: int,
                   process_id: int) -> None:
    """Joins this process to a multi-host JAX cluster (the DCN analog
    of the reference's control-plane fan-out; its NCCL/MPI role is
    played by XLA collectives here).  After it returns,
    `jax.devices()` is the GLOBAL device list, so `default_mesh()`
    spans every host with no further changes.

    Mesh-axis guidance for multi-host runs:
      * the "keys" axis (per-key batched WGL, elle screens) has NO
        cross-key communication — shard it across hosts freely; the
        only DCN traffic is the initial scatter and final gather.
      * the "beam" axis (frontier sharding of ONE search,
        ops/wgl.py) all-gathers candidates every round — keep that
        mesh within one host's ICI domain (pass the local slice of
        jax.devices() to Mesh) or the collective rides DCN every
        barrier block.

    Call BEFORE any other JAX use: jax.distributed.initialize refuses
    an already-initialized backend, so there is no late-join path (a
    prior default_mesh()/jax.devices() call makes this raise).
    Exercised in CI by tests/test_multihost.py: two fresh processes
    join one cluster over localhost, build the global mesh, and run a
    cross-process psum.  The call delegates to
    jax.distributed.initialize, which blocks until all
    `num_processes` join."""
    if not coordinator or ":" not in coordinator:
        raise ValueError(
            f"coordinator must be host:port, got {coordinator!r}"
        )
    if not (0 <= process_id < num_processes):
        raise ValueError(
            f"process_id {process_id} outside [0, {num_processes})"
        )
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def checker_mesh(test: Optional[dict] = None):
    """The mesh a checker should use: the test map's "mesh" entry if set,
    else all local devices, else None for single-device."""
    if test and test.get("mesh") is not None:
        return test["mesh"]
    import jax

    if len(jax.devices()) > 1:
        return default_mesh()
    return None
