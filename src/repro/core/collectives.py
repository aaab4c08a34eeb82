"""Distributed-runtime layer (the engine's only JAX surface).

The paper (§4) argues that a scalable miner needs a "well-engineered
communication protocol" kept *separate* from the mining logic.  This module
is that separation for the JAX substrate: `shard_map`, the SPMD collectives,
mesh construction, simulated host device counts, and compiled-artifact cost
introspection live here, so the superstep-phase modules
(expand/steal/global_sync) and the launchers have exactly one import for
their distributed runtime.  It targets the installed JAX (0.9): `shard_map`
is `jax.shard_map` with `check_vma`, and `Compiled.cost_analysis()` returns
a dict.
"""

from __future__ import annotations

import os
from typing import Callable, Sequence

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec

__all__ = [
    "MINERS_AXIS",
    "HOSTS_AXIS",
    "LOCAL_AXIS",
    "TOPO_AXES",
    "shard_map",
    "psum",
    "ppermute",
    "axis_index",
    "make_miner_mesh",
    "make_topo_mesh",
    "require_device_count",
    "host_device_count_env",
    "device_count",
    "normalize_cost_analysis",
]

# The engine's canonical 1-D mesh axis: one logical miner per device.
MINERS_AXIS = "miners"

# The 2-D topology mesh axes (repro.topo): miners laid out
# [n_hosts, devices_per_host]; global rank = hosts-index * dph + local-index.
HOSTS_AXIS = "hosts"
LOCAL_AXIS = "local"
TOPO_AXES = (HOSTS_AXIS, LOCAL_AXIS)


def shard_map(
    f: Callable,
    *,
    mesh: Mesh,
    in_specs,
    out_specs,
    check_replication: bool = False,
) -> Callable:
    """`jax.shard_map(f)` with the replication checker off by default.

    `check_replication=False` (the engine default) disables the static
    VMA checker: the BSP program's out_specs deliberately mix replicated
    collective results with per-miner outputs.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_replication)


# ---------------------------------------------------------------- collectives
# Thin aliases that pin the engine's collective surface to this module.

def psum(x, axis_name=MINERS_AXIS):
    """Sum `x` across the mesh axis (every miner gets the total).

    A *tuple* of axis names runs the staged hierarchical reduction: the
    last-named (innermost, intra-host) axis first, then outward — on the
    topo mesh that is one cheap on-host stage followed by one cross-host
    stage over already-reduced values.  Integer sums commute, so the result
    is bit-identical to a flat single-axis psum over the same miners.
    """
    if isinstance(axis_name, tuple):
        for name in reversed(axis_name):
            x = lax.psum(x, name)
        return x
    return lax.psum(x, axis_name)


def ppermute(x, perm: Sequence[tuple[int, int]], axis_name: str = MINERS_AXIS):
    """Point-to-point permutation: (src, dst) pairs; absent dst receives 0."""
    return lax.ppermute(x, axis_name, perm=list(perm))


def axis_index(axis_name=MINERS_AXIS):
    """This miner's position on the mesh axis (0..P-1), as a traced scalar.

    A *tuple* of axis names yields the flattened row-major rank — on the
    topo mesh `(HOSTS_AXIS, LOCAL_AXIS)` that is the global miner rank
    `host * devices_per_host + local`, matching `Topology.rank_of`.
    """
    if isinstance(axis_name, tuple):
        idx = lax.axis_index(axis_name[0])
        for name in axis_name[1:]:
            idx = idx * lax.psum(1, name) + lax.axis_index(name)
        return idx
    return lax.axis_index(axis_name)


# ----------------------------------------------------------------------- mesh
def make_miner_mesh(devices=None, axis_name: str = MINERS_AXIS) -> Mesh:
    """1-D mesh over all (or the given) devices — one logical miner each."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def make_topo_mesh(topology, devices=None) -> Mesh:
    """[n_hosts, devices_per_host] mesh with axes ("hosts", "local").

    `devices` defaults to every global device; jax orders them by process
    (each process owns a contiguous block), so the row-major reshape puts
    host h's devices in mesh row h and global rank = h * dph + l — exactly
    `Topology.rank_of`.  A single process can *simulate* a multi-host shape
    by reshaping its local devices the same way (the cross-host axis then
    permutes on-host, semantically identical, latency aside).
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    if devices.size != topology.n_proc:
        raise ValueError(
            f"topology {topology} needs {topology.n_proc} devices, "
            f"got {devices.size}"
        )
    grid = devices.reshape(topology.n_hosts, topology.devices_per_host)
    return Mesh(grid, TOPO_AXES)


def device_count() -> int:
    return jax.device_count()


_FORCE_FLAG = "--xla_force_host_platform_device_count"


def _device_count_flags(xla_flags: str, n: int) -> str:
    """`xla_flags` with the simulated device-count flag set to `n`."""
    flags = [f for f in xla_flags.split() if not f.startswith(_FORCE_FLAG)]
    return " ".join([f"{_FORCE_FLAG}={int(n)}", *flags])


def host_device_count_env(n: int, env: dict | None = None) -> dict:
    """Return a copy of `env` (default os.environ) for a child process that
    runs on `n` simulated CPU devices.

    XLA_FLAGS forces `n` host devices (replacing any existing device-count
    flag; it must precede the child's first jax init) and JAX_PLATFORMS is
    pinned to cpu, so such a child never competes with its parent for an
    accelerator.
    """
    env = dict(os.environ if env is None else env)
    env["XLA_FLAGS"] = _device_count_flags(env.get("XLA_FLAGS", ""), n)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def require_device_count(n: int) -> None:
    """Make this process run on exactly `n` devices, or raise ValueError.

    On the CPU platform `n` simulated host devices are forced, which works
    only before the first jax backend init (jax locks the device count
    then).  An accelerator has the devices it has.  Either way a mismatch
    is an error, never a silent run on a different machine shape.
    """
    os.environ["XLA_FLAGS"] = _device_count_flags(os.environ.get("XLA_FLAGS", ""), n)
    have = jax.device_count()
    if have != n:
        raise ValueError(
            f"asked for {n} devices but JAX has {have} "
            f"{jax.default_backend()} device(s) (on CPU, set the device count "
            "before JAX initializes)"
        )


# ------------------------------------------------------------ cost analysis
def normalize_cost_analysis(cost) -> dict:
    """`Compiled.cost_analysis()` as a plain dict; {} when there is none."""
    if cost is None:
        return {}
    if isinstance(cost, dict):
        return dict(cost)
    raise TypeError(f"unrecognized cost_analysis() return: {type(cost)!r}")
