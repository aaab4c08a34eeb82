"""Distributed-runtime layer (core/collectives.py) unit tests.

Covers the shard_map wrapper and its collectives, cost_analysis()
normalization, the device-count helpers, and simulated multi-device mesh
setup on CPU (subprocess: the flag must precede first jax init).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.core.collectives import (
    MINERS_AXIS,
    host_device_count_env,
    make_miner_mesh,
    normalize_cost_analysis,
    require_device_count,
)

HERE = os.path.dirname(os.path.abspath(__file__))


# ----------------------------------------------------------------- shard_map
def test_shard_map_wrapper_runs_collectives():
    """The wrapped shard_map compiles a psum+ppermute program (any P>=1)."""
    mesh = make_miner_mesh()
    p = mesh.devices.size

    def prog(x):
        total = collectives.psum(x[0], MINERS_AXIS)
        shifted = collectives.ppermute(
            x[0], [(i, (i + 1) % p) for i in range(p)], MINERS_AXIS
        )
        return total, shifted[None]

    f = collectives.shard_map(
        prog, mesh=mesh, in_specs=(P(MINERS_AXIS),), out_specs=(P(), P(MINERS_AXIS)),
    )
    x = np.arange(p, dtype=np.int32)
    total, shifted = jax.jit(f)(x)
    assert int(total) == x.sum()
    np.testing.assert_array_equal(np.asarray(shifted), np.roll(x, 1))


def test_shard_map_wrapper_mixed_replication_specs():
    """check_replication=False must tolerate replicated + sharded out_specs
    (the engine mixes psum'd globals with per-miner outputs)."""
    mesh = make_miner_mesh()

    def prog(x):
        return collectives.psum(x[0], MINERS_AXIS), x * 2

    f = collectives.shard_map(
        prog, mesh=mesh, in_specs=(P(MINERS_AXIS),),
        out_specs=(P(), P(MINERS_AXIS)),
    )
    g, local = jax.jit(f)(np.ones(mesh.devices.size, np.int32))
    assert int(g) == mesh.devices.size
    assert np.asarray(local).tolist() == [2] * mesh.devices.size


# ---------------------------------------------------------- cost_analysis()
def test_normalize_cost_analysis_shapes():
    assert normalize_cost_analysis(None) == {}
    cost = {"flops": 2.0}
    got = normalize_cost_analysis(cost)
    assert got == {"flops": 2.0} and got is not cost  # a copy
    with pytest.raises(TypeError):
        normalize_cost_analysis(42)


def test_normalize_cost_analysis_on_real_compiled():
    comp = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)
    ).compile()
    got = normalize_cost_analysis(comp.cost_analysis())
    assert isinstance(got, dict)
    assert got.get("flops", 0) > 0


# ------------------------------------------------- simulated devices + mesh
def test_host_device_count_env_replaces_stale_flag():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 --foo=bar"}
    out = host_device_count_env(8, env)
    flags = out["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=8" in flags
    assert "--foo=bar" in flags
    assert sum(f.startswith("--xla_force_host_platform_device_count") for f in flags) == 1
    assert env["XLA_FLAGS"].endswith("--foo=bar")  # input not mutated


def test_host_device_count_env_pins_cpu():
    """Children on simulated host devices never reach for an accelerator."""
    out = host_device_count_env(4, {"JAX_PLATFORMS": "tpu"})
    assert out["JAX_PLATFORMS"] == "cpu"


def test_require_device_count(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--foo=bar")  # restored after the test
    have = jax.device_count()
    require_device_count(have)  # the devices JAX has: fine
    with pytest.raises(ValueError, match=f"asked for {have + 1} devices"):
        require_device_count(have + 1)


def test_miner_mesh_1d_axis():
    mesh = make_miner_mesh()
    assert mesh.axis_names == (MINERS_AXIS,)
    assert mesh.devices.ndim == 1
    assert mesh.devices.size == len(jax.devices())


def test_simulated_8_device_mesh_setup():
    """8 simulated CPU devices: mesh + shard_map psum in a fresh subprocess
    (pytest's jax is already initialized with this process's device count)."""
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives
        from repro.core.collectives import MINERS_AXIS, make_miner_mesh

        mesh = make_miner_mesh()
        f = collectives.shard_map(
            lambda x: (collectives.psum(x[0], MINERS_AXIS),),
            mesh=mesh, in_specs=(P(MINERS_AXIS),), out_specs=(P(),),
        )
        (total,) = jax.jit(f)(np.arange(mesh.devices.size, dtype=np.int32))
        print(json.dumps({
            "n_devices": len(jax.devices()),
            "axis_names": list(mesh.axis_names),
            "mesh_size": int(mesh.devices.size),
            "psum": int(total),
        }))
    """)
    env = host_device_count_env(8)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {
        "n_devices": 8, "axis_names": [MINERS_AXIS], "mesh_size": 8,
        "psum": sum(range(8)),
    }
