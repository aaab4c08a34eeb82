"""Multi-process mesh bring-up + argument/result marshalling (DESIGN.md §12).

Three concerns, all behind this module:

1. **Bring-up** — `init_distributed()` wraps `jax.distributed.initialize`
   with the CPU-collectives (gloo) configuration a simulated multi-host run
   needs.  It must run before the first jax backend touch in the process;
   the device-count XLA flag must already be in the environment (the
   launcher below sets both).

2. **Marshalling** — the engine's host pre/postprocess is deterministic
   numpy: every process derives the *identical* full argument arrays from
   the same dataset, so `globalize_args` just wraps them as global
   `jax.Array`s (each process contributing its local shards via
   `make_array_from_callback`) matching the phase program's PartitionSpecs,
   and `fetch_outputs` brings results back — `process_allgather` for
   miner-sharded outputs, the local replica for replicated ones.  Every
   process ends up with the same numpy outputs, so the existing
   single-process postprocess (and ResultSet construction) runs unchanged
   everywhere.

3. **CI testability** — `launch_local_cluster` spawns N local processes x
   M simulated devices against a 127.0.0.1 coordinator, mirroring
   tests/engine_subproc_main.py's launcher: each child runs a harness
   script with the cluster coordinates folded into its JSON spec, and the
   parent returns process 0's JSON stdout.  Multi-host code paths get
   exercised on one machine, every commit.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys

import numpy as np

__all__ = [
    "init_distributed",
    "is_multiprocess",
    "globalize_args",
    "fetch_outputs",
    "free_port",
    "launch_local_cluster",
]


def init_distributed(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """`jax.distributed.initialize` with gloo CPU collectives.

    Call before any other jax API in the process (the backend locks its
    device/process view on first use).  On CPU the cross-process collective
    transport must be selected explicitly — without it the processes come
    up as P isolated singletons.
    """
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_multiprocess() -> bool:
    """True under a live jax.distributed runtime spanning > 1 process."""
    import jax

    return jax.process_count() > 1


# ----------------------------------------------------------- marshalling
def globalize_args(args, mesh, specs):
    """Host numpy argument tuple -> global jax.Arrays on `mesh` per `specs`.

    Every process must pass the *same* full arrays (engine preprocessing is
    deterministic, so they do); each wraps only its addressable shards.
    Single-process meshes pass through unchanged — the dispatch path stays
    zero-cost there.
    """
    import jax
    from jax.sharding import NamedSharding

    if not is_multiprocess():
        return tuple(args)
    out = []
    for arg, spec in zip(args, specs):
        arr = np.asarray(arg)
        sharding = NamedSharding(mesh, spec)
        out.append(
            jax.make_array_from_callback(
                arr.shape, sharding, lambda idx, a=arr: a[idx]
            )
        )
    return tuple(out)


def fetch_outputs(raw, specs):
    """Global jax.Array outputs -> full numpy arrays on every process.

    Miner-sharded outputs (non-empty spec) are allgathered across
    processes; replicated outputs are read from the local replica.  After
    this, every process holds identical numpy results and the ordinary
    host postprocess produces the same ResultSet everywhere.
    """
    import jax
    from jax.experimental import multihost_utils

    if not is_multiprocess():
        return raw
    out = []
    for x, spec in zip(raw, specs):
        if isinstance(x, jax.Array) and any(s is not None for s in spec):
            out.append(
                np.asarray(multihost_utils.process_allgather(x, tiled=True))
            )
        elif isinstance(x, jax.Array):
            out.append(np.asarray(x.addressable_data(0)))
        else:
            out.append(np.asarray(x))
    return tuple(out)


# ------------------------------------------------------- local CI cluster
def free_port() -> int:
    """An OS-assigned free TCP port on localhost (for the coordinator)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local_cluster(
    harness_path: str,
    spec: dict,
    *,
    n_processes: int,
    devices_per_process: int,
    timeout: float = 900.0,
    env: dict | None = None,
):
    """Run `harness_path` as an N-process gloo cluster on this machine.

    Each child gets `spec` plus the cluster coordinates
    (coordinator/num_processes/process_id) as its argv[1] JSON, and an
    environment pinned to the CPU platform with `devices_per_process`
    simulated host devices (replacing any inherited device-count flag — the
    harness itself must not touch jax before calling `init_distributed`).
    The cluster is a CPU gloo rehearsal by design: its children never
    compete with the parent for an accelerator.  Returns the last
    stdout line of process 0 parsed as JSON; raises with the children's
    stderr on any nonzero exit.
    """
    from repro.core.collectives import host_device_count_env

    coordinator = f"127.0.0.1:{free_port()}"
    child_env = host_device_count_env(devices_per_process, env)
    procs = []
    for pid in range(n_processes):
        child_spec = dict(
            spec,
            coordinator=coordinator,
            num_processes=n_processes,
            process_id=pid,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, harness_path, json.dumps(child_spec)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=child_env,
            )
        )
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=timeout))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    failures = [
        f"process {i} exit {p.returncode}:\n{outs[i][1][-4000:]}"
        for i, p in enumerate(procs)
        if p.returncode != 0
    ]
    if failures:
        raise RuntimeError(
            f"local cluster ({n_processes}x{devices_per_process}) failed:\n"
            + "\n".join(failures)
        )
    return json.loads(outs[0][0].strip().splitlines()[-1])
