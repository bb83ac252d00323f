"""Mesh construction: the rebuild of MPI.COMM_WORLD bring-up.

The reference gets (rank, size) from mpi4py at launch (SURVEY.md §3.1); here
the "world" is a 1-D device mesh. Multi-host bring-up is
jax.distributed.initialize over DCN (SURVEY.md §5.8 control plane) before
building the mesh over all addressable devices; single-host is just the local
devices. The solver only sees the Mesh.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

from gamesmanmpi_tpu.utils.env import env_int, env_opt, env_str

AXIS = "shards"


def make_mesh(num_shards: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over `num_shards` devices (default: all available)."""
    if devices is None:
        devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if num_shards > len(devices):
        raise ValueError(
            f"requested {num_shards} shards but only {len(devices)} devices"
        )
    return Mesh(np.array(devices[:num_shards]), (AXIS,))


def enable_cpu_collectives() -> None:
    """Turn on cross-process CPU collectives (Gloo) before backend init.

    XLA's CPU client ships a Gloo TCP collectives implementation but
    leaves it OFF by default — a multi-process CPU run without it fails
    at the first cross-process computation with "Multiprocess
    computations aren't implemented on the CPU backend", which is
    exactly why tests/test_multihost.py used to skip on this container.
    GAMESMAN_CPU_COLLECTIVES picks the implementation (gloo/mpi/none;
    default gloo); jax versions without the knob are left untouched (a
    real TPU/GPU backend never consults it).
    """
    impl = env_str("GAMESMAN_CPU_COLLECTIVES", "gloo")
    if impl == "none":
        return
    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except (AttributeError, ValueError):  # jax without the knob
        pass


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, **kwargs) -> None:
    """Multi-host process-group bring-up (DCN): jax.distributed.initialize.

    Convenience wrapper so launchers can call it unconditionally; the
    identity triple falls back to the environment
    (``GAMESMAN_COORDINATOR``, ``GAMESMAN_NUM_PROCESSES``,
    ``GAMESMAN_PROCESS_ID``) so a process launcher — tools/
    launch_multihost.py — can configure children without touching their
    argv. Must run before the first backend touch; CPU collectives
    (Gloo) are enabled here for the same reason.
    """
    if coordinator_address is None:
        coordinator_address = env_opt("GAMESMAN_COORDINATOR")
    if num_processes is None:
        num_processes = env_int("GAMESMAN_NUM_PROCESSES", 1)
    if process_id is None:
        process_id = env_int("GAMESMAN_PROCESS_ID", 0)
    enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
