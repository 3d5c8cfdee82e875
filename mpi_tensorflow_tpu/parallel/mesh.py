"""Device mesh and process bootstrap — the communication-backend layer.

Replaces the reference's MPI world setup (``MPI.COMM_WORLD`` +
``Get_rank``/``Get_size``, mpipy.py:208-210) with the TPU-native equivalent:
``jax.distributed.initialize()`` for multi-host process setup over DCN, and a
``jax.sharding.Mesh`` whose named axes carry the parallelism strategy.  On a
mesh, collectives ride ICI and are inserted by XLA — there is no explicit
rank-indexed message passing to write.

Default topology is a 1-D ``('data',)`` mesh over all devices (pure DP, the
reference's only strategy).  Multi-axis meshes (``data`` x ``model`` x
``seq``) drive TP/SP for the transformer families.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (the ``mpiexec`` equivalent).

    Joins a coordinator only for a launch that NAMES one: an explicit
    ``coordinator_address`` / ``JAX_COORDINATOR_ADDRESS``, or a TPU pod
    whose ``TPU_WORKER_HOSTNAMES`` lists more than one host (arguments
    are then auto-detected from the environment).  A single-host machine
    — one entry there, whatever it is called (the sealed one-chip and
    four-chip machines set ``localhost``) — never calls
    ``jax.distributed.initialize`` and so can never wait on a
    coordinator that will not answer.  No-op when already initialized.

    Must NOT touch ``jax.process_count()`` before deciding: that
    initializes the XLA backend, after which
    ``jax.distributed.initialize`` is a hard error.
    """
    if jax.distributed.is_initialized():
        return
    explicit = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    if explicit or len(hosts) > 1:
        try:
            jax.distributed.initialize(coordinator_address, num_processes,
                                       process_id)
        except (RuntimeError, ValueError) as e:
            # A pod that was configured for multi-host but failed to
            # initialize must NOT silently degrade to single-process
            # training (it would train on 1/N of the data at 1/N scale
            # with no error) — the mpiexec equivalent of a rank failing
            # to join COMM_WORLD is a launch failure.
            raise RuntimeError(
                "distributed initialization failed for an explicitly "
                f"configured multi-host launch (coordinator="
                f"{explicit or 'auto-detected env'}): {e}") from e


def make_mesh(shape: Optional[Mapping[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build the device mesh.

    ``shape`` maps axis name -> size, e.g. ``{"data": 4, "model": 2}``.
    ``None`` puts every device on one ``data`` axis.  An axis sized -1 absorbs
    the remaining devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = {"data": len(devices)}
    names = tuple(shape.keys())
    sizes = list(shape.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, names)


def data_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("data", 1)


def process_index() -> int:
    """The ``comm.Get_rank()`` analogue, but per host (mpipy.py:209)."""
    return jax.process_index()


def process_count() -> int:
    """The ``comm.Get_size()`` analogue, but per host (mpipy.py:210)."""
    return jax.process_count()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Leading-dim sharding over the data axis — how input batches live."""
    return NamedSharding(mesh, PartitionSpec(axis))
