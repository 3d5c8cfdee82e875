"""mpi_tensorflow_tpu — a TPU-native data-parallel training framework.

A brand-new JAX/XLA re-design of the capabilities of
``youzhenfei1995/mpi-Tensorflow`` (an mpi4py + TensorFlow-v1 synchronous
MNIST trainer, reference ``mpipy.py``):

- ``data``      — in-repo IDX parsing, dataset pipelines, per-host sharding
                  (replaces the reference's external ``convolutional`` helpers
                  and root-0 ``MPI.Scatter``, mpipy.py:12, 236-241).
- ``parallel``  — device mesh, XLA collectives, sharding rules, ring attention
                  (replaces ``MPI.COMM_WORLD`` and mpi4py collectives,
                  mpipy.py:5, 208-210).
- ``models``    — the reference CNN (mpipy.py:33-68, 155-167) plus the
                  scale-out model families from BASELINE.json (ResNet, BERT).
- ``train``     — jit-compiled train step with in-graph gradient ``psum``,
                  host loop, evaluation, checkpointing (replaces
                  ``Cnn.run_process`` / ``bcast_parameters``, mpipy.py:76-153).
- ``ops``       — Pallas TPU kernels for hot ops.
- ``utils``     — console trace in the reference's format, timing harness.

The public surface mirrors what a user of the reference needs: build a model,
get sharded data, run the training loop, read the 50-step error trace.
"""

__version__ = "0.3.0"

from mpi_tensorflow_tpu.config import Config  # noqa: F401
