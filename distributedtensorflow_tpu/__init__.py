"""distributedtensorflow_tpu — a TPU-native distributed-training framework.

A from-scratch JAX/XLA re-design of the capabilities of the reference repo
(SvenGronauer/distributedTensorFlow, a driver over ``tf.distribute`` — see
SURVEY.md): the strategy zoo becomes one SPMD engine over a
``jax.sharding.Mesh``, NCCL/gRPC collectives become XLA collectives over
ICI/DCN, and tf.data keeps feeding host infeed — extended with tensor,
pipeline, sequence (ring attention / Ulysses) and expert parallelism.
"""

__version__ = "0.1.0"

from . import obs  # noqa: F401  (telemetry first: everything writes to it)
from . import parallel  # noqa: F401
from . import strategies  # noqa: F401
