"""What the three files of pre-checks for a described v5e share
(``test_kernel_export.py``: the kernels alone; ``test_kernel_export_gpt2.py``:
GPT-2's training step and serving programs; ``test_kernel_export_families.py``:
the other serving families' programs): shapes with a sharding, the described
mesh, and the answer the programs get when they ask whether they are on the
chip.

Each of those files describes the topology inside its tests, never while it
is imported.  Only one process at a time may load the TPU's library unless
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set: ``tests/conftest.py`` sets it where
the environment does not, so the three files pass side by side, a worker
each.
"""

import jax
import jax.numpy as jnp
import pytest

BF16, F32 = jnp.bfloat16, jnp.float32


def sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def sum32(x):
    return jnp.sum(x.astype(F32))


def v5e_mesh(n):
    from jax.experimental import topologies

    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason means "not here"
        pytest.skip(f"this libtpu cannot describe a v5e topology: {e}")
    return build_mesh(MeshSpec(data=n), topo.devices[:n])


def as_on_the_chip(monkeypatch):
    """The programs choose their kernels by ``runtime.on_tpu()``, which
    sees this sandbox's CPU: answer for the described chip, in the test."""
    import sys

    for name, module in list(sys.modules.items()):
        if name.startswith("distributedtensorflow_tpu") \
                and hasattr(module, "on_tpu"):
            monkeypatch.setattr(module, "on_tpu", lambda: True)
