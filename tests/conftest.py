"""Test harness: 8 virtual CPU devices stand in for a TPU slice.

The reference tests everything as "multi-process on one box" under
``mpirun -np 2`` (reference .travis.yml:102-111); the TPU analog is a
multi-chip host simulated with ``--xla_force_host_platform_device_count=8``
(SURVEY §4).  Collective correctness is asserted against local math exactly
as the reference does (test_tensorflow.py:56-247).
"""

import os

# Set before anything imports jax: the platform and the virtual device count
# are read once, at backend initialisation.  Child interpreters the
# multi-process tests spawn inherit both.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    # Keep initialized across tests (init is idempotent); shutdown at exit.


@pytest.fixture(scope="session", autouse=True)
def _teardown():
    yield
    import horovod_tpu as hvd

    hvd.shutdown()
