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

# Worker-seconds of every file past 40 (the driver's junit of PR 61's tree,
# to the nearest ten).  Under ``--dist loadfile`` a file is one worker's
# chain, and xdist hands the files out by their count of cases, most first:
# a file of four cases that start a process each went out last and was the
# run's tail, a sixth of its wall with five workers idle.  Handed out longest
# first the six chains end together.  A file that is new or has changed by
# more than a ten is entered from ``--junitxml`` (ROADMAP.md, Design 16); one
# that is not here goes out after these, by its count of cases.
FILE_SECONDS = {
    "test_prefill_rows": 230, "test_mamba2": 230, "test_kda": 230,
    "test_flash_attention": 220, "test_examples": 210,
    "test_examples_frameworks": 210, "test_tpu_structure_served": 205,
    "test_bench_hybrid": 190, "test_latent_attention": 180,
    "test_tpu_structure": 180, "test_examples_launched": 170,
    "test_bench_moe": 160, "test_cohere2_moe": 160, "test_cca_served": 160,
    "test_moe_held_walk": 160, "test_cca": 150, "test_moe_rows": 150,
    "test_models": 130, "test_block_diffusion": 120,
    "test_tpu_structure_trained": 120, "test_bench_axk1": 110,
    "test_multiprocess": 100, "test_grouped_matmul": 100,
    "test_eva_attention": 100, "test_mamba2_model": 100,
    "test_bench_zaya": 90, "test_flash_length": 90, "test_bench_ling": 80,
    "test_elastic_reconfig": 80, "test_moe_dropless": 80,
    "test_serving_spans": 70, "test_serving": 70, "test_wire_bytes": 70,
    "test_context_plan": 60, "test_serving_prefill_length": 60,
    "test_elastic": 60, "test_bench_evabyte": 60,
    "test_hyper_connections": 60, "test_parallel": 50, "test_setup_spans": 50,
    "test_bench_xing": 50, "test_failure_detection": 50, "test_zigzag": 40,
    "test_kv_rows_pool": 40,
}


def pytest_configure(config):
    # the order below is the order the files go out in (xdist's own option;
    # absent where the plugin is not loaded)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    cases: dict[str, int] = {}
    for item in items:
        cases[item.path.stem] = cases.get(item.path.stem, 0) + 1
    # (a case of an unlisted file counts a second, and the file under 40)
    items.sort(key=lambda item: -FILE_SECONDS.get(
        item.path.stem, min(cases[item.path.stem], 39)))



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
