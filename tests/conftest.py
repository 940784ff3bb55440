"""Test configuration: force an 8-device virtual CPU mesh before JAX backends initialize.

Tests exercise the multi-chip sharding path the same way the reference exercises
"multi-node" behavior inside a single Docker container (build.sbt:48-77): by faking the
topology — here with XLA's host-platform device-count flag instead of Docker.

Tests are CPU by design: they pin exactness and control flow, never device speed, so
the platform is forced here whatever the machine holds (env var before import, config
after — backends are still uninitialized at conftest time). The chip is exercised by
``chip_smoke.py``, not by this suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

REFERENCE_CORPUS = "/root/reference/de_wikipedia_articles_country_capitals.txt"


@pytest.fixture(scope="session")
def toy_corpus_path():
    if not os.path.exists(REFERENCE_CORPUS):
        pytest.skip("reference toy corpus not available")
    return REFERENCE_CORPUS


@pytest.fixture(scope="module", autouse=True)
def _span_recorder_starts_each_file_as_a_fresh_process_has_it():
    """Off and empty. The recorder is process-wide and a telemetry-on trainer
    leaves it armed until the next trainer is built, so under xdist a file's
    first tests could record into the ring of whichever file the scheduler
    ran before it on the same worker: a test that reads the ring then counted
    calls it had not made (PR 56: ``tests/benchmark/test_analogy_reference.py::
    test_the_call_is_recorded_span_by_span``, one whole run in three)."""
    from glint_word2vec_tpu.obs.spans import default_tracer

    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
