"""Test harness: force an 8-device virtual CPU platform.

This is the analogue of the reference's Spark ``local[4]`` simulated
topology (SURVEY §4.3): distributed code paths (mesh, psum_scatter,
all_gather) run on 8 virtual CPU devices without TPU hardware.
Must run before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Entry points the suite drives in-process (InferenceServer.start, the
# train/perf CLIs) turn on the persistent compile cache; the suite stays
# hermetic — it neither reads executables an earlier run left on disk
# nor writes a thousand tests' worth into the checkout.
jax.config.update("jax_enable_compilation_cache", False)

import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

_EXIT_STATUS = None


def pytest_sessionfinish(session, exitstatus):
    global _EXIT_STATUS
    _EXIT_STATUS = int(exitstatus)


def pytest_unconfigure(config):
    # A full run accumulates hundreds of jitted XLA executables whose
    # teardown (GC + backend destruction) costs ~30s at interpreter
    # exit — wall-clock the tier-1 timeout budget cannot spare, with
    # nothing worth collecting. Hard-exit with pytest's own status;
    # unconfigure runs after the terminal summary, so no output is
    # lost. BIGDL_TEST_FAST_EXIT=0 opts out (e.g. for profiling
    # teardown itself).
    if _EXIT_STATUS is not None and \
            os.environ.get("BIGDL_TEST_FAST_EXIT", "1") != "0":
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_EXIT_STATUS)


@pytest.fixture(autouse=True)
def _fresh_default_tracer():
    """Each test reads only its own spans in the process-wide ring."""
    from bigdl_tpu.telemetry import reset_default_tracer

    reset_default_tracer()
    yield


@pytest.fixture(autouse=True)
def _seed_rng():
    """Deterministic host RNG per test (reference tests fix seeds per spec)."""
    from bigdl_tpu.utils.rng import RNG

    RNG().set_seed(1)
    np.random.seed(1)
    yield

