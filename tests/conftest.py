import jax
import pytest

# The suite runs on the CPU, with Pallas kernels in interpret mode; the
# 512 placeholder devices are ONLY for the dry-run (see
# launch/dryrun.py). `tests/test_tpu_compile.py` compiles for a
# described chip without attaching one.
jax.config.update("jax_platforms", "cpu")

# Share compiled scan engines across processes (and with benchmarks/run.py)
# in $JAX_COMPILATION_CACHE_DIR, else experiments/xla_cache
from repro.core.sim import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


def pytest_configure(config):
    # also declared in pyproject.toml; registering here keeps the mark
    # known when pytest is invoked with an explicit -c elsewhere
    config.addinivalue_line(
        "markers", "slow: slow compile/integration tests")


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
