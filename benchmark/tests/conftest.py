"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

Rehearsals on the CPU at tiny sizes: they prove control flow, counts and the
comparison with the reference, never a time.  Products are full float32
(``highest``), as the repo's own CPU tests have them.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MDT_PALLAS_INTERPRET", "1")
# four CPU devices, for the rehearsal of the four-chip kind of cell
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

DATA = os.path.join(CHECKOUT, "benchmark", "tests", "data")


@pytest.fixture()
def tiny_cell():
    """run_cell bound to the tiny manifest (CPU, no device metric printed)."""
    from benchmark import run

    def go(name, seed=7, seconds=2.5, trace=False):
        return run.run_cell(name, seed, seconds, trace, require_tpu=False,
                            manifest=os.path.join(DATA, "BENCHMARK.json"),
                            data_root=DATA)

    cwd = os.getcwd()
    yield go
    os.chdir(cwd)
