"""Real-chip test env — the inverse of tests/conftest.py.

tests/ pins JAX_PLATFORMS=cpu for fast, deterministic CPU runs; everything
here runs on the actual TPU to guard the Mosaic lowering paths those tests
cannot see (interpret mode is not Mosaic — a lowering bug in e.g. the int32
min-reduction workaround or the SMEM multi-window found-flag would pass
every CPU test and still ship invalid work).

This process is the one that holds the chip: no test here starts another
process that needs it. No TPU is a failure, not a skip. Run on the chip:
``python -m pytest tests_tpu -q``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_dpow.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


def pytest_collection_modifyitems(config, items):
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise pytest.UsageError(f"tests_tpu needs a TPU; JAX found {platform!r}")
