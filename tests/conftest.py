"""Test env: force CPU with a virtual 8-device mesh BEFORE jax import.

The tests exercise the engine's code paths on the CPU backend, with 8
virtual devices so the multi-device paths (the shard_map mesh and the pmap
fan) compile and run. What only the chip can show is `chip_smoke.py`'s job,
and `tests/test_tpu_compile.py` compiles the main path's kernels for a
described v5e (SURVEY.md §4: the reference has no test suite at all — this
strategy is designed from scratch).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compile cache: XLA-on-CPU compiles dominate test wall clock on
# small hosts; cache compiled executables across pytest invocations, in
# $JAX_COMPILATION_CACHE_DIR when it is set, else <checkout>/.jax_cache.
from tpu_dpow.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache(min_compile_secs=0.5)
