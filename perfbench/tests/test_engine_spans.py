"""The engine's profiler spans land in the host plane of a real trace: one
engine solve on the CPU, profiled as the harness profiles a window."""

import asyncio

import harness
import xplane

EASY = 0xFFF0000000000000


def test_one_profiled_solve_puts_the_dpow_spans_in_the_host_plane(tmp_path):
    import jax
    import numpy as np

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    jax.config.update("jax_enable_compilation_cache", False)
    h = np.random.default_rng(23).bytes(32).hex().upper()

    async def solve():
        b = JaxWorkBackend(kernel="xla", sublanes=8, iters=8)
        await b.setup()
        harness._start_trace(str(tmp_path))
        try:
            work = await b.generate(WorkRequest(h, EASY))
        finally:
            harness._stop_trace()
            await b.close()
        return work

    nc.validate_work(h, asyncio.run(solve()), EASY)
    pd = xplane.load(xplane.find_xplane(str(tmp_path)))
    names = {name for _thread, name, _s, _e in xplane.host_events(pd)}
    for span in ("dpow.engine.dispatch", "dpow.launch.dispatch", "dpow.launch.wait",
                 "dpow.launch.readback", "dpow.engine.apply"):
        assert span in names, (span, sorted(n for n in names if n.startswith("dpow")))
