"""Running the harness on the CPU, for the benchmark's own tests.

The cell is the real harness with the real server, worker and load
generator; only the threshold is low and the engine's window small, so that
the CPU's XLA path answers in milliseconds. Nothing here is a measurement.
"""

from __future__ import annotations

import asyncio
import contextlib
import os

import catalog

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_cell(traffic: str = "tiny-backlog") -> catalog.Cell:
    config = catalog.load_json(os.path.join(DATA, "cpu-tiny.json"))
    bench = catalog.load_json(os.path.join(DATA, "BENCHMARK.tiny.json"))
    return catalog.Cell(
        name=f"cpu-{traffic}", config=config,
        traffic=catalog.load_json(os.path.join(DATA, f"{traffic}.json")),
        chips=1,
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


@contextlib.contextmanager
def small_engine():
    """The engine at a CPU-sized window (8 sublanes x 128 x 64 iters, one
    block): the same code path, a smaller launch."""
    from tpu_dpow.backend import jax_backend

    orig = jax_backend.JaxWorkBackend.__init__

    def init(self, **kw):
        kw.update(sublanes=8, iters=64, nblocks=1, group=8)
        orig(self, **kw)

    jax_backend.JaxWorkBackend.__init__ = init
    try:
        yield
    finally:
        jax_backend.JaxWorkBackend.__init__ = orig


def run_cell(cell, *, seed=7, seconds=3.0, trace_dir=None, **session_kw):
    """One run of the harness on the CPU: (WindowData, result dict)."""
    import harness
    import run

    run.prepare()
    import jax

    # CPU programs stay out of the checkout's cache, which the chip uses.
    jax.config.update("jax_enable_compilation_cache", False)
    session = harness.Session(cell, require_chip=False, **session_kw)
    session.check_chip()
    with small_engine():
        w = asyncio.run(run.measure(session, seed, seconds, trace_dir))
    if trace_dir is not None:
        run.reduce_trace(w, trace_dir, keep=False)
    return w, run.result(w, cell, trace_dir is not None)
