"""Mixed-load fairness: a hard request under an easy-precache flood.

The engine groups jobs into difficulty rungs served round-robin
(tpu_dpow/backend/jax_backend.py _next_rung), so a steady stream of
steps-1 precache work must not starve — nor be starved by — one wide 8x
on-demand request. This measures exactly that adversarial mix: a hard
request timed through a sustained base-difficulty flood against its OWN
solo baseline. The gap is the scheduling tax; round-robin + the
shared_steps_cap successor narrowing bound it near one capped launch per
hard launch (the reference's one-POST-at-a-time worker serializes the
whole queue instead, reference client/work_handler.py:98-108).

Solo and mixed trials are INTERLEAVED pair-by-pair, with an engine-drain
gate before each solo trial: round 3's block design (all solo, then all
mixed) measured the two halves in different session states — a drifting
dispatch floor made the flood look 146 ms FASTER than idle, i.e. the design
measured drift, not scheduling.

Usage: python benchmarks/fairness.py [--n 10] [--flood 8] [--multiplier 8]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import asyncio
import json
import time

import numpy as np

from tpu_dpow.backend import get_backend
from tpu_dpow.models import WorkRequest
from tpu_dpow.utils import nanocrypto as nc

RNG = np.random.default_rng(0xFA)


async def timed_hard(backend, difficulty: int) -> float:
    h = RNG.bytes(32).hex().upper()
    t0 = time.perf_counter()
    work = await backend.generate(WorkRequest(h, difficulty))
    dt = time.perf_counter() - t0
    nc.validate_work(h, work, difficulty)
    return dt


async def run(n: int, flood_width: int, multiplier: float) -> None:
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    base = nc.BASE_DIFFICULTY if on_tpu else 0xFFF0000000000000
    hard = nc.derive_work_difficulty(multiplier, base)
    backend = get_backend("jax")
    await backend.setup()
    await _bootstrap.wait_for_warmup(backend)  # steady-state, not compile queueing

    async def drain() -> None:
        # Solo trials need a genuinely idle engine: residual flood jobs
        # (and their in-flight launches) from the previous mixed trial
        # would contend with — and inflate — the solo measurement.
        deadline = time.perf_counter() + 5.0
        while backend._jobs and time.perf_counter() < deadline:
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.1)  # in-flight launches finish draining

    solo, mixed = [], []
    flood_count = 0
    for _ in range(n):
        await drain()
        solo.append(await timed_hard(backend, hard))

        stop = asyncio.Event()

        async def flooder():
            nonlocal flood_count
            while not stop.is_set():
                h = RNG.bytes(32).hex().upper()
                try:
                    work = await backend.generate(WorkRequest(h, base))
                    nc.validate_work(h, work, base)
                    flood_count += 1
                except Exception:
                    if not stop.is_set():
                        raise

        floods = [asyncio.ensure_future(flooder()) for _ in range(flood_width)]
        await asyncio.sleep(0.2)  # flood reaches steady state
        mixed.append(await timed_hard(backend, hard))
        stop.set()
        for f in floods:
            f.cancel()
        await asyncio.gather(*floods, return_exceptions=True)
    await backend.close()

    solo_ms = np.asarray(sorted(solo)) * 1e3
    mixed_ms = np.asarray(sorted(mixed)) * 1e3
    print(
        json.dumps(
            {
                "bench": "mixed_load_fairness",
                "platform": "tpu" if on_tpu else "cpu",
                "n": n,
                "flood_width": flood_width,
                "multiplier": multiplier,
                "flood_solves_during_mixed": flood_count,
                "solo_p50_ms": round(float(np.percentile(solo_ms, 50)), 2),
                "mixed_p50_ms": round(float(np.percentile(mixed_ms, 50)), 2),
                "mixed_p95_ms": round(float(np.percentile(mixed_ms, 95)), 2),
                "added_p50_ms": round(
                    float(np.percentile(mixed_ms, 50) - np.percentile(solo_ms, 50)), 2
                ),
            }
        )
    )


def main() -> None:
    p = argparse.ArgumentParser("mixed-load fairness benchmark")
    p.add_argument("--n", type=int, default=10)
    p.add_argument("--flood", type=int, default=8)
    p.add_argument("--multiplier", type=float, default=8.0)
    args = p.parse_args()
    asyncio.run(run(args.n, args.flood, args.multiplier))


if __name__ == "__main__":
    main()
