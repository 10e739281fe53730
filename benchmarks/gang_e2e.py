"""Gang-mode end-to-end benchmark on the virtual 8-device mesh, with criteria.

VERDICT r4 item 6: engine-level gang tests exist (tests/test_backend.py) and
multichip.py --sweep measures the machinery, but nothing GRADED pinned the
full HTTP -> server -> broker -> client -> ganged-engine path at gang size
n > 1 — the flagship v5e-8 configuration. This closes that: a gang
regression now fails a summarizer criterion, not just a unit test.

What runs (always on the virtual CPU mesh — this step is graded every
capture, and it must not touch the TPU):

  1. A full in-process stack whose worker backend is the REAL ganged engine
     (``mesh_devices=8``: shard_map launches over an 8-device mesh, pmin
     winner election, replicated params — tpu_dpow/backend/jax_backend.py).
  2. ``--n`` sequential service POSTs + one ``--burst``-wide concurrent
     burst through HTTP; every work value validated with nanocrypto.
  3. The same request schedule against the PLAIN (ungang) backend, same
     stack config, for the e2e machinery A/B.

Criteria (graded by summarize_capture.py):
  * gang engaged for real: backend.mesh is not None and spans 8 devices,
    ganged window == 8x the per-shard window;
  * zero errors, every response validates at the requested difficulty;
  * ganged sequential p50 within ``--p50-bound-ms`` (default 500 ms: ~7x
    the 67 ms first measurement — virtual-CPU collectives dominate — so
    the bound only needs to catch order-of-magnitude breaks);
  * e2e machinery delta (ganged p50 - plain p50) within
    ``--machinery-bound-ms`` (default 400 ms vs 58 ms first measured).

Usage: python benchmarks/gang_e2e.py [--n 12] [--burst 6]
"""

from __future__ import annotations

import os
import sys

# Pin to the virtual CPU mesh BEFORE jax (or _bootstrap) can import it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import _bootstrap  # noqa: E402,F401  (repo root on sys.path)

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import aiohttp  # noqa: E402
import numpy as np  # noqa: E402

RNG = np.random.default_rng(0x6A46)
GANG = 8
# A CPU threshold: the stack, not the scan, is what this bench grades.
EASY = 0xFF00000000000000


async def drive(stack, n: int, burst: int) -> dict:
    from tpu_dpow.utils import nanocrypto as nc

    url = f"http://127.0.0.1:{stack.ports['service']}/service/"
    times: list = []  # sequential requests only: clean p50, no queue skew
    ok = 0
    errors = 0

    async def one(session, record, graded=True):
        nonlocal ok, errors
        h = RNG.bytes(32).hex().upper()
        body = {"user": "bench", "api_key": "bench", "hash": h, "timeout": 30}
        t0 = time.perf_counter()
        try:
            async with session.post(url, json=body) as resp:
                data = await resp.json()
            dt = time.perf_counter() - t0
            nc.validate_work(h, data["work"], stack.base_difficulty)
        except Exception:
            # Transport resets, non-JSON 500s, missing/invalid work — all
            # one graded error; a crash here must not kill the run before
            # the result JSON prints (the summarizer grades crashes FAIL,
            # but a counted error carries more diagnostic signal).
            if graded:
                errors += 1
            return
        if graded:
            ok += 1
        if record:
            times.append(dt)

    async with aiohttp.ClientSession() as session:
        # Steady-state: shapes warmed; neither its success nor its failure
        # is part of the graded counts.
        await one(session, record=False, graded=False)
        for _ in range(n):
            await one(session, record=True)
        t0 = time.perf_counter()
        await asyncio.gather(*(one(session, record=False)
                               for _ in range(burst)))
        burst_wall = time.perf_counter() - t0

    ms = np.asarray(sorted(times)) * 1e3
    return {
        "ok": ok,
        "errors": errors,
        "p50_ms": round(float(np.percentile(ms, 50)), 2) if len(times) else None,
        "p95_ms": round(float(np.percentile(ms, 95)), 2) if len(times) else None,
        "burst_wall_ms": round(burst_wall * 1e3, 1),
    }


async def run(n: int, burst: int, p50_bound: float, machinery_bound: float) -> None:
    import jax

    from tpu_dpow.backend.jax_backend import JaxWorkBackend

    assert len(jax.devices()) >= GANG, (
        f"virtual mesh did not materialize: {len(jax.devices())} devices")

    def ganged():
        return JaxWorkBackend(kernel="xla", sublanes=8, iters=8,
                              max_batch=32, mesh_devices=GANG)

    def plain():
        return JaxWorkBackend(kernel="xla", sublanes=8, iters=8, max_batch=32)

    stack = await _bootstrap.start_full_stack(
        backend_factory=ganged, base_difficulty=EASY)
    b = stack.backend
    gang_engaged = (
        b.mesh is not None
        and b.mesh.devices.size == GANG
        and b.chunk == GANG * b.chunk_per_shard
    )
    ganged_res = await drive(stack, n, burst)
    await stack.client.close()
    await stack.runner.stop()

    stack = await _bootstrap.start_full_stack(  # plain A/B, same config
        backend_factory=plain, base_difficulty=EASY)
    plain_res = await drive(stack, n, burst)
    await stack.client.close()
    await stack.runner.stop()

    machinery_ms = (
        round(ganged_res["p50_ms"] - plain_res["p50_ms"], 2)
        if ganged_res["p50_ms"] is not None and plain_res["p50_ms"] is not None
        else None
    )
    result = {
        "bench": "gang_e2e",
        "platform": "cpu-virtual-mesh",
        "gang": GANG,
        "n": n,
        "burst": burst,
        "gang_engaged": bool(gang_engaged),
        **{f"ganged_{k}": v for k, v in ganged_res.items()},
        **{f"plain_{k}": v for k, v in plain_res.items()},
        "machinery_added_p50_ms": machinery_ms,
        "p50_bound_ms": p50_bound,
        "machinery_bound_ms": machinery_bound,
    }
    print(json.dumps(result))
    failed = (
        not gang_engaged
        or ganged_res["errors"] or plain_res["errors"]
        or ganged_res["ok"] != n + burst or plain_res["ok"] != n + burst
        or ganged_res["p50_ms"] is None
        or ganged_res["p50_ms"] > p50_bound
        or machinery_ms is None
        or machinery_ms > machinery_bound
    )
    if failed:
        raise SystemExit(1)


def main() -> None:
    p = argparse.ArgumentParser("ganged-engine e2e bench (virtual 8-mesh)")
    p.add_argument("--n", type=int, default=12)
    p.add_argument("--burst", type=int, default=6)
    p.add_argument("--p50-bound-ms", type=float, default=500.0)
    p.add_argument("--machinery-bound-ms", type=float, default=400.0)
    args = p.parse_args()
    asyncio.run(run(args.n, args.burst, args.p50_bound_ms,
                    args.machinery_bound_ms))


if __name__ == "__main__":
    main()
