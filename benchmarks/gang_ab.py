"""One-chip A/B: the ganged (shard_map/pmin) path vs the plain path.

The 8-chip <50 ms projection (BASELINE.md) needs the ganged machinery's
cost measured on real hardware, not assumed. A mesh of ONE device runs the
exact shard_map + replicate_params + pmin-election code of the flagship
gang (parallel/mesh_search.py) with zero actual ICI traffic — so

    p50(mesh_devices=1) - p50(plain)

prices the gang's dispatch-side machinery at real geometry on the real
chip. Combined with benchmarks/multichip.py --sweep (how the machinery
SCALES with gang size, measured on a virtual mesh), the projection's
"~2 ms ICI/dispatch" assumption becomes two measured components plus only
the physical ICI hop as the remaining estimate.

Both sides run the SAME engine, difficulty, and geometry; kernel launches
differ only in the mesh. Uses direct kernel-path launches (not the full
backend) so the A/B isolates the launch machinery from engine scheduling.

Second measurement (VERDICT r4 item 7): the RESIDENT-LOOP window sweep.
The 8-chip projection's last soft term was the per-window cost of
sharded_search_run's device-resident while_loop (loop bookkeeping +
per-window pmin), measured only on virtual CPU (4.6 ms/window — collective-
dominated, a host artifact). Here the SAME sharded_search_run runs on the
real chip at gang=1 across max_steps 1/2/4/8/16 with an unreachable
difficulty and a scan-negligible window (~0.24 ms of scan at flagship
rate), so

    (t[16] - t[1]) / 15  =  marginal ms per extra resident window

is a REAL-SILICON number for everything in the loop except the physical
ICI hop of the per-window pmin — which is the one remaining (physical,
~10-30 us on v5e) estimate in the projection.

Usage: python benchmarks/gang_ab.py [--reps 20]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import time

import numpy as np

from tpu_dpow.ops import pallas_kernel, search

SUBLANES, ITERS, NBLOCKS, GROUP = 32, 1024, 8, 8


def run(reps: int) -> None:
    import jax

    from tpu_dpow.parallel import (
        make_mesh,
        replicate_params,
        sharded_search_chunk_batch,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    sublanes, iters, nblocks, group = (
        (SUBLANES, ITERS, NBLOCKS, GROUP) if on_tpu else (8, 8, 1, 1)
    )
    kernel = "pallas" if on_tpu else "xla"
    chunk = sublanes * 128 * iters * nblocks
    windows = pallas_kernel.window_count(nblocks, nblocks)
    rows = np.stack([search.pack_params(bytes(32), (1 << 64) - 1, 0)])

    # plain path: single-device kernel launch
    pj = jax.device_put(rows, dev)

    def plain():
        if kernel == "pallas":
            return pallas_kernel.pallas_search_chunk_batch(
                pj, windows, sublanes=sublanes, iters=iters, nblocks=nblocks,
                group=group,
            )
        return search.search_chunk_batch(pj, chunk_size=chunk)

    # ganged path, gang size ONE: same shard_map/pmin code, no ICI traffic
    mesh = make_mesh([dev])
    params = replicate_params(rows, mesh)

    def ganged():
        return sharded_search_chunk_batch(
            params, windows, mesh=mesh, chunk_per_shard=chunk, kernel=kernel,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
        )

    def time_p50_ms(fn) -> float:
        np.asarray(fn())  # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fn())
            times.append(time.perf_counter() - t0)
        return float(np.percentile(times, 50)) * 1e3

    p50 = {"plain": time_p50_ms(plain), "ganged_1": time_p50_ms(ganged)}

    # Resident-loop window sweep at gang=1 (projection item: per-window
    # loop cost on real silicon). Scan-negligible window; unreachable
    # difficulty holds the while_loop at exactly max_steps windows.
    from tpu_dpow.parallel import sharded_search_run

    if on_tpu:
        w_sublanes, w_iters = 32, 64  # 262k nonces ≈ 0.24 ms of scan
    else:
        w_sublanes, w_iters = 8, 8
    w_chunk = w_sublanes * 128 * w_iters
    window_p50 = {}
    for steps in (1, 2, 4, 8, 16):
        def resident(steps=steps):
            lo, _ = sharded_search_run(
                params, mesh=mesh, chunk_per_shard=w_chunk, kernel=kernel,
                sublanes=w_sublanes, iters=w_iters, nblocks=1, group=1,
                max_steps=steps,
            )
            return lo

        window_p50[steps] = round(time_p50_ms(resident), 3)

    print(json.dumps({
        "bench": "gang_machinery_ab",
        "platform": dev.platform,
        "reps": reps,
        "chunk": chunk,
        "plain_p50_ms": round(p50["plain"], 3),
        "ganged1_p50_ms": round(p50["ganged_1"], 3),
        "machinery_ms": round(p50["ganged_1"] - p50["plain"], 3),
        "resident_window_chunk": w_chunk,
        "resident_window_p50_ms": window_p50,
        "resident_marginal_ms_per_window": round(
            (window_p50[16] - window_p50[1]) / 15, 4),
    }))


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--reps", type=int, default=20)
    args = p.parse_args()
    run(args.reps)
