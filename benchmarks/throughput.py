"""Geometry sweep: Blake2b scan throughput vs Pallas launch shape.

BASELINE.json north star: >= 1e9 H/s/chip on v5e. The launch geometry
(sublanes x 128 lanes x iters) trades VPU occupancy against early-exit and
cancel latency; this sweep finds the knee. Also times the native C++ engine
(backend=native) for a host-CPU reference point.

Usage: python benchmarks/throughput.py [--reps 8] [--native]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import time

import numpy as np


def sweep_jax(reps: int) -> None:
    import jax

    from tpu_dpow.ops import pallas_kernel, search

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    params = np.stack([search.pack_params(bytes(range(32)), (1 << 64) - 1, 0)])
    pj = jax.device_put(params, dev)

    if on_tpu:
        # (sublanes, iters, nblocks, group): single-window tile scan first,
        # then the multi-window persistent-kernel shapes that amortize the
        # ~8 ms dispatch floor — the bench.py/backend defaults come from
        # this grid, so re-running it re-derives them.
        geometries = [
            (s, i, 1, 1) for s in (8, 16, 32, 64, 128) for i in (64, 256, 1024)
        ] + [
            (32, 1024, nb, g) for nb in (8, 32, 64) for g in (1, 8)
        ] + [
            (64, 1024, 16, 8), (16, 1024, 128, 8),
        ] + [
            # r4 additions around the r3 champion (32,1024,64,8) @1.119 GH/s:
            # rarer early-exit checks (the found-flag cond costs scalar-
            # pipeline time every `group` tiles) and longer-iter shapes that
            # halve the grid-step count at the same window.
            (32, 1024, 64, 16), (32, 1024, 64, 32),
            (32, 2048, 32, 8), (32, 2048, 32, 16),
            (64, 512, 64, 8), (64, 2048, 16, 8),
        ]
    else:
        geometries = [(8, 8, 1, 1)]  # CPU smoke shape

    best = None
    for sublanes, iters, nblocks, group in geometries:
        chunk = sublanes * 128 * iters * nblocks

        def launch():
            if on_tpu:
                return pallas_kernel.pallas_search_chunk_batch(
                    pj, pallas_kernel.row_count(pj.shape[0], pj.shape[0]),
                    pallas_kernel.window_count(nblocks, nblocks),
                    sublanes=sublanes, iters=iters, nblocks=nblocks,
                    group=group,
                )
            return search.search_chunk_batch(pj, chunk_size=chunk)

        np.asarray(launch())  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = launch()
        np.asarray(out)
        dt = time.perf_counter() - t0
        rec = {
            "bench": "throughput_geometry",
            "platform": dev.platform,
            "sublanes": sublanes,
            "iters": iters,
            "nblocks": nblocks,
            "group": group,
            "chunk": chunk,
            "hs": round(reps * chunk / dt, 1),
            "launch_ms": round(dt / reps * 1e3, 3),
        }
        print(json.dumps(rec), flush=True)
        if best is None or rec["hs"] > best["hs"]:
            best = rec
    # Final summary line: evidence-capture steps record the LAST JSON line,
    # so the champion shape lands in BENCH_latency.json while the full grid
    # stays in the step's stdout/watch log.
    print(json.dumps({**best, "bench": "throughput_sweep_best"}))


def sweep_native(reps: int) -> None:
    import ctypes
    import os

    from tpu_dpow.backend import native_backend as nb

    lib = nb.load_library()
    h = bytes(range(32))
    nonce_out = ctypes.c_uint64(0)
    done = ctypes.c_uint64(0)
    count = 1 << 22
    for threads in {1, max(1, (os.cpu_count() or 1) // 2), os.cpu_count() or 1}:
        lib.bw_search_range(  # warm the thread pool path
            h, (1 << 64) - 1, 0, 1 << 16, threads, None,
            ctypes.byref(nonce_out), ctypes.byref(done),
        )
        t0 = time.perf_counter()
        for r in range(reps):
            lib.bw_search_range(
                h, (1 << 64) - 1, r * count, count, threads, None,
                ctypes.byref(nonce_out), ctypes.byref(done),
            )
        dt = time.perf_counter() - t0
        print(
            json.dumps(
                {
                    "bench": "throughput_native",
                    "threads": threads,
                    "hs": round(reps * count / dt, 1),
                }
            )
        )


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--native", action="store_true", help="also time the C++ engine")
    args = p.parse_args()
    sweep_jax(args.reps)
    if args.native:
        sweep_native(args.reps)
