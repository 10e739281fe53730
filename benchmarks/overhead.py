"""Decompose the engine's per-solve overhead at base difficulty.

This isolates where a solve's milliseconds live by timing each layer
separately on the real chip:

  1. ``null``       — tiniest possible kernel dispatch, numpy in/out: the
                      irreducible dispatch + transfer floor.
  2. ``pad``        — full production launch shape (batch, widened grid)
                      whose rows are all difficulty-0 pads: every window is
                      skipped via the found flag, so this prices the GRID
                      DRAIN (per-window scheduling with no compute) plus
                      the floor.
  3. ``drain slope`` — all-pad launches at several grid sizes: the cost per
                      SKIPPED window (found-flag short-circuit), i.e. what
                      every real solve pays for the windows behind its hit.
  4. ``kernel vs engine`` — solve-time distributions at a base-equivalent
                      difficulty, once through raw kernel launches and once
                      through the full JaxWorkBackend path. Both share the
                      same hash-bound median, so the median delta isolates
                      host/engine overhead (pack, asyncio, validation).

Usage: python benchmarks/overhead.py [--reps 10]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import asyncio
import json
import time

import numpy as np

from tpu_dpow.ops import pallas_kernel, search

# Engine production geometry (backend/jax_backend.py defaults on TPU).
SUBLANES, ITERS, NBLOCKS, GROUP = 32, 1024, 8, 8
WINDOW = SUBLANES * 128 * ITERS  # one grid window (4.19M nonces)
STEPS = 4  # the base-difficulty rung: nblocks*steps windows per launch


def _timed(fn, reps: int) -> float:
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(fn())
    return (time.perf_counter() - t0) / reps


def run(reps: int) -> None:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if not on_tpu:
        print(json.dumps({"bench": "overhead_decomposition",
                          "error": "needs the real chip"}))
        return

    out = {"bench": "overhead_decomposition", "platform": dev.platform,
           "reps": reps, "window_nonces": WINDOW,
           "launch_windows": NBLOCKS * STEPS}

    # 1. null dispatch floor
    tiny = np.stack([search.pack_params(bytes(32), 1, 0)])
    pj_tiny = jax.device_put(tiny, dev)

    def null():
        return pallas_kernel.pallas_search_chunk_batch(
            pj_tiny, pallas_kernel.row_count(1, 1),
            pallas_kernel.window_count(1, 1),
            sublanes=8, iters=8, nblocks=1, group=1,
        )

    out["null_ms"] = round(_timed(null, reps) * 1e3, 2)

    # 2+3. all-pad launches across grid sizes: drain cost per skipped window
    pads = np.stack([search.pack_params(bytes(32), 0, 0)] * 16)
    pj_pads = jax.device_put(pads, dev)
    pad_ms = {}
    for windows in (NBLOCKS, NBLOCKS * 4, NBLOCKS * 16):

        def all_pad(w=windows):
            return pallas_kernel.pallas_search_chunk_batch(
                pj_pads, pallas_kernel.row_count(16, 16),
                pallas_kernel.window_count(w, w), sublanes=SUBLANES,
                iters=ITERS, nblocks=w, group=GROUP,
            )

        pad_ms[windows] = _timed(all_pad, reps) * 1e3
        out[f"pad_batch16_{windows}win_ms"] = round(pad_ms[windows], 2)
    wmin, wmax = NBLOCKS, NBLOCKS * 16
    out["drain_us_per_window"] = round(
        (pad_ms[wmax] - pad_ms[wmin]) / (wmax - wmin) * 1e3, 1
    )

    # 4. kernel-loop vs engine solve distributions at a base-equivalent
    # difficulty (median depth ≈ 11 windows): the median delta is pure
    # host/engine overhead, the kernel median vs hash-bound is the
    # quantization + drain overshoot.
    rng = np.random.default_rng(0x0E)
    median_windows = 11
    difficulty = (1 << 64) - int(
        np.log(2) * 2**64 / (median_windows * WINDOW)
    )
    solves = max(reps, 10)

    def kernel_solve() -> float:
        h = rng.bytes(32)
        base = int(rng.integers(0, 1 << 63))
        t0 = time.perf_counter()
        while True:
            row = np.stack([search.pack_params(h, difficulty, base)])
            got = int(np.asarray(
                pallas_kernel.pallas_search_chunk_batch(
                    jax.device_put(row, dev),
                    pallas_kernel.row_count(1, 1),
                    pallas_kernel.window_count(NBLOCKS * STEPS, NBLOCKS * STEPS),
                    sublanes=SUBLANES, iters=ITERS, nblocks=NBLOCKS * STEPS,
                    group=GROUP,
                )
            )[0])
            if got != int(search.SENTINEL):
                return time.perf_counter() - t0
            base += NBLOCKS * STEPS * WINDOW

    kernel_solve()  # compile
    ktimes = [kernel_solve() for _ in range(solves)]
    out["kernel_solve_p50_ms"] = round(
        float(np.percentile(ktimes, 50)) * 1e3, 2
    )
    out["hash_bound_median_ms"] = round(
        np.log(2) * 2**64 / (2**64 - difficulty) / 1.129e9 * 1e3, 2
    )

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest

    async def engine():
        b = JaxWorkBackend(run_steps=16)
        b.record_timeline = True
        await b.setup()
        times = []
        for _ in range(solves):
            h = rng.bytes(32).hex().upper()
            t0 = time.perf_counter()
            await b.generate(WorkRequest(h, difficulty))
            times.append(time.perf_counter() - t0)
        timeline = list(b.timeline)
        await b.close()
        return times, timeline

    etimes, timeline = asyncio.run(engine())
    out["engine_solve_p50_ms"] = round(
        float(np.percentile(etimes, 50)) * 1e3, 2
    )
    out["engine_overhead_p50_ms"] = round(
        (np.percentile(etimes, 50) - np.percentile(ktimes, 50)) * 1e3, 2
    )

    # Stage decomposition of the engine path (names each overhead ms):
    #   queue_wait   — generate() → first dispatch carrying the job (engine
    #                  pass scheduling + waiting on a pipeline slot)
    #   exec_queue   — dispatch → launch thread starts (executor hop)
    #   device       — launch thread: transfer + device scan + readback
    #   apply_hop    — readback done → engine loop applies results
    launches = [t for kind, t in timeline if kind == "launch"
                and "t_apply" in t and "t_thread" in t]
    solves_t = [t for kind, t in timeline if kind == "solve"]
    if launches:
        out["stage_exec_queue_p50_ms"] = round(float(np.percentile(
            [(t["t_thread"] - t["t_dispatch"]) * 1e3 for t in launches], 50)), 2)
        out["stage_device_p50_ms"] = round(float(np.percentile(
            [(t["t_done"] - t["t_thread"]) * 1e3 for t in launches], 50)), 2)
        out["stage_apply_hop_p50_ms"] = round(float(np.percentile(
            [(t["t_apply"] - t["t_done"]) * 1e3 for t in launches], 50)), 2)
        # Head launches (nothing in flight) vs successors: prices how much
        # device time a fresh dispatch spends queued behind residue.
        head_dev = [(t["t_done"] - t["t_thread"]) * 1e3
                    for t in launches if t.get("inflight", 0) == 0]
        succ_dev = [(t["t_done"] - t["t_thread"]) * 1e3
                    for t in launches if t.get("inflight", 0) > 0]
        if head_dev:
            out["stage_device_head_p50_ms"] = round(
                float(np.percentile(head_dev, 50)), 2)
        if succ_dev:
            out["stage_device_successor_p50_ms"] = round(
                float(np.percentile(succ_dev, 50)), 2)
    if solves_t:
        out["stage_queue_wait_p50_ms"] = round(float(np.percentile(
            [t["queue_wait"] * 1e3 for t in solves_t], 50)), 2)
    print(json.dumps(out))


def main() -> None:
    p = argparse.ArgumentParser("engine overhead decomposition")
    p.add_argument("--reps", type=int, default=10)
    args = p.parse_args()
    run(args.reps)


if __name__ == "__main__":
    main()
