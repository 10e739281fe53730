"""Trace-time vs runtime: unrolled vs rolled-group kernel bodies.

The cold-start cost of the engine is dominated by TRACING, not XLA/Mosaic
compiling: profiling the e2e flood showed ~15 s of jaxpr tracing per launch
shape (the unrolled 12-round Blake2b body is ~4.7k jnp calls, and the
``group`` unrolling duplicates it 8x per early-exit branch), while the
Mosaic compile itself is ~2 s. Measured on CPU (tracing is host-side):

    unrolled-group trace: 8.5 s    rolled-group trace: 1.5 s   (5.7x)

Rolling the 12 ROUNDS is a non-starter on TPU (measured 324 MH/s vs
1025 MH/s — Mosaic cannot software-pipeline through the fori_loop+switch),
but rolling only the GROUP loop keeps the full unrolled compress body as
the loop payload; whether Mosaic still pipelines it is the open question
this benchmark answers on real hardware:

    python benchmarks/trace_cost.py            # trace times (any host)
    python benchmarks/trace_cost.py --runtime  # + on-chip throughput A/B

Adopt the rolled group in ops/pallas_kernel.py::_search_core only if the
on-chip H/s stays within a few percent of the unrolled body — the warmup
window (the cold-start compile of every launch shape) then shrinks ~5x.
"""

from __future__ import annotations

import os

# Compile cost is a MEASURED OUTPUT here (compile_s below), so this bench
# must see real Mosaic compiles, not persistent-cache loads.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import _bootstrap  # noqa: F401,E402  (repo root on sys.path)

import argparse
import json
import time

import numpy as np


def rolledgroup_core(get_param, sublanes, iters, unroll, block_start=None, group=1):
    """ops/pallas_kernel._search_core with the group loop as a fori_loop.

    Deliberately a local variant, not a flag on _search_core: it is the
    EXPERIMENT this benchmark exists to judge — promote it into
    pallas_kernel only if the on-chip A/B says the throughput holds.
    Guards mirror _search_core's so a bad geometry fails identically.
    """
    import jax.numpy as jnp
    from jax import lax

    from tpu_dpow.ops import blake2b
    from tpu_dpow.ops import pallas_kernel as pk

    tile = sublanes * 128
    if tile * iters >= 1 << 31:
        raise ValueError("launch window must stay below 2^31 nonces")
    if iters % group != 0:
        raise ValueError("iters must be a multiple of group")
    lane = (
        lax.broadcasted_iota(jnp.uint32, (sublanes, 128), 0) * np.uint32(128)
        + lax.broadcasted_iota(jnp.uint32, (sublanes, 128), 1)
    )
    if block_start is not None:
        lane = lane + block_start
    msg = [get_param(i) for i in range(8)]
    diff = (get_param(pk.DIFF_LO), get_param(pk.DIFF_HI))
    base_lo = get_param(pk.BASE_LO)
    base_hi = get_param(pk.BASE_HI)

    def tile_best(k):
        offset = lane + (k * np.int32(tile)).astype(jnp.uint32)
        lo = base_lo + offset
        carry = (lo < base_lo).astype(jnp.uint32)
        hi = base_hi + carry
        ok = blake2b.pow_meets_difficulty((lo, hi), msg, diff, unroll=unroll)
        return jnp.min(jnp.where(ok, offset.astype(jnp.int32), pk._NOT_FOUND_I32))

    def scan_block(k, best):
        def compute(_):
            return lax.fori_loop(
                0, group,
                lambda j, b: jnp.minimum(b, tile_best(k * group + j)),
                pk._NOT_FOUND_I32,
            )
        return lax.cond(best == pk._NOT_FOUND_I32, compute, lambda _: best, None)

    best = lax.fori_loop(0, iters // group, scan_block, pk._NOT_FOUND_I32)
    return jnp.where(best == pk._NOT_FOUND_I32, pk.SENTINEL, best.astype(jnp.uint32))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runtime", action="store_true",
                    help="also A/B throughput on the real device")
    ap.add_argument("--reps", type=int, default=8)
    args = ap.parse_args()
    if args.reps < 1:
        ap.error("--reps must be >= 1")

    import jax

    from tpu_dpow.ops import pallas_kernel as pk
    from tpu_dpow.ops import search

    s, i, nb, g = 32, 1024, 8, 8
    params = np.stack([search.pack_params(bytes(range(32)), (1 << 64) - 1, 7 << 40)])
    unrolled_core = pk._search_core

    for label, core in (("unrolled-group", unrolled_core),
                        ("rolled-group", rolledgroup_core)):
        pk._search_core = core
        t0 = time.perf_counter()
        jax.make_jaxpr(
            lambda p: pk.pallas_search_chunk_batch.__wrapped__(
                p, pk.row_count(1, 1), pk.window_count(nb, nb), sublanes=s,
                iters=i, nblocks=nb, group=g, unroll=True)
        )(params)
        print(json.dumps({"bench": "kernel_trace_time", "mode": label,
                          "trace_s": round(time.perf_counter() - t0, 2)}))
    pk._search_core = unrolled_core

    if not args.runtime:
        return
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(json.dumps({"bench": "kernel_runtime_ab", "skipped": "no accelerator"}))
        return
    pj = jax.device_put(params, dev)
    chunk = s * 128 * i * nb
    for label, core in (("unrolled-group", unrolled_core),
                        ("rolled-group", rolledgroup_core)):
        pk._search_core = core
        pk.pallas_search_chunk_batch.clear_cache()

        def launch():
            return pk.pallas_search_chunk_batch(
                pj, pk.row_count(1, 1), pk.window_count(nb, nb), sublanes=s,
                iters=i, nblocks=nb, group=g)

        t0 = time.perf_counter()
        np.asarray(launch())
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = launch()
        np.asarray(out)
        dt = time.perf_counter() - t0
        print(json.dumps({"bench": "kernel_runtime_ab", "mode": label,
                          "compile_s": round(compile_s, 1),
                          "hs": round(args.reps * chunk / dt, 1)}))
    pk._search_core = unrolled_core


if __name__ == "__main__":
    main()
