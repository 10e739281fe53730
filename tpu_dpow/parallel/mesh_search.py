"""Multi-chip nonce search: shard_map over a (batch, nonce) device mesh.

This is the TPU-native replacement for the reference's swarm-level
parallelism. The reference scales the 64-bit nonce search by broadcasting the
same (hash, difficulty) to every volunteer client over MQTT and letting them
race from random starting nonces, electing a winner with a Redis SETNX lock
and cancelling the losers over MQTT (reference README.md:21,
server/dpow_server.py:138,155). Inside a TPU pod none of that redundancy or
millisecond-scale messaging is needed:

  * the **nonce axis** splits each request's search window into disjoint
    per-chip sub-ranges (chip i scans [base + i*chunk, base + (i+1)*chunk)) —
    deterministic sharding instead of random-start racing;
  * winner election is a `lax.pmin` over the nonce axis — a microsecond ICI
    collective instead of the reference's MQTT result/cancel round-trip;
  * the **batch axis** spreads concurrent requests across chip groups — the
    device-level analog of the reference's request-level asyncio concurrency
    (server/dpow_server.py:44, client/work_handler.py:9-36).

Mesh shapes are free: (1, N) puts all chips on one hash (latency mode — the
<50 ms p50 target at 2^29-expected-hash difficulty needs all 8 chips of a
v5e-8 on one request, SURVEY.md §7 hard part #3), (N, 1) gives every chip its
own request stream (throughput mode), and anything between trades the two.

The per-shard compute reuses the exact single-chip scanners (ops/search.py,
ops/pallas_kernel.py), so the sharded path is bit-identical to the tested
single-chip path; only placement and the winner reduction differ. The MQTT
cancel fan-out survives solely for the *outside* swarm — intra-pod
termination is the pmin plus the host dropping the job from the next launch.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import pallas_kernel, runloop, search
from ..ops.search import BASE_LO, BASE_HI, PARAMS_LEN, SENTINEL

BATCH_AXIS = "batch"
NONCE_AXIS = "nonce"


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    batch_shards: int = 1,
) -> Mesh:
    """A (batch, nonce) mesh over the given devices.

    batch_shards=1 (default) is latency mode: the full device complement
    gangs up on each request's nonce space. batch_shards=len(devices) is
    throughput mode: one independent request stream per chip.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % batch_shards != 0:
        raise ValueError(f"{batch_shards} batch shards do not divide {n} devices")
    arr = np.asarray(devices).reshape(batch_shards, n // batch_shards)
    return Mesh(arr, (BATCH_AXIS, NONCE_AXIS))


_advance_base = search.advance_base_batch


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "chunk_per_shard", "kernel", "sublanes", "iters", "nblocks",
        "group", "interpret",
    ),
)
def sharded_search_chunk_batch(
    params_batch: jnp.ndarray,
    *,
    mesh: Mesh,
    chunk_per_shard: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """One ganged multi-chip launch: uint32[B,12] → uint32[B] global offsets.

    Each request's window of ``chunk_per_shard * mesh.shape[NONCE_AXIS]``
    nonces is scanned in parallel; the returned offset is relative to the
    request's own base (SENTINEL if the whole ganged window is dry), so the
    host loop advances bases by the *global* chunk exactly as in the
    single-chip engine.

    kernel='pallas' uses the hand-tiled TPU kernel per shard (then
    chunk_per_shard must equal sublanes*128*iters*nblocks); 'xla' uses the
    fused jnp scanner (runs on any backend — this is what the CPU-mesh tests
    and the driver's virtual-device dryrun exercise).

    ``nblocks``/``group`` select the persistent-kernel mode per shard: each
    chip scans ``nblocks`` consecutive windows in ONE dispatch with
    per-request early exit between windows (ops/pallas_kernel.py
    _kernel_blocks), so the multi-chip gang pays the ~8 ms dispatch floor
    once per ``nblocks`` windows — the same amortization the single-chip
    flagship mode uses, now per shard.
    """
    n_nonce = mesh.shape[NONCE_AXIS]
    if chunk_per_shard * n_nonce >= 1 << 31:
        # Global offsets must stay below the int32/SENTINEL range so the
        # pmin winner reduction and uint32 return contract both hold.
        raise ValueError("global chunk (chunk_per_shard * nonce shards) must be < 2^31")
    if kernel == "pallas" and chunk_per_shard != sublanes * 128 * iters * nblocks:
        raise ValueError(
            "pallas kernel: chunk_per_shard must equal sublanes*128*iters*nblocks"
        )

    def shard_fn(p_local: jnp.ndarray) -> jnp.ndarray:
        idx = lax.axis_index(NONCE_AXIS).astype(jnp.uint32)
        span = jnp.uint32(chunk_per_shard)
        p_local = _advance_base(p_local, idx * span)
        if kernel == "pallas":
            local = pallas_kernel.pallas_search_chunk_batch(
                p_local, sublanes=sublanes, iters=iters, nblocks=nblocks,
                group=group, interpret=interpret,
            )
        else:
            local = search.search_chunk_batch(p_local, chunk_size=chunk_per_shard)
        # Local offset → offset from the request's own base. SENTINEL
        # (uint32 max) stays above every reachable global offset (< 2^31),
        # so the min-election needs no special casing.
        glob = jnp.where(local == SENTINEL, SENTINEL, idx * span + local)
        return lax.pmin(glob, NONCE_AXIS)

    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(BATCH_AXIS, None),
        out_specs=P(BATCH_AXIS),
        check_vma=False,  # pmin replicates the result across NONCE_AXIS
    )(params_batch)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "chunk_per_shard", "max_steps", "kernel", "sublanes", "iters",
        "nblocks", "group", "interpret",
    ),
)
def sharded_search_run(
    params_batch: jnp.ndarray,
    active: Optional[jnp.ndarray] = None,
    *,
    mesh: Mesh,
    chunk_per_shard: int,
    max_steps: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-resident multi-step search: keep ganged chunks flowing until
    every request has a hit or max_steps windows are dry.

    Returns (nonce_lo, nonce_hi) uint32[B] pairs — the absolute winning
    64-bit nonces (all-ones where unsolved). The while_loop keeps the whole
    search on-device between host checks: one dispatch covers up to
    ``max_steps * chunk_per_shard * nonce_shards`` nonces per request, which
    is how dispatch overhead is amortised toward the <50 ms p50 target
    (SURVEY.md §7 hard part #3). max_steps bounds the launch so the host can
    still interleave cancels between dispatches.

    ``active`` (bool[B], optional) marks real rows: False rows are the
    engine's fixed-shape batch padding — without the mask their unreachable
    difficulty would hold the while_loop at ``max_steps`` every launch, even
    when all real requests solved in the first window.
    """
    n_nonce = mesh.shape[NONCE_AXIS]
    global_chunk = chunk_per_shard * n_nonce

    def launch(params: jnp.ndarray) -> jnp.ndarray:
        return sharded_search_chunk_batch(
            params, mesh=mesh, chunk_per_shard=chunk_per_shard, kernel=kernel,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
            interpret=interpret,
        )

    return runloop.run_loop_core(
        params_batch, active, launch=launch, window=global_chunk,
        max_steps=max_steps,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "chunk_per_shard", "max_steps", "poll_steps", "kernel",
        "sublanes", "iters", "nblocks", "group", "interpret",
    ),
)
def sharded_search_run_controlled(
    params_batch: jnp.ndarray,
    active: Optional[jnp.ndarray],
    slot: jnp.ndarray,
    *,
    mesh: Mesh,
    chunk_per_shard: int,
    max_steps: int,
    poll_steps: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`sharded_search_run` with a live control channel — the
    PERSISTENT mesh launch (the fan twin is
    ``parallel.fan_search.fan_search_run_controlled``).

    SPMD caveat (why the engine refuses mesh+persistent): on a REAL
    multi-device mesh every device executes this program — including the
    control poll — independently, while the host mutates the control
    block concurrently; two devices can observe a command at different
    poll blocks, diverge in while_loop trip count, and deadlock the next
    collective. Safe on a one-device mesh (the gang-machinery A/B); the
    multi-device fix is pinning the poll to one device and broadcasting
    (``io_callback(..., sharding=)``), not yet validated on real chips.

    The loop structure is identical to :func:`sharded_search_run` — the
    while_loop sits OUTSIDE the shard_map and every window's ganged launch
    re-applies each shard's ``idx * chunk_per_shard`` interleave offset to
    the current request-level base — so the control channel needs no
    per-shard staggering: a rebase rewrites the replicated base words and
    the next window's launch shards the new region exactly as the first
    window sharded the old one. Control polls carry ``dev=0`` (the gang is
    one logical frontier; per-device attribution is the fan's concern).
    """
    n_nonce = mesh.shape[NONCE_AXIS]
    global_chunk = chunk_per_shard * n_nonce

    def launch(params: jnp.ndarray) -> jnp.ndarray:
        return sharded_search_chunk_batch(
            params, mesh=mesh, chunk_per_shard=chunk_per_shard, kernel=kernel,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
            interpret=interpret,
        )

    return runloop.run_loop_core(
        params_batch, active, launch=launch, window=global_chunk,
        max_steps=max_steps,
        control_poll=runloop.make_control_poll(slot),
        poll_steps=poll_steps,
    )


def expected_steps(difficulty: int, *, chunk_per_shard: int, n_nonce: int) -> int:
    """Median number of ganged windows to a solution at this difficulty."""
    p = (2**64 - difficulty) / 2**64
    median_hashes = math.log(2) / max(p, 1e-30)
    return max(1, math.ceil(median_hashes / (chunk_per_shard * n_nonce)))


def replicate_params(params_batch: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place a host params batch with the sharding the ganged launch expects."""
    return jax.device_put(
        params_batch, NamedSharding(mesh, P(BATCH_AXIS, None))
    )
