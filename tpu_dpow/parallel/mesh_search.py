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
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import pallas_kernel, runloop, search
from ..ops.search import SENTINEL

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
    windows: jnp.ndarray,
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

    Each shard scans ``windows`` windows (an int32 scalar from
    ``pallas_kernel.window_count``) of ``chunk_per_shard // nblocks``
    nonces; ``chunk_per_shard`` is the static bound of ``nblocks`` windows,
    so one program per batch size serves every window count. The ganged
    window is ``span * mesh.shape[NONCE_AXIS]`` nonces with ``span`` the
    shards' common scan, and shard i starts ``i * span`` past the base.
    The returned offset is relative to the request's own base (SENTINEL if
    the whole ganged window is dry), so the host loop advances bases by the
    *global* span exactly as in the single-chip engine.

    kernel='pallas' runs the hand-tiled TPU kernel per shard, ``windows``
    its runtime grid bound (then chunk_per_shard must equal
    sublanes*128*iters*nblocks); 'xla' runs the fused jnp scanner (any
    backend — what the CPU-mesh tests and the virtual-device dryrun of
    ``__graft_entry__.py`` exercise). Either way the per-shard scan is ops/runloop.py
    ``scan_windows``, with per-request early exit between windows, so the
    gang pays the ~8 ms dispatch floor once per launch like the plain path.
    """
    n_nonce = mesh.shape[NONCE_AXIS]
    if chunk_per_shard * n_nonce >= 1 << 31:
        # Global offsets must stay below the int32/SENTINEL range so the
        # pmin winner reduction and uint32 return contract both hold.
        raise ValueError("global chunk (chunk_per_shard * nonce shards) must be < 2^31")
    runloop.check_geometry(
        chunk_per_shard, kernel=kernel, sublanes=sublanes, iters=iters,
        nblocks=nblocks,
    )

    def shard_fn(p_local: jnp.ndarray, windows: jnp.ndarray) -> jnp.ndarray:
        idx = lax.axis_index(NONCE_AXIS).astype(jnp.uint32)
        span = windows.astype(jnp.uint32) * jnp.uint32(chunk_per_shard // nblocks)
        local = runloop.scan_windows(
            _advance_base(p_local, idx * span), windows, kernel=kernel,
            chunk=chunk_per_shard, sublanes=sublanes, iters=iters,
            nblocks=nblocks, group=group, interpret=interpret,
        )
        # Local offset → offset from the request's own base. SENTINEL
        # (uint32 max) stays above every reachable global offset (< 2^31),
        # so the min-election needs no special casing.
        glob = jnp.where(local == SENTINEL, SENTINEL, idx * span + local)
        return lax.pmin(glob, NONCE_AXIS)

    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS, None), P()),
        out_specs=P(BATCH_AXIS),
        check_vma=False,  # pmin replicates the result across NONCE_AXIS
    )(params_batch, windows)


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
    windows = pallas_kernel.window_count(nblocks, nblocks)

    def launch(params: jnp.ndarray) -> jnp.ndarray:
        return sharded_search_chunk_batch(
            params, windows, mesh=mesh, chunk_per_shard=chunk_per_shard,
            kernel=kernel, sublanes=sublanes, iters=iters, nblocks=nblocks,
            group=group, interpret=interpret,
        )

    return runloop.run_loop_core(
        params_batch, active, launch=launch, window=global_chunk,
        max_steps=max_steps,
    )


def replicate_params(params_batch: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place a host params batch with the sharding the ganged launch expects."""
    return jax.device_put(
        params_batch, NamedSharding(mesh, P(BATCH_AXIS, None))
    )
