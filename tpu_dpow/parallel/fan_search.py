"""Device-parallel nonce search as a pmap fan-out.

The twin of the shard_map mesh gang (parallel/mesh_search.py), built on
``jax.pmap`` over ``jax.local_devices()`` with ``lax.axis_index`` range
interleaving and a ``lax.pmin`` winner election.

Semantics match the mesh gang exactly (the fan tests run the mesh suite's
assertions verbatim):

  * each request's window of ``chunk_per_shard * n_devices`` nonces splits
    into disjoint per-device sub-ranges — device i scans
    ``[base + i*chunk_per_shard, base + (i+1)*chunk_per_shard)``;
  * winner election is a ``lax.pmin`` over the fan axis (an ICI collective
    on TPU, a shared-memory reduce on CPU) — the returned offset is global,
    relative to the request's own base, SENTINEL when the whole fanned
    window is dry;
  * the per-device compute is the untouched single-chip scanner
    (ops/search.py / ops/pallas_kernel.py), so the fanned path is
    bit-identical to the tested single-chip path; only placement and the
    election differ.

Engines that need to know WHICH device won (per-device scan clocks, EMA
attribution — backend/jax_backend.py's fan mode) use
:func:`fan_search_devices` instead: per-device base rows in, per-device
local offsets out, no collective — the host elects the winner and keeps
the attribution.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import pallas_kernel, runloop, search
from ..ops.search import SENTINEL

FAN_AXIS = "fan"

_MASK64 = (1 << 64) - 1


def fan_devices(n: int = -1) -> List[jax.Device]:
    """Resolve the local device complement for a fan of ``n``.

    ``n == -1`` takes every local device; ``n >= 1`` takes the first n —
    including 1: a one-device fan runs the exact pmap machinery with zero
    cross-device traffic, the A/B configuration that prices the fan
    plumbing against the plain path (same idiom as ``mesh_devices=1``).
    Only *local* devices: a fan is one host's ICI domain — cross-host
    scale is the fleet layer's job (tpu_dpow/fleet/).
    """
    devices = list(jax.local_devices())
    if n < 0:
        return devices
    if n < 1 or n > len(devices):
        raise ValueError(
            f"devices={n} but {len(devices)} local devices visible"
        )
    return devices[:n]


def _check_geometry(
    n: int, chunk_per_shard: int, kernel: str, sublanes: int, iters: int,
    nblocks: int,
) -> None:
    if chunk_per_shard * n >= 1 << 31:
        # Global offsets must stay below the int32/SENTINEL range so the
        # pmin winner election and uint32 return contract both hold.
        raise ValueError(
            "global chunk (chunk_per_shard * devices) must be < 2^31"
        )
    if kernel == "pallas" and chunk_per_shard != sublanes * 128 * iters * nblocks:
        raise ValueError(
            "pallas kernel: chunk_per_shard must equal sublanes*128*iters*nblocks"
        )


def _local_scan(
    p_local: jnp.ndarray, *, chunk_per_shard: int, kernel: str, sublanes: int,
    iters: int, nblocks: int, group: int, interpret: bool,
) -> jnp.ndarray:
    """One device's window scan — the untouched single-chip kernels."""
    if kernel == "pallas":
        return pallas_kernel.pallas_search_chunk_batch(
            p_local, sublanes=sublanes, iters=iters, nblocks=nblocks,
            group=group, interpret=interpret,
        )
    return search.search_chunk_batch(p_local, chunk_size=chunk_per_shard)


# pmap callables are cached per static geometry: jax.pmap returns a fresh
# wrapper each call, and rebuilding it per launch would re-trace on the hot
# path. Keyed on the device tuple too — a different fan width or device
# subset is a different compiled program.


@functools.lru_cache(maxsize=None)
def _fan_chunk_fn(
    devices: tuple, chunk_per_shard: int, kernel: str, sublanes: int,
    iters: int, nblocks: int, group: int, interpret: bool,
):
    def shard_fn(p_local: jnp.ndarray) -> jnp.ndarray:
        idx = lax.axis_index(FAN_AXIS).astype(jnp.uint32)
        span = jnp.uint32(chunk_per_shard)
        p_local = search.advance_base_batch(p_local, idx * span)
        local = _local_scan(
            p_local, chunk_per_shard=chunk_per_shard, kernel=kernel,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
            interpret=interpret,
        )
        # Local offset → offset from the request's own base. SENTINEL
        # (uint32 max) stays above every reachable global offset (< 2^31),
        # so the min-election needs no special casing.
        glob = jnp.where(local == SENTINEL, SENTINEL, idx * span + local)
        return lax.pmin(glob, FAN_AXIS)

    return jax.pmap(shard_fn, axis_name=FAN_AXIS, devices=devices)


def _stack_for_fan(params_batch, n: int) -> np.ndarray:
    """Replicate uint32[B,12] host rows to the pmap-leading [n,B,12]."""
    arr = np.asarray(params_batch, dtype=np.uint32)
    return np.ascontiguousarray(np.broadcast_to(arr, (n,) + arr.shape))


def fan_search_chunk_batch(
    params_batch,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    n_devices: int = -1,
    chunk_per_shard: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> np.ndarray:
    """One fanned multi-device launch: uint32[B,12] → uint32[B] global offsets.

    The pmap twin of mesh_search.sharded_search_chunk_batch: each request's
    window of ``chunk_per_shard * n_devices`` nonces is scanned in parallel
    across the fan, and the returned offset is relative to the request's
    own base (SENTINEL if the whole fanned window is dry), so a host loop
    advances bases by the *global* chunk exactly as in the single-chip
    engine.
    """
    devs = tuple(devices) if devices is not None else tuple(fan_devices(n_devices))
    _check_geometry(len(devs), chunk_per_shard, kernel, sublanes, iters, nblocks)
    fn = _fan_chunk_fn(
        devs, chunk_per_shard, kernel, sublanes, iters, nblocks, group,
        interpret,
    )
    out = fn(_stack_for_fan(params_batch, len(devs)))
    # pmin replicated the election across the fan; any row of the leading
    # axis is the answer.
    return np.asarray(out)[0]


@functools.lru_cache(maxsize=None)
def _fan_devices_fn(
    devices: tuple, chunk_per_shard: int, kernel: str, sublanes: int,
    iters: int, nblocks: int, group: int, interpret: bool,
):
    def dev_fn(p_local: jnp.ndarray) -> jnp.ndarray:
        return _local_scan(
            p_local, chunk_per_shard=chunk_per_shard, kernel=kernel,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
            interpret=interpret,
        )

    return jax.pmap(dev_fn, axis_name=FAN_AXIS, devices=devices)


def fan_search_devices(
    stacked_params: np.ndarray,
    *,
    devices: Sequence[jax.Device],
    chunk_per_shard: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> np.ndarray:
    """Per-device launch with caller-owned bases: uint32[D,B,12] → uint32[D,B].

    No collective and no election: every device scans its own rows' windows
    (the caller bakes each device's base words into its slice) and returns
    LOCAL offsets. This is the engine's fan primitive — the host keeps the
    per-device bases, so it can elect the winner AND attribute it to the
    device whose sub-range produced it (per-device scan clocks / EMA,
    backend/jax_backend.py).
    """
    devs = tuple(devices)
    if stacked_params.shape[0] != len(devs):
        raise ValueError(
            f"stacked params lead axis {stacked_params.shape[0]} != "
            f"{len(devs)} fan devices"
        )
    if kernel == "pallas" and chunk_per_shard != sublanes * 128 * iters * nblocks:
        raise ValueError(
            "pallas kernel: chunk_per_shard must equal sublanes*128*iters*nblocks"
        )
    fn = _fan_devices_fn(
        devs, chunk_per_shard, kernel, sublanes, iters, nblocks, group,
        interpret,
    )
    return np.asarray(fn(jnp.asarray(stacked_params)))


@functools.lru_cache(maxsize=None)
def _fan_controlled_fn(
    devices: tuple, chunk_per_shard: int, max_steps: int, poll_steps: int,
    stride: int, kernel: str, sublanes: int, iters: int, nblocks: int,
    group: int, interpret: bool,
):
    def dev_fn(p_local: jnp.ndarray, active: jnp.ndarray, slot: jnp.ndarray):
        idx = lax.axis_index(FAN_AXIS)

        def launch(params: jnp.ndarray) -> jnp.ndarray:
            return _local_scan(
                params, chunk_per_shard=chunk_per_shard, kernel=kernel,
                sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
                interpret=interpret,
            )

        return runloop.run_loop_core(
            p_local, active, launch=launch, window=stride,
            max_steps=max_steps,
            control_poll=runloop.make_control_poll(slot, dev=idx),
            poll_steps=poll_steps,
        )

    return jax.pmap(
        dev_fn, axis_name=FAN_AXIS, devices=devices, in_axes=(0, 0, None)
    )


def fan_search_run_controlled(
    stacked_params: np.ndarray,
    slot: int,
    *,
    devices: Sequence[jax.Device],
    chunk_per_shard: int,
    max_steps: int,
    poll_steps: int,
    stride: Optional[int] = None,
    active: Optional[np.ndarray] = None,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """The PERSISTENT fan launch: per-device multi-window search with a live
    control channel — uint32[D,B,12] caller-baked bases in, per-device
    absolute (lo, hi) uint32[D,B] nonces out (all-ones unsolved/cancelled).

    The engine twin of :func:`fan_search_devices`: no collective, the host
    elects the winner and keeps the attribution. Every device polls the
    SAME control slot every ``poll_steps`` windows with its own fan index,
    so ops/control.py can hand each device its own rebase base (a fleet
    cover_range re-partitions all device shards mid-launch — the PR-6
    idiom without the relaunch). ``stride`` is each device's per-window
    frontier advance: ``chunk_per_shard`` for contiguous 'split' macro-
    ranges (the default), ``chunk_per_shard * n_devices`` for 'interleave'
    (caller bakes the initial ``d * chunk_per_shard`` stagger into the
    base words, exactly as at dispatch time).
    """
    devs = tuple(devices)
    n = len(devs)
    if stacked_params.shape[0] != n:
        raise ValueError(
            f"stacked params lead axis {stacked_params.shape[0]} != {n} fan devices"
        )
    if kernel == "pallas" and chunk_per_shard != sublanes * 128 * iters * nblocks:
        raise ValueError(
            "pallas kernel: chunk_per_shard must equal sublanes*128*iters*nblocks"
        )
    if stride is None:
        stride = chunk_per_shard
    if stride >= 1 << 31:
        raise ValueError("per-window stride must stay below 2^31 nonces")
    b = stacked_params.shape[1]
    if active is None:
        act = np.ones((n, b), dtype=bool)
    else:
        act = np.ascontiguousarray(
            np.broadcast_to(np.asarray(active, dtype=bool), (n, b))
        )
    fn = _fan_controlled_fn(
        devs, chunk_per_shard, max_steps, poll_steps, stride, kernel,
        sublanes, iters, nblocks, group, interpret,
    )
    lo, hi = fn(
        jnp.asarray(stacked_params), jnp.asarray(act), jnp.uint32(slot)
    )
    return np.asarray(lo), np.asarray(hi)


@functools.lru_cache(maxsize=None)
def _fan_run_fn(
    devices: tuple, chunk_per_shard: int, max_steps: int, kernel: str,
    sublanes: int, iters: int, nblocks: int, group: int, interpret: bool,
):
    n = len(devices)
    global_window = chunk_per_shard * n

    def dev_fn(p_local: jnp.ndarray, active: jnp.ndarray):
        idx = lax.axis_index(FAN_AXIS).astype(jnp.uint32)
        p_local = search.advance_base_batch(p_local, idx * jnp.uint32(chunk_per_shard))

        def launch(params: jnp.ndarray) -> jnp.ndarray:
            return _local_scan(
                params, chunk_per_shard=chunk_per_shard, kernel=kernel,
                sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
                interpret=interpret,
            )

        # Window k of device i covers [base + k*global + i*chunk, +chunk):
        # the fan's interleaved windows tile the nonce space with no gaps
        # or overlaps, exactly like the mesh gang's sharded_search_run.
        return runloop.run_loop_core(
            p_local, active, launch=launch, window=global_window,
            max_steps=max_steps,
        )

    return jax.pmap(dev_fn, axis_name=FAN_AXIS, devices=devices, in_axes=(0, 0))


def fan_search_run(
    params_batch,
    active=None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    n_devices: int = -1,
    chunk_per_shard: int,
    max_steps: int,
    kernel: str = "xla",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
):
    """Multi-step fanned search: windows flow until every request hits or
    ``max_steps`` fanned windows are dry → (lo, hi) uint32[B] absolute
    nonces (all-ones unsolved) — the pmap twin of sharded_search_run.

    Each device runs the shared device-resident while_loop
    (ops/runloop.py) over its own interleaved sub-windows; a device whose
    rows all hit exits its loop early (siblings run on to their own hit or
    ``max_steps`` — the host-side election below then picks the globally
    earliest offset, which is bit-identical to the mesh gang's per-window
    pmin election because every device reports its FIRST hit).
    """
    devs = tuple(devices) if devices is not None else tuple(fan_devices(n_devices))
    n = len(devs)
    _check_geometry(n, chunk_per_shard, kernel, sublanes, iters, nblocks)
    fn = _fan_run_fn(
        devs, chunk_per_shard, max_steps, kernel, sublanes, iters, nblocks,
        group, interpret,
    )
    rows = np.asarray(params_batch, dtype=np.uint32)
    b = rows.shape[0]
    if active is None:
        act = np.ones((n, b), dtype=bool)
    else:
        act = np.ascontiguousarray(
            np.broadcast_to(np.asarray(active, dtype=bool), (n, b))
        )
    lo_d, hi_d = fn(jnp.asarray(_stack_for_fan(rows, n)), jnp.asarray(act))
    lo_d, hi_d = np.asarray(lo_d), np.asarray(hi_d)
    bases = (
        rows[:, search.BASE_HI].astype(np.uint64) << np.uint64(32)
    ) | rows[:, search.BASE_LO].astype(np.uint64)
    out_lo = np.full((b,), 0xFFFFFFFF, dtype=np.uint32)
    out_hi = np.full((b,), 0xFFFFFFFF, dtype=np.uint32)
    for i in range(b):
        best: Optional[int] = None
        for d in range(n):
            nonce = (int(hi_d[d, i]) << 32) | int(lo_d[d, i])
            if nonce == _MASK64:
                continue
            off = (nonce - int(bases[i])) & _MASK64
            if best is None or off < ((best - int(bases[i])) & _MASK64):
                best = nonce
        if best is not None:
            out_lo[i] = best & 0xFFFFFFFF
            out_hi[i] = best >> 32
    return out_lo, out_hi
