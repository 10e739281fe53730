"""Device-parallel nonce search as a pmap fan-out.

The twin of the shard_map mesh gang (parallel/mesh_search.py), built on
``jax.pmap`` over ``jax.local_devices()``.

Every device scans its own rows' windows: the caller bakes each device's
base words into its slice of the uint32[D, B, 12] params, and each device
returns LOCAL results — no collective and no election. The host keeps the
per-device bases, so it can elect the winner AND attribute it to the
device whose sub-range produced it (per-device scan clocks, EMA —
backend/jax_backend.py's fan mode). The per-device compute is the
untouched single-chip window scan (ops/runloop.py ``scan_windows``), so
the fanned path is bit-identical to the tested single-chip path; only
placement differs.

  * :func:`fan_search_devices` — one chunked launch per device, its window
    count a runtime operand under the static bound ``nblocks``;
  * :func:`fan_search_run_controlled` — the persistent launch: each device
    runs the shared device-resident while_loop (ops/runloop.py) with a
    live control channel.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import pallas_kernel, runloop

FAN_AXIS = "fan"


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


def _check_fan(
    stacked_params: np.ndarray, devs: tuple, chunk_per_shard: int, kernel: str,
    sublanes: int, iters: int, nblocks: int,
) -> None:
    if stacked_params.shape[0] != len(devs):
        raise ValueError(
            f"stacked params lead axis {stacked_params.shape[0]} != "
            f"{len(devs)} fan devices"
        )
    if chunk_per_shard * len(devs) >= 1 << 31:
        # The fan tiles one request window of chunk_per_shard * devices
        # nonces; the engine's offsets within it must stay below 2^31.
        raise ValueError("fan chunk (chunk_per_shard * devices) must be < 2^31")
    runloop.check_geometry(
        chunk_per_shard, kernel=kernel, sublanes=sublanes, iters=iters,
        nblocks=nblocks,
    )


# pmap callables are cached per static geometry: jax.pmap returns a fresh
# wrapper each call, and rebuilding it per launch would re-trace on the hot
# path. Keyed on the device tuple too — a different fan width or device
# subset is a different compiled program. The window count is an operand,
# not part of the key: one program per batch size serves every count.


@functools.lru_cache(maxsize=None)
def _fan_devices_fn(
    devices: tuple, chunk_per_shard: int, kernel: str, sublanes: int,
    iters: int, nblocks: int, group: int, interpret: bool,
):
    def dev_fn(p_local: jnp.ndarray, windows: jnp.ndarray) -> jnp.ndarray:
        return runloop.scan_windows(
            p_local, windows, kernel=kernel, chunk=chunk_per_shard,
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
            interpret=interpret,
        )

    return jax.pmap(
        dev_fn, axis_name=FAN_AXIS, devices=devices, in_axes=(0, None)
    )


def fan_search_devices(
    stacked_params: np.ndarray,
    windows,
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
    """Per-device launch with caller-owned bases: uint32[D,B,12] → uint32[D,B]
    LOCAL offsets (SENTINEL where that device's span is dry).

    Each device scans ``windows`` windows (an int32 scalar from
    ``pallas_kernel.window_count``) of ``chunk_per_shard // nblocks``
    nonces from its own rows' bases; ``chunk_per_shard`` is the static
    bound of ``nblocks`` windows.
    """
    devs = tuple(devices)
    _check_fan(stacked_params, devs, chunk_per_shard, kernel, sublanes, iters, nblocks)
    fn = _fan_devices_fn(
        devs, chunk_per_shard, kernel, sublanes, iters, nblocks, group,
        interpret,
    )
    return np.asarray(fn(jnp.asarray(stacked_params), windows))


@functools.lru_cache(maxsize=None)
def _fan_controlled_fn(
    devices: tuple, chunk_per_shard: int, max_steps: int, poll_steps: int,
    stride: int, kernel: str, sublanes: int, iters: int, nblocks: int,
    group: int, interpret: bool,
):
    def dev_fn(p_local: jnp.ndarray, active: jnp.ndarray, slot: jnp.ndarray):
        idx = lax.axis_index(FAN_AXIS)
        windows = pallas_kernel.window_count(nblocks, nblocks)

        def launch(params: jnp.ndarray) -> jnp.ndarray:
            return runloop.scan_windows(
                params, windows, kernel=kernel, chunk=chunk_per_shard,
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

    Like :func:`fan_search_devices`: no collective, the host elects the
    winner and keeps the attribution. Slot 0 has no control block: its
    polls read dead zeros and the loop just runs. Every device polls the
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
    _check_fan(stacked_params, devs, chunk_per_shard, kernel, sublanes, iters, nblocks)
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
