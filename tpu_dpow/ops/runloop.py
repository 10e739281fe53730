"""Device-resident multi-window nonce search for one device.

The chunked engine (backend/jax_backend.py) pays a host↔device round trip
per launch: upload the params batch, run one kernel dispatch, download the
offsets — against the <50 ms p50 latency budget (SURVEY.md §7 hard part #3;
the reference's analog of this overhead is its per-work-item HTTP POST
dialogue with the native worker, reference client/work_handler.py:104-108).

``search_run_batch_controlled`` keeps the whole search on device: a
``lax.while_loop`` launches up to ``max_steps`` consecutive windows,
advances every row's 64-bit base between windows on device, polls a host
control channel (ops/control.py) for cancel/raise/rebase, and exits as soon
as every *active* row has a hit. One launch therefore costs one round trip
regardless of how many windows the solution needs — the persistent run
mode. Slot 0 has no control block: its polls read dead zeros and the loop
just runs.

:func:`scan_windows` is the one window scan every launch path shares: this
loop, the pmap fan (parallel/fan_search.py) and the shard_map mesh
(parallel/mesh_search.py). Which run mode costs less per solve on the chip
is ROADMAP A2 (not measured).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback

from . import control as ctl
from . import pallas_kernel, search
from .search import BASE_LO, BASE_HI, SENTINEL

#: nonce value reported for unsolved rows (all-ones). A genuine solution at
#: nonce 2^64-1 would be indistinguishable and re-searched — a 2^-64 event
#: per window, accepted for a branch-free device contract.
UNSOLVED = (1 << 64) - 1


def check_geometry(
    chunk: int, *, kernel: str, sublanes: int, iters: int, nblocks: int
) -> None:
    """Refuse a launch whose ``chunk`` is not ``nblocks`` whole windows of
    the kernel's geometry (the span :func:`scan_windows` is bounded by)."""
    if chunk % nblocks:
        raise ValueError("chunk must be a whole number of nblocks windows")
    if kernel == "pallas" and chunk != pallas_kernel.chunk_size(sublanes, iters) * nblocks:
        raise ValueError(
            "pallas kernel: chunk must equal sublanes*128*iters*nblocks"
        )


def scan_windows(
    params_batch: jnp.ndarray,
    rows,
    windows,
    *,
    kernel: str,
    chunk: int,
    sublanes: int,
    iters: int,
    nblocks: int,
    group: int,
    interpret: bool,
    unroll: Optional[bool] = None,
) -> jnp.ndarray:
    """One launch's scan: the first ``windows`` of ``nblocks`` windows of
    ``chunk // nblocks`` nonces for each of the first ``rows`` rows →
    uint32[B] lowest valid offsets, SENTINEL where dry.

    ``rows`` and ``windows`` are int32 scalars from
    ``pallas_kernel.row_count`` and ``pallas_kernel.window_count``.
    'pallas' makes them the kernel's runtime grid bounds, so one program
    serves every count, and the rows past ``rows`` read SENTINEL; 'xla'
    (the CPU fallback) scans every row's static ``chunk`` with the fused
    jnp scanner and drops the hits past ``windows`` windows, so both
    kernels answer for the same span of every visited row.
    """
    if kernel == "pallas":
        return pallas_kernel.pallas_search_chunk_batch(
            params_batch, rows, windows, sublanes=sublanes, iters=iters,
            nblocks=nblocks, group=group, interpret=interpret, unroll=unroll,
        )
    offs = search.search_chunk_batch(params_batch, chunk_size=chunk, unroll=unroll)
    span = jnp.asarray(windows).astype(jnp.uint32) * jnp.uint32(chunk // nblocks)
    return jnp.where(offs < span, offs, SENTINEL)


def run_loop_core(
    params_batch: jnp.ndarray,
    active: Optional[jnp.ndarray],
    *,
    launch,
    window,
    max_steps: int,
    control_poll=None,
    poll_steps: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The shared multi-window while_loop: trace-time building block.

    ``launch(params) -> offsets`` scans one window of ``window`` nonces per
    row; this core advances bases between windows, records first hits, and
    exits once every active row is done. Used by the single-chip
    :func:`search_run_batch_controlled`, the fan's controlled loop and the
    mesh-ganged :func:`tpu_dpow.parallel.sharded_search_run` so the subtle
    parts — found-masking, pinning solved rows at their winning nonce,
    zeroing padding rows' difficulty — live in exactly one place.

    With ``control_poll`` set (a traced ``(k, done) -> uint32[B, CTRL_WORDS]``
    callback — ops/control.py's io_callback wrapper), the loop becomes the
    PERSISTENT flavor: every ``poll_steps`` windows it reads host-updatable
    control state and reacts MID-LAUNCH — cancel exits the row (difficulty
    drops to 0 so the lanes free after one tile group; the row returns the
    all-ones UNSOLVED marker), raise swaps the target in place, rebase
    re-aims the frontier. The launch then returns only on win, cancel or
    span end, so ``max_steps`` can be span-sized without coupling cancel
    latency to launch length.
    """

    def step(state):
        k, params, lo, hi, done = state
        offs = launch(params)
        found = (offs != SENTINEL) & ~done
        win_lo, win_hi = search.nonces_from_offsets(params, offs)
        lo = jnp.where(found, win_lo, lo)
        hi = jnp.where(found, win_hi, hi)
        done = done | found
        params = search.advance_base_batch(params, window)
        # Pin solved rows at their winning nonce: every later window then
        # hits at offset 0 and takes the in-kernel early exit after one
        # tile group, instead of re-scanning a full window per step while
        # a harder row keeps the loop alive.
        params = params.at[:, BASE_LO].set(jnp.where(done, lo, params[:, BASE_LO]))
        params = params.at[:, BASE_HI].set(jnp.where(done, hi, params[:, BASE_HI]))
        return k + 1, params, lo, hi, done

    def cond(state):
        k, _, _, _, done = state
        return (k < max_steps) & ~jnp.all(done)

    b = params_batch.shape[0]
    ones = jnp.full((b,), 0xFFFFFFFF, dtype=jnp.uint32)
    pb = params_batch
    if active is None:
        done0 = jnp.zeros((b,), dtype=bool)
    else:
        done0 = ~active
        # Inactive (padding) rows get difficulty 0: they "hit" at offset 0
        # and early-exit each window at one tile group's cost; done0 keeps
        # their result pinned at the all-ones unsolved marker.
        zero = jnp.uint32(0)
        pb = pb.at[:, search.DIFF_LO].set(
            jnp.where(active, pb[:, search.DIFF_LO], zero)
        )
        pb = pb.at[:, search.DIFF_HI].set(
            jnp.where(active, pb[:, search.DIFF_HI], zero)
        )
    if control_poll is None:
        init = (jnp.int32(0), pb, ones, ones, done0)
        _, _, lo, hi, _ = lax.while_loop(cond, step, init)
        return lo, hi

    # Persistent flavor: an outer loop of poll blocks around the same
    # inner window loop. The poll runs at the START of each block, so a
    # command written during block k takes effect at block k+1 — worst-
    # case poll-to-effect is one poll interval (poll_steps windows).
    # io_callback cannot sit inside lax.cond (effect rules), which is why
    # the cadence is a nested loop rather than a `k % poll_steps` branch.
    poll_steps = max(1, int(poll_steps))

    def inner_cond(state):
        k, j, _, _, _, done = state
        return (j < poll_steps) & (k < max_steps) & ~jnp.all(done)

    def inner_step(state):
        k, j, params, lo, hi, done = state
        k, params, lo, hi, done = step((k, params, lo, hi, done))
        return k, j + 1, params, lo, hi, done

    def outer_step(state):
        k, params, lo, hi, done, seq = state
        ctrl = control_poll(k, done)
        flags = ctrl[:, ctl.IDX_FLAGS]
        live = ~done
        cancel = live & ((flags & ctl.FLAG_CANCEL) != 0)
        fresh = live & (ctrl[:, ctl.IDX_SEQ] != seq) & ~cancel
        do_raise = fresh & ((flags & ctl.FLAG_RAISE) != 0)
        do_rebase = fresh & ((flags & ctl.FLAG_REBASE) != 0)
        zero = jnp.uint32(0)
        params = params.at[:, search.DIFF_LO].set(
            jnp.where(
                cancel, zero,
                jnp.where(do_raise, ctrl[:, ctl.IDX_DIFF_LO],
                          params[:, search.DIFF_LO]),
            )
        )
        params = params.at[:, search.DIFF_HI].set(
            jnp.where(
                cancel, zero,
                jnp.where(do_raise, ctrl[:, ctl.IDX_DIFF_HI],
                          params[:, search.DIFF_HI]),
            )
        )
        params = params.at[:, BASE_LO].set(
            jnp.where(do_rebase, ctrl[:, ctl.IDX_BASE_LO], params[:, BASE_LO])
        )
        params = params.at[:, BASE_HI].set(
            jnp.where(do_rebase, ctrl[:, ctl.IDX_BASE_HI], params[:, BASE_HI])
        )
        # A cancelled row is done (exits the loop) but stays pinned at the
        # all-ones unsolved marker — the zeroed difficulty keeps its lanes
        # nearly free for any windows its batch siblings still need.
        done = done | cancel
        seq = jnp.where(fresh, ctrl[:, ctl.IDX_SEQ], seq)
        k, _, params, lo, hi, done = lax.while_loop(
            inner_cond, inner_step, (k, jnp.int32(0), params, lo, hi, done)
        )
        return k, params, lo, hi, done, seq

    def outer_cond(state):
        k, _, _, _, done, _ = state
        return (k < max_steps) & ~jnp.all(done)

    init = (jnp.int32(0), pb, ones, ones, done0, jnp.zeros((b,), jnp.uint32))
    _, _, lo, hi, _, _ = lax.while_loop(outer_cond, outer_step, init)
    return lo, hi


def make_control_poll(slot, *, dev=0):
    """The traced control poll for :func:`run_loop_core`: an unordered
    ``io_callback`` into ops/control.py's slot table.

    ``slot`` is a TRACED scalar (the launch's slot id), so one compiled
    program serves every launch of the same shape — the callback routes by
    value at run time. ``dev`` is the fan axis index (0 on the plain path);
    passing ``k`` and the live ``done`` mask makes the callback loop-variant
    (it cannot be hoisted out of the while_loop) and gives the host the
    delivery bookkeeping it mirrors (ops/control.py ``poll``).
    """

    def control_poll(k, done):
        return io_callback(
            ctl.poll_slot,
            jax.ShapeDtypeStruct((done.shape[0], ctl.CTRL_WORDS), jnp.uint32),
            slot, dev, k, done,
            ordered=False,
        )

    return control_poll


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_steps", "poll_steps", "kernel", "sublanes", "iters", "nblocks",
        "group", "interpret", "unroll",
    ),
)
def search_run_batch_controlled(
    params_batch: jnp.ndarray,
    active: Optional[jnp.ndarray],
    slot: jnp.ndarray,
    *,
    max_steps: int,
    poll_steps: int,
    kernel: str = "pallas",
    sublanes: int = pallas_kernel.DEFAULT_SUBLANES,
    iters: int = pallas_kernel.DEFAULT_ITERS,
    nblocks: int = 1,
    group: int = 1,
    interpret: bool = False,
    unroll: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scan up to ``max_steps`` windows per row in ONE device launch — the
    PERSISTENT single-chip launch.

    Args:
      params_batch: uint32[B, 12] rows (ops/search.py layout).
      active: bool[B] or None — False rows are batch padding: they are
        never scanned-for and never keep the loop alive.
      slot: the launch's control slot, traced — one compile per (batch,
        max_steps, poll_steps) shape, reused by every launch. The loop
        polls its host control block every ``poll_steps`` windows and
        applies cancel/raise/rebase mid-launch, so ``max_steps`` can span
        the whole request while cancel latency stays one poll interval.
      kernel: 'pallas' (TPU tiles) or 'xla' (fused jnp scanner — the CPU
        fallback/test path).

    Returns:
      (lo, hi) uint32[B] pairs — each row's absolute winning 64-bit nonce,
      or all-ones (UNSOLVED) where ``max_steps`` windows came up dry or the
      row was cancelled. Each step scans ``sublanes * 128 * iters *
      nblocks`` nonces per row through :func:`scan_windows`.
    """
    window = sublanes * 128 * iters * nblocks
    if window >= 1 << 31:
        raise ValueError("per-step window must stay below 2^31 nonces")

    rows = pallas_kernel.row_count(params_batch.shape[0], params_batch.shape[0])
    windows = pallas_kernel.window_count(nblocks, nblocks)

    def launch(params: jnp.ndarray) -> jnp.ndarray:
        return scan_windows(
            params, rows, windows, kernel=kernel, chunk=window, sublanes=sublanes,
            iters=iters, nblocks=nblocks, group=group, interpret=interpret,
            unroll=unroll,
        )

    return run_loop_core(
        params_batch, active, launch=launch, window=window,
        max_steps=max_steps, control_poll=make_control_poll(slot),
        poll_steps=poll_steps,
    )
