"""Pallas TPU kernel for the Blake2b nonce search.

Hand-tiled version of ops/search.py's chunk scan for the TPU VPU:

  * a (sublanes, 128) tile of uint32 lanes, each lane testing one nonce per
    inner iteration — all 64-bit words live as (lo, hi) uint32 register pairs
    (ops/u64.py), so one tile evaluates sublanes*128 blake2b compressions in
    parallel on the 8x128 vector unit;
  * an inner ``fori_loop`` strides the tile across ``iters`` consecutive
    offset blocks, so one launch covers sublanes * 128 * iters nonces with a
    single kernel dispatch (dispatch overhead is the enemy of the <50 ms p50
    target — SURVEY.md §7 hard part #3);
  * a found-flag early exit: once any lane hits, remaining iterations take
    the cheap branch of a ``lax.cond`` and the launch drains fast — the
    in-kernel analog of the reference's MQTT cancel fan-out (reference
    server/dpow_server.py:155).

Scalar parameters (message words, difficulty, base) ride in SMEM; the single
uint32 result (first valid offset, or SENTINEL) comes back through SMEM too —
no HBM traffic in the steady state, the kernel is pure VPU compute. The same
kernel body runs in interpreter mode on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import blake2b
from .search import SENTINEL, BASE_LO, BASE_HI, DIFF_LO, DIFF_HI

# Default launch geometry: 32 sublanes x 128 lanes x 256 iters = 2^20 nonces
# per launch. bench.py tunes this on real hardware; the backend overrides.
DEFAULT_SUBLANES = 32
DEFAULT_ITERS = 256


# Mosaic has no unsigned min-reduction, so the in-kernel winner reduction
# runs in int32: offsets are < 2^31 by the launch-size cap, and INT32_MAX
# stands in for "not found" until converted back to the uint32 SENTINEL.
_NOT_FOUND_I32 = np.int32(0x7FFFFFFF)


def _search_core(
    get_param,
    sublanes: int,
    iters: int,
    unroll: bool,
    block_start,
    group: int = 1,
) -> jnp.ndarray:
    """The kernel body: scan sublanes*128*iters offsets → best offset.

    ``block_start`` (uint32) shifts the whole window, so sequential grid
    steps cover consecutive windows within one dispatch. ``group`` tiles are
    scanned per early-exit check: the found-flag ``lax.cond`` costs real
    scalar-pipeline time, so checking every tile taxes throughput; checking
    every ``group`` bounds the post-hit overshoot to ``group`` tiles instead.
    """
    tile = sublanes * 128
    if tile * iters >= 1 << 31:
        raise ValueError("launch window must stay below 2^31 nonces")
    if iters % group != 0:
        raise ValueError("iters must be a multiple of group")
    lane = (
        lax.broadcasted_iota(jnp.uint32, (sublanes, 128), 0) * np.uint32(128)
        + lax.broadcasted_iota(jnp.uint32, (sublanes, 128), 1)
    )
    lane = lane + block_start
    msg = [get_param(i) for i in range(8)]
    diff = (get_param(DIFF_LO), get_param(DIFF_HI))
    base_lo = get_param(BASE_LO)
    base_hi = get_param(BASE_HI)

    def tile_best(k):
        offset = lane + (k * np.int32(tile)).astype(jnp.uint32)
        lo = base_lo + offset
        carry = (lo < base_lo).astype(jnp.uint32)
        hi = base_hi + carry
        ok = blake2b.pow_meets_difficulty((lo, hi), msg, diff, unroll=unroll)
        return jnp.min(jnp.where(ok, offset.astype(jnp.int32), _NOT_FOUND_I32))

    def scan_block(k, best):
        def compute(_):
            # Fully unrolled: the tile body is traced once, and Mosaic's
            # lowering still emits ``group`` straight-line copies of it.
            return lax.fori_loop(
                0, group,
                lambda j, acc: jnp.minimum(acc, tile_best(k * group + j)),
                _NOT_FOUND_I32, unroll=True,
            )

        # Early exit: after a hit, every remaining group is a no-op.
        return lax.cond(best == _NOT_FOUND_I32, compute, lambda _: best, None)

    best = lax.fori_loop(0, iters // group, scan_block, _NOT_FOUND_I32)
    return jnp.where(best == _NOT_FOUND_I32, SENTINEL, best.astype(jnp.uint32))


def _kernel_blocks(
    params_ref, out_ref, *, sublanes: int, iters: int, unroll: bool, group: int
):
    """The search kernel: grid = (rows, windows); one dispatch, early exit.

    The SMEM output is shared across sequential grid steps, so it doubles as
    the found-flag: once a block writes a real offset for request b, every
    later block for b skips its compute entirely. This is the persistent-
    kernel shape that amortizes the ~8 ms dispatch overhead the
    geometry sweep exposed (SURVEY.md §7 hard part #3: "dispatch overhead
    ≈ 0 is load-bearing") while keeping in-launch cancellation granularity
    at one window.

    The first grid step clears every one of the B output words, so rows
    past the runtime row count, which the grid never visits, read SENTINEL.
    """
    b = pl.program_id(0)
    g = pl.program_id(1)
    span = np.uint32(sublanes * 128 * iters)

    @pl.when((b == 0) & (g == 0))
    def _init():
        for row in range(out_ref.shape[0]):
            out_ref[row, 0] = jnp.uint32(SENTINEL)

    @pl.when(out_ref[b, 0] == SENTINEL)
    def _compute():
        start = g.astype(jnp.uint32) * span
        local = _search_core(
            lambda i: params_ref[b, i], sublanes, iters, unroll,
            block_start=start, group=group,
        )
        out_ref[b, 0] = local


def _default_unroll(interpret: bool) -> bool:
    # Real TPU lowering gets the flat 12-round body (Mosaic pipelines it);
    # interpreter runs (CPU tests) get the rolled body — XLA-CPU takes
    # pathologically long compiling the 5k+-op unrolled graph.
    return not interpret and jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("sublanes", "iters", "nblocks", "interpret", "unroll", "group"),
)
def pallas_search_chunk_batch(
    params_batch: jnp.ndarray,
    rows: jnp.ndarray,
    windows: jnp.ndarray,
    *,
    sublanes: int = DEFAULT_SUBLANES,
    iters: int = DEFAULT_ITERS,
    nblocks: int = 1,
    interpret: bool = False,
    unroll: bool | None = None,
    group: int = 1,
) -> jnp.ndarray:
    """Batched launch: uint32[B, 12] → uint32[B], one dispatch.

    Batching concurrent requests into a single fixed-shape launch replaces
    the reference's one-item-at-a-time POSTs to the native worker
    (reference client/work_handler.py:98-108) without recompiles.

    The grid visits the first ``rows`` of the B rows (an int32 scalar, from
    ``row_count``); the rest read SENTINEL. Each visited request scans
    ``windows`` consecutive windows of ``sublanes * 128 * iters`` nonces
    inside the one dispatch, with early exit between windows — the
    persistent-kernel mode that amortizes dispatch overhead. ``windows``
    (an int32 scalar, from ``window_count``) is the grid's other runtime
    bound; ``nblocks`` is its static upper bound, the one the 2^31-offset
    guard holds. One compiled program per B then serves every row count up
    to B and every window count up to ``nblocks``.
    """
    if unroll is None:
        unroll = _default_unroll(interpret)
    if nblocks < 1:
        raise ValueError("nblocks must be >= 1")
    if nblocks * sublanes * 128 * iters >= 1 << 31:
        raise ValueError("total launch window must stay below 2^31 nonces")
    b = params_batch.shape[0]
    kernel = functools.partial(
        _kernel_blocks, sublanes=sublanes, iters=iters, unroll=unroll,
        group=group,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.uint32),
        grid=(rows, windows),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(params_batch)[:, 0]


def row_count(rows: int, batch: int) -> np.int32:
    """A launch's runtime row count for ``pallas_search_chunk_batch``,
    checked on the host against the program's static batch ``batch``.

    Inside the program the count is traced, so nothing there can refuse a
    count past the batch (whose grid would read rows that do not exist) or
    below 1 (whose grid never runs the step that clears the result). Always
    an ``np.int32``: a weak-typed Python int would trace a second program.
    """
    if not 1 <= rows <= batch:
        raise ValueError(f"row count {rows} outside [1, {batch}]")
    return np.int32(rows)


def window_count(windows: int, nblocks: int) -> np.int32:
    """A launch's runtime window count for ``pallas_search_chunk_batch``,
    checked on the host against the program's static bound ``nblocks``.

    Inside the program the count is traced, so nothing there can refuse a
    count past the bound (whose offsets would cross 2^31) or below 1 (whose
    grid never writes the result). Always an ``np.int32``: a weak-typed
    Python int would trace a second program.
    """
    if not 1 <= windows <= nblocks:
        raise ValueError(f"window count {windows} outside [1, {nblocks}]")
    return np.int32(windows)


def chunk_size(sublanes: int = DEFAULT_SUBLANES, iters: int = DEFAULT_ITERS) -> int:
    return sublanes * 128 * iters
