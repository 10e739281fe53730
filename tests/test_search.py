"""Nonce-search correctness: jnp path, Pallas kernel (interpret), batching."""

import hashlib
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dpow.ops import pallas_kernel, runloop, search

RNG = np.random.default_rng(42)


def ref_value(nonce: int, h: bytes) -> int:
    return int.from_bytes(
        hashlib.blake2b(struct.pack("<Q", nonce & ((1 << 64) - 1)) + h, digest_size=8).digest(),
        "little",
    )


def first_valid_offset(h: bytes, difficulty: int, base: int, window: int):
    for off in range(window):
        if ref_value(base + off, h) >= difficulty:
            return off
    return None


EASY = 0xFFF0000000000000  # ~1 in 4096 nonces


def test_search_chunk_finds_first_valid():
    h = RNG.bytes(32)
    params = search.pack_params(h, EASY, base=999)
    off = int(search.search_chunk(params, chunk_size=16384))
    assert off != int(search.SENTINEL)
    assert off == first_valid_offset(h, EASY, 999, off + 1)


def test_search_chunk_none_found():
    h = RNG.bytes(32)
    params = search.pack_params(h, (1 << 64) - 1, base=0)
    off = int(search.search_chunk(params, chunk_size=2048))
    # all-ones difficulty is unreachable except with probability 2^-64/hash
    assert off == int(search.SENTINEL)


def test_search_chunk_base_carry_across_32bit_boundary():
    h = RNG.bytes(32)
    base = (5 << 32) - 100  # offsets cross the lo-limb wrap
    params = search.pack_params(h, EASY, base=base)
    off = int(search.search_chunk(params, chunk_size=8192))
    assert off != int(search.SENTINEL)
    assert ref_value(base + off, h) >= EASY
    assert first_valid_offset(h, EASY, base, off + 1) == off


def test_search_chunk_batch_matches_single():
    hashes = [RNG.bytes(32) for _ in range(4)]
    params = np.stack(
        [search.pack_params(h, EASY, base=i * 1000) for i, h in enumerate(hashes)]
    )
    batch = np.asarray(search.search_chunk_batch(jnp.asarray(params), chunk_size=8192))
    for i, h in enumerate(hashes):
        single = int(search.search_chunk(jnp.asarray(params[i]), chunk_size=8192))
        assert batch[i] == single


ONE_ROW = pallas_kernel.row_count(1, 1)
ONE_WINDOW = pallas_kernel.window_count(1, 1)


def _pallas_one_row(params, **kw) -> int:
    """One row through the batched kernel at B = 1, one window."""
    return int(pallas_kernel.pallas_search_chunk_batch(
        params[None], ONE_ROW, ONE_WINDOW, interpret=True, **kw)[0])


def test_pallas_interpret_matches_jnp():
    h = RNG.bytes(32)
    params = jnp.asarray(search.pack_params(h, EASY, base=31337))
    n = pallas_kernel.chunk_size(8, 16)
    want = int(search.search_chunk(params, chunk_size=n))
    got = _pallas_one_row(params, sublanes=8, iters=16)
    assert got == want


def test_pallas_interpret_batch():
    hashes = [RNG.bytes(32) for _ in range(3)]
    params = np.stack([search.pack_params(h, EASY, base=77) for h in hashes])
    n = pallas_kernel.chunk_size(8, 8)
    got = np.asarray(
        pallas_kernel.pallas_search_chunk_batch(
            jnp.asarray(params), pallas_kernel.row_count(3, 3), ONE_WINDOW,
            sublanes=8, iters=8, interpret=True,
        )
    )
    for i in range(3):
        want = int(search.search_chunk(jnp.asarray(params[i]), chunk_size=n))
        assert got[i] == want


def _count_primitive(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr and, recursively, in the
    sub-jaxprs its equations carry (pallas_call, cond, scan, while, jit)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


def test_pallas_kernel_traces_the_tile_body_once_per_group():
    """At the engine's geometry (group 8, the flat 12-round body) the kernel
    holds one traced tile, not one per tile of a group: Mosaic's lowering
    unrolls the group loop, so the body need not be traced ``group`` times."""
    import jax

    from tpu_dpow.ops import blake2b

    sublanes = 32
    kernel = pallas_kernel.pallas_search_chunk_batch.trace(
        jax.ShapeDtypeStruct((1, search.PARAMS_LEN), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        sublanes=sublanes, iters=1024, nblocks=8, group=8, unroll=True,
    ).jaxpr
    lanes = jax.ShapeDtypeStruct((sublanes, 128), jnp.uint32)
    word = jax.ShapeDtypeStruct((), jnp.uint32)
    tile = jax.make_jaxpr(
        lambda lo, hi, msg, d: blake2b.pow_meets_difficulty((lo, hi), msg, d, unroll=True)
    )(lanes, lanes, [word] * 8, (word, word))
    per_tile = _count_primitive(tile.jaxpr, "shift_left")
    assert per_tile > 0
    assert per_tile <= _count_primitive(kernel.jaxpr, "shift_left") < 2 * per_tile


@pytest.mark.parametrize("group", [4, 8])
def test_pallas_interpret_group_keeps_the_lowest_hit(group):
    """Several hits inside one group of tiles: the launch still returns the
    lowest offset of the window, as a one-tile-at-a-time scan would."""
    h = RNG.bytes(32)
    easy = 0xFF00000000000000  # ~1 in 256 nonces: every 1024-lane tile hits
    params = jnp.asarray(search.pack_params(h, easy, base=4242))
    n = pallas_kernel.chunk_size(8, 8)
    want = int(search.search_chunk(params, chunk_size=n))
    got = _pallas_one_row(params, sublanes=8, iters=8, group=group)
    assert got == want == first_valid_offset(h, easy, 4242, want + 1)


def test_pallas_launch_window_cap():
    h = RNG.bytes(32)
    params = jnp.asarray(search.pack_params(h, EASY, base=0))
    with pytest.raises(ValueError):
        _pallas_one_row(params, sublanes=1024, iters=1 << 16)


def test_work_hex_convention():
    # nano work hex is the big-endian rendering of the u64 nonce
    assert search.work_hex_from_nonce(0x123456789ABCDEF0) == "123456789abcdef0"
    assert search.nonce_from_offset((1 << 64) - 1, 2) == 1


def test_pallas_interpret_multiblock_matches_single_window():
    """nblocks>1 + group>1: one dispatch over consecutive windows, same
    result as one big single-window scan (the persistent-kernel mode that
    amortizes dispatch overhead on real hardware)."""
    hashes = [RNG.bytes(32) for _ in range(2)]
    sub, it, nb, grp = 8, 4, 4, 2
    total = sub * 128 * it * nb
    params = np.stack([search.pack_params(h, EASY, base=123) for h in hashes])
    got = np.asarray(
        pallas_kernel.pallas_search_chunk_batch(
            jnp.asarray(params), pallas_kernel.row_count(2, 2),
            pallas_kernel.window_count(nb, nb),
            sublanes=sub, iters=it, nblocks=nb, group=grp, interpret=True,
        )
    )
    for i in range(2):
        want = int(search.search_chunk(jnp.asarray(params[i]), chunk_size=total))
        assert got[i] == want, (i, got[i], want)


def test_pallas_interpret_multiblock_sentinel_when_dry():
    params = np.stack([search.pack_params(bytes(32), (1 << 64) - 1, base=0)])
    got = np.asarray(
        pallas_kernel.pallas_search_chunk_batch(
            jnp.asarray(params), ONE_ROW, pallas_kernel.window_count(3, 3),
            sublanes=8, iters=4, nblocks=3, interpret=True,
        )
    )
    assert got[0] == search.SENTINEL


def _late_hit_row(difficulty: int, lo: int, hi: int) -> tuple:
    """A hash and its row (base 0) whose first hit lies in [lo, hi)."""
    for i in range(200):
        h = hashlib.blake2b(b"late-hit %d" % i, digest_size=32).digest()
        params = search.pack_params(h, difficulty, base=0)
        off = int(search.search_chunk(jnp.asarray(params), chunk_size=hi))
        if lo <= off < hi:
            return h, params
    raise AssertionError("no hash with a late first hit")


def test_pallas_runtime_window_count_matches_the_static_grid():
    """One program, its window count a runtime grid bound, called with 1x,
    4x and 16x nblocks windows: row by row the same offsets as the jnp
    reference scan of that many windows — a hit only in a late window, a
    dry row (SENTINEL) and difficulty-0 pad rows — and one trace for all
    three."""
    sub, it, nb = 8, 2, 2
    window = pallas_kernel.chunk_size(sub, it)
    counts = (nb, 4 * nb, 16 * nb)
    hard = 0xFFFF800000000000  # ~1 in 32768 nonces
    h, late = _late_hit_row(hard, counts[1] * window, counts[2] * window)
    pad = search.pack_params(bytes(32), 0, base=0)
    dry = search.pack_params(bytes(32), (1 << 64) - 1, base=0)
    easy = search.pack_params(RNG.bytes(32), EASY, base=99)
    params = jnp.asarray(np.stack([late, dry, pad, easy, pad]))
    geometry = dict(sublanes=sub, iters=it, interpret=True)
    bound = counts[-1]
    fn = pallas_kernel.pallas_search_chunk_batch
    got, traced = {}, []
    rows = pallas_kernel.row_count(len(params), len(params))
    for n in counts:
        got[n] = np.asarray(fn(params, rows, pallas_kernel.window_count(n, bound),
                               nblocks=bound, **geometry))
        traced.append(fn._cache_size())
    assert traced[0] == traced[1] == traced[2], "a window count traced again"
    for n in counts:
        want = [int(search.search_chunk(row, chunk_size=n * window)) for row in params]
        np.testing.assert_array_equal(got[n], want)
    sentinel = int(search.SENTINEL)
    assert [int(got[n][0]) == sentinel for n in counts] == [True, True, False]
    assert all(int(got[n][1]) == sentinel for n in counts)
    assert all(int(got[n][2]) == int(got[n][4]) == 0 for n in counts)
    off = int(got[counts[2]][0])
    assert off >= counts[1] * window
    assert first_valid_offset(h, hard, 0, off + 1) == off


@pytest.mark.parametrize("windows", [0, 33, -1])
def test_pallas_window_count_outside_the_bound_is_refused(windows):
    """A count past the program's static bound (offsets past 2^31) or
    below 1 (a grid that never writes the result) fails on the host."""
    with pytest.raises(ValueError, match="outside"):
        pallas_kernel.window_count(windows, 32)
    count = pallas_kernel.window_count(32, 32)
    assert count.dtype == np.int32 and int(count) == 32


@pytest.mark.parametrize("rows", [1, 3, 5])
def test_pallas_runtime_row_count_matches_a_full_launch(rows):
    """One program of B = 5 rows, its row count a runtime grid bound beside
    the window count, at 1x, 4x and 16x nblocks windows: each visited row
    returns what a launch of exactly those rows returns, the rows past the
    count read SENTINEL, and no row or window count traces again."""
    sub, it, nb = 8, 2, 2
    window = pallas_kernel.chunk_size(sub, it)
    counts = (nb, 4 * nb, 16 * nb)
    bound = counts[-1]
    hard = 0xFFFF800000000000  # ~1 in 32768 nonces
    _, late = _late_hit_row(hard, counts[1] * window, counts[2] * window)
    easier = 0xFF00000000000000  # ~1 in 256 nonces: hits in the first window
    easy = [search.pack_params(RNG.bytes(32), easier, base=7 * i) for i in range(4)]
    params = jnp.asarray(np.stack([easy[0], late, easy[1], easy[2], easy[3]]))
    batch = len(params)
    geometry = dict(sublanes=sub, iters=it, nblocks=bound, interpret=True)
    fn = pallas_kernel.pallas_search_chunk_batch
    fn(params, pallas_kernel.row_count(batch, batch),
       pallas_kernel.window_count(1, bound), **geometry)
    traced = fn._cache_size()
    got = {
        n: np.asarray(fn(params, pallas_kernel.row_count(rows, batch),
                         pallas_kernel.window_count(n, bound), **geometry))
        for n in counts
    }
    assert fn._cache_size() == traced, "a row or window count traced again"
    for n in counts:
        want = fn(params[:rows], pallas_kernel.row_count(rows, rows),
                  pallas_kernel.window_count(n, bound), **geometry)
        np.testing.assert_array_equal(got[n][:rows], np.asarray(want))
        assert int(got[n][0]) != int(search.SENTINEL)
        assert all(int(o) == int(search.SENTINEL) for o in got[n][rows:])


@pytest.mark.parametrize("rows", [0, 6, -1])
def test_pallas_row_count_outside_the_batch_is_refused(rows):
    """A count past the program's batch (rows that do not exist) or below 1
    (a grid that never clears the result) fails on the host."""
    with pytest.raises(ValueError, match="outside"):
        pallas_kernel.row_count(rows, 5)
    count = pallas_kernel.row_count(5, 5)
    assert count.dtype == np.int32 and int(count) == 5


# -- device-resident run loop (ops/runloop.py) ---------------------------


def _run_batch(params, active, *, max_steps, **kw):
    """The run loop at slot 0: no control block, so its one poll reads dead
    zeros and the loop just runs."""
    return runloop.search_run_batch_controlled(
        params, active, jnp.uint32(0), max_steps=max_steps,
        poll_steps=max_steps, sublanes=8, iters=2, **kw,
    )


def test_run_batch_finds_nonce_across_windows():
    h = RNG.bytes(32)
    base = 7 << 20
    window = 8 * 128 * 2  # sublanes=8, iters=2
    # Plant the first solution several windows past the base.
    planted = None
    for off in range(6 * window):
        if ref_value(base + off, h) >= EASY:
            planted = off
            break
    assert planted is not None
    difficulty = EASY
    params = jnp.stack([jnp.asarray(search.pack_params(h, difficulty, base))])
    lo, hi = _run_batch(params, jnp.array([True]), max_steps=8, kernel="xla")
    nonce = (int(hi[0]) << 32) | int(lo[0])
    assert nonce == base + planted


def test_run_batch_respects_max_steps():
    h = RNG.bytes(32)
    params = jnp.stack([jnp.asarray(search.pack_params(h, (1 << 64) - 1, 0))])
    lo, hi = _run_batch(params, jnp.array([True]), max_steps=3, kernel="xla")
    assert int(lo[0]) == 0xFFFFFFFF and int(hi[0]) == 0xFFFFFFFF


def test_run_batch_inactive_rows_do_not_hold_loop():
    h = RNG.bytes(32)
    rows = jnp.stack(
        [
            jnp.asarray(search.pack_params(h, EASY, 0)),
            # padding row: unreachable difficulty, must not keep scanning
            jnp.asarray(search.pack_params(bytes(32), (1 << 64) - 1, 0)),
        ]
    )
    lo, hi = _run_batch(rows, jnp.array([True, False]), max_steps=64, kernel="xla")
    assert int(lo[0]) != 0xFFFFFFFF or int(hi[0]) != 0xFFFFFFFF
    assert int(lo[1]) == 0xFFFFFFFF and int(hi[1]) == 0xFFFFFFFF


def test_run_batch_base_carry_across_64bit_wrap():
    h = RNG.bytes(32)
    window = 8 * 128 * 2
    # Base close to 2^64: the advance must wrap cleanly through zero.
    base = (1 << 64) - window - 3
    params = jnp.stack([jnp.asarray(search.pack_params(h, EASY, base))])
    lo, hi = _run_batch(params, jnp.array([True]), max_steps=8, kernel="xla")
    nonce = (int(hi[0]) << 32) | int(lo[0])
    assert ref_value(nonce, h) >= EASY


def test_run_batch_pallas_interpret_matches_xla():
    h = RNG.bytes(32)
    params = jnp.stack([jnp.asarray(search.pack_params(h, EASY, 1234))])
    lo_x, hi_x = _run_batch(params, jnp.array([True]), max_steps=4, kernel="xla")
    lo_p, hi_p = _run_batch(
        params, jnp.array([True]), max_steps=4, kernel="pallas", interpret=True,
    )
    assert int(lo_x[0]) == int(lo_p[0]) and int(hi_x[0]) == int(hi_p[0])
