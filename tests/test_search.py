"""Nonce-search correctness: jnp path, Pallas kernel (interpret), batching."""

import hashlib
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dpow.ops import pallas_kernel, search

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


def test_pallas_interpret_matches_jnp():
    h = RNG.bytes(32)
    params = jnp.asarray(search.pack_params(h, EASY, base=31337))
    n = pallas_kernel.chunk_size(8, 16)
    want = int(search.search_chunk(params, chunk_size=n))
    got = int(
        pallas_kernel.pallas_search_chunk(params, sublanes=8, iters=16, interpret=True)
    )
    assert got == want


def test_pallas_interpret_batch():
    hashes = [RNG.bytes(32) for _ in range(3)]
    params = np.stack([search.pack_params(h, EASY, base=77) for h in hashes])
    n = pallas_kernel.chunk_size(8, 8)
    got = np.asarray(
        pallas_kernel.pallas_search_chunk_batch(
            jnp.asarray(params), sublanes=8, iters=8, interpret=True
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
    got = int(pallas_kernel.pallas_search_chunk(
        params, sublanes=8, iters=8, group=group, interpret=True))
    assert got == want == first_valid_offset(h, easy, 4242, want + 1)


def test_pallas_launch_window_cap():
    h = RNG.bytes(32)
    params = jnp.asarray(search.pack_params(h, EASY, base=0))
    with pytest.raises(ValueError):
        pallas_kernel.pallas_search_chunk(params, sublanes=1024, iters=1 << 16, interpret=True)


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
            jnp.asarray(params),
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
            jnp.asarray(params), sublanes=8, iters=4, nblocks=3, interpret=True
        )
    )
    assert got[0] == search.SENTINEL


# -- device-resident run loop (ops/runloop.py) ---------------------------


def test_run_batch_finds_nonce_across_windows():
    from tpu_dpow.ops import runloop

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
    lo, hi = runloop.search_run_batch(
        params, jnp.array([True]), max_steps=8, kernel="xla",
        sublanes=8, iters=2,
    )
    nonce = (int(hi[0]) << 32) | int(lo[0])
    assert nonce == base + planted


def test_run_batch_respects_max_steps():
    from tpu_dpow.ops import runloop

    h = RNG.bytes(32)
    params = jnp.stack([jnp.asarray(search.pack_params(h, (1 << 64) - 1, 0))])
    lo, hi = runloop.search_run_batch(
        params, jnp.array([True]), max_steps=3, kernel="xla",
        sublanes=8, iters=2,
    )
    assert int(lo[0]) == 0xFFFFFFFF and int(hi[0]) == 0xFFFFFFFF


def test_run_batch_inactive_rows_do_not_hold_loop():
    from tpu_dpow.ops import runloop

    h = RNG.bytes(32)
    rows = jnp.stack(
        [
            jnp.asarray(search.pack_params(h, EASY, 0)),
            # padding row: unreachable difficulty, must not keep scanning
            jnp.asarray(search.pack_params(bytes(32), (1 << 64) - 1, 0)),
        ]
    )
    lo, hi = runloop.search_run_batch(
        rows, jnp.array([True, False]), max_steps=64, kernel="xla",
        sublanes=8, iters=2,
    )
    assert int(lo[0]) != 0xFFFFFFFF or int(hi[0]) != 0xFFFFFFFF
    assert int(lo[1]) == 0xFFFFFFFF and int(hi[1]) == 0xFFFFFFFF


def test_run_batch_base_carry_across_64bit_wrap():
    from tpu_dpow.ops import runloop

    h = RNG.bytes(32)
    window = 8 * 128 * 2
    # Base close to 2^64: the advance must wrap cleanly through zero.
    base = (1 << 64) - window - 3
    params = jnp.stack([jnp.asarray(search.pack_params(h, EASY, base))])
    lo, hi = runloop.search_run_batch(
        params, jnp.array([True]), max_steps=8, kernel="xla",
        sublanes=8, iters=2,
    )
    nonce = (int(hi[0]) << 32) | int(lo[0])
    assert ref_value(nonce, h) >= EASY


def test_run_batch_pallas_interpret_matches_xla():
    from tpu_dpow.ops import runloop

    h = RNG.bytes(32)
    params = jnp.stack([jnp.asarray(search.pack_params(h, EASY, 1234))])
    lo_x, hi_x = runloop.search_run_batch(
        params, jnp.array([True]), max_steps=4, kernel="xla",
        sublanes=8, iters=2,
    )
    lo_p, hi_p = runloop.search_run_batch(
        params, jnp.array([True]), max_steps=4, kernel="pallas",
        sublanes=8, iters=2, interpret=True,
    )
    assert int(lo_x[0]) == int(lo_p[0]) and int(hi_x[0]) == int(hi_p[0])
