"""Multi-chip device-parallel search on the virtual 8-device CPU mesh.

The reference has no analog of these tests: its 'multi-node' story is live
clients racing over a real broker (SURVEY.md §4). Here the gang path must be
bit-identical to the single-chip scanner, with winner election moved into an
on-device reduction instead of the Redis SETNX lock (reference
server/dpow_server.py:138).

TWO gang implementations share the contract and run the same assertions
(parametrized below): the shard_map mesh (parallel/mesh_search.py) and the
pmap fan (parallel/fan_search.py).
"""

import hashlib
import math
import secrets

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dpow.ops import pallas_kernel, search
from tpu_dpow.parallel import (
    BATCH_AXIS,
    NONCE_AXIS,
    fan_search_devices,
    fan_search_run_controlled,
    make_mesh,
    replicate_params,
    sharded_search_chunk_batch,
    sharded_search_run,
)
from tpu_dpow.utils import nanocrypto as nc

CHUNK = 256  # tiny per-shard windows: tests stay fast on CPU
MASK64 = (1 << 64) - 1
SENTINEL = int(search.SENTINEL)

#: Each gang test runs once per implementation.
GANG_IMPLS = [
    pytest.param("fan", id="fan"),
    pytest.param("shard_map", id="shard_map"),
]


@pytest.fixture(params=GANG_IMPLS)
def gang(request):
    return request.param


def _devs(n=None):
    devices = jax.devices()
    return devices if n is None else devices[:n]


def _all_windows(kw) -> np.int32:
    nblocks = kw.get("nblocks", 1)
    return pallas_kernel.window_count(nblocks, nblocks)


def _all_rows(rows) -> np.int32:
    return pallas_kernel.row_count(len(rows), len(rows))


def _base(row) -> int:
    return (int(row[search.BASE_HI]) << 32) | int(row[search.BASE_LO])


def _fan_stack(rows, n: int, chunk_per_shard: int) -> np.ndarray:
    """uint32[B,12] rows → the fan's [n,B,12], device d based d * chunk
    past each row's own base (the mesh's shard interleave)."""
    stacked = np.repeat(np.asarray(rows, np.uint32)[None], n, axis=0)
    for d in range(n):
        for row in stacked[d]:
            base = (_base(row) + d * chunk_per_shard) & MASK64
            row[search.BASE_LO], row[search.BASE_HI] = base & 0xFFFFFFFF, base >> 32
    return stacked


def gang_chunk_batch(impl, rows, *, chunk_per_shard, n_devices=None,
                     n_rows=None, **kw):
    """One ganged window launch via either implementation → offsets[B],
    visiting the first ``n_rows`` rows (all by default). The fan returns
    each device's local offset; the min-offset election here is the one the
    engine makes on the host (_apply_fan_rows)."""
    devices = _devs(n_devices)
    windows = _all_windows(kw)
    live = _all_rows(rows) if n_rows is None else pallas_kernel.row_count(
        n_rows, len(rows))
    if impl == "fan":
        n = len(devices)
        local = fan_search_devices(
            _fan_stack(rows, n, chunk_per_shard), live, windows,
            devices=devices,
            chunk_per_shard=chunk_per_shard, **kw
        )
        glob = [
            [SENTINEL if off == SENTINEL else d * chunk_per_shard + off
             for off in map(int, local[d])]
            for d in range(n)
        ]
        return np.asarray(np.min(glob, axis=0), dtype=np.uint32)
    mesh = make_mesh(devices)
    return np.asarray(
        sharded_search_chunk_batch(
            replicate_params(rows, mesh), live, windows, mesh=mesh,
            chunk_per_shard=chunk_per_shard, **kw
        )
    )


def gang_run(impl, rows, active=None, *, chunk_per_shard, max_steps,
             n_devices=None, **kw):
    """Multi-step ganged search via either implementation → (lo, hi)[B].
    The fan runs its controlled loop at slot 0 (no control block: its one
    poll reads dead zeros), window k of device d covering
    ``base + (k * n + d) * chunk``; the host elects each row's earliest
    nonce past its base."""
    devices = _devs(n_devices)
    if impl == "fan":
        n = len(devices)
        lo_d, hi_d = fan_search_run_controlled(
            _fan_stack(rows, n, chunk_per_shard), 0, devices=devices,
            chunk_per_shard=chunk_per_shard, max_steps=max_steps,
            poll_steps=max_steps, stride=chunk_per_shard * n, active=active,
            **kw
        )
        out = np.full((2, len(rows)), 0xFFFFFFFF, dtype=np.uint32)
        for i, row in enumerate(np.asarray(rows)):
            nonces = [(int(hi_d[d, i]) << 32) | int(lo_d[d, i]) for d in range(n)]
            found = [x for x in nonces if x != MASK64]
            if found:
                best = min(found, key=lambda x: (x - _base(row)) & MASK64)
                out[:, i] = best & 0xFFFFFFFF, best >> 32
        return out[0], out[1]
    mesh = make_mesh(devices)
    lo, hi = sharded_search_run(
        replicate_params(rows, mesh),
        jnp.asarray(active) if active is not None else None,
        mesh=mesh, chunk_per_shard=chunk_per_shard, max_steps=max_steps, **kw
    )
    return np.asarray(lo), np.asarray(hi)


def _params(block_hash: bytes, difficulty: int, base: int) -> np.ndarray:
    return np.stack([search.pack_params(block_hash, difficulty, base)])


def _plant_solution(block_hash: bytes, nonce: int) -> int:
    """Difficulty that nonce exactly meets for this hash (so it's a hit)."""
    digest = hashlib.blake2b(
        nonce.to_bytes(8, "little") + block_hash, digest_size=8
    ).digest()
    return int.from_bytes(digest, "little")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices())


def test_mesh_shape():
    m = make_mesh(jax.devices())
    assert m.shape[NONCE_AXIS] == len(jax.devices())
    m2 = make_mesh(jax.devices(), batch_shards=4)
    assert m2.shape[NONCE_AXIS] == len(jax.devices()) // 4


def test_engine_mesh_spans_distinct_local_devices():
    """mesh_devices=N builds the gang over N DISTINCT local devices (not N
    copies of device 0), and asking for more than exist fails at
    construction."""
    from tpu_dpow.backend import WorkError
    from tpu_dpow.backend.jax_backend import JaxWorkBackend

    b = JaxWorkBackend(kernel="xla", mesh_devices=4)
    assert sorted(d.id for d in b.mesh.devices.flat) == [
        d.id for d in jax.local_devices()[:4]]
    with pytest.raises(WorkError, match="local devices"):
        JaxWorkBackend(kernel="xla", mesh_devices=len(jax.local_devices()) + 1)


def test_finds_planted_nonce_in_any_shard(gang):
    """A solution planted in each chip's sub-range is found with the correct
    global offset — the disjoint-range split leaves no gaps or overlaps."""
    h = bytes(range(32))
    base = 1 << 40
    n = len(jax.devices())
    for shard in range(n):
        offset = shard * CHUNK + (CHUNK // 2)
        nonce = base + offset
        diff = _plant_solution(h, nonce)
        out = gang_chunk_batch(gang, _params(h, diff, base), chunk_per_shard=CHUNK)
        got = int(np.asarray(out)[0])
        assert got <= offset, f"shard {shard}: missed or overshot ({got})"
        # whatever offset won must itself be valid at that difficulty
        won = search.nonce_from_offset(base, got)
        assert _plant_solution(h, won) >= diff


def test_winner_election_picks_global_minimum(gang):
    """Two planted solutions in different shards: the election picks the
    lower offset — deterministic, unlike the reference's first-message race."""
    h = secrets.token_bytes(32)
    base = 7 << 33
    lo_off = 2 * CHUNK + 17  # shard 2
    hi_off = 5 * CHUNK + 3  # shard 5
    d_lo = _plant_solution(h, base + lo_off)
    d_hi = _plant_solution(h, base + hi_off)
    diff = min(d_lo, d_hi)
    out = gang_chunk_batch(gang, _params(h, diff, base), chunk_per_shard=CHUNK)
    got = int(np.asarray(out)[0])
    assert got <= lo_off
    assert _plant_solution(h, search.nonce_from_offset(base, got)) >= diff


def test_dry_window_returns_sentinel(gang):
    out = gang_chunk_batch(
        gang, _params(bytes(32), (1 << 64) - 1, 123), chunk_per_shard=CHUNK
    )
    assert int(np.asarray(out)[0]) == int(search.SENTINEL)


def test_matches_single_chip_scan(gang):
    """The ganged window must equal one big single-chip window bit-for-bit."""
    h = secrets.token_bytes(32)
    base = secrets.randbits(64)
    n = len(jax.devices())
    diff = 0xFFF0000000000000  # easy enough for hits in a small window
    p = _params(h, diff, base)
    ganged = gang_chunk_batch(gang, p, chunk_per_shard=CHUNK)
    single = search.search_chunk_batch(jax.numpy.asarray(p), chunk_size=CHUNK * n)
    assert int(np.asarray(ganged)[0]) == int(np.asarray(single)[0])


def test_batched_requests_independent(gang):
    """Batch lanes are independent: planted hit in lane 0, dry lane 1."""
    h0, h1 = secrets.token_bytes(32), secrets.token_bytes(32)
    base = 99
    diff0 = _plant_solution(h0, base + 10)
    rows = np.stack(
        [
            search.pack_params(h0, diff0, base),
            search.pack_params(h1, (1 << 64) - 1, base),
        ]
    )
    out = np.asarray(gang_chunk_batch(gang, rows, chunk_per_shard=CHUNK))
    assert int(out[0]) <= 10
    assert int(out[1]) == int(search.SENTINEL)


def test_batch_rows_on_partial_gang(gang):
    """Multiple requests on a 2-device gang (the mesh's (batch=4, nonce=2)
    shape; the fan's equivalent is every row fanned over the same 2
    devices): all rows solve independently."""
    h = secrets.token_bytes(32)
    base = 5000
    diff = _plant_solution(h, base + 3)
    rows = np.stack([search.pack_params(h, diff, base) for _ in range(4)])
    if gang == "fan":
        out = np.asarray(
            gang_chunk_batch(gang, rows, chunk_per_shard=CHUNK, n_devices=2)
        )
    else:
        m = make_mesh(jax.devices(), batch_shards=4)
        out = np.asarray(
            sharded_search_chunk_batch(
                replicate_params(rows, m), _all_rows(rows),
                pallas_kernel.window_count(1, 1),
                mesh=m, chunk_per_shard=CHUNK,
            )
        )
    assert all(int(o) <= 3 for o in out)


@pytest.mark.parametrize("n_rows", [1, 3])
def test_gang_runtime_row_count_visits_only_live_rows(gang, n_rows):
    """A chunked gang launch passes its live row count to the kernel: the
    rows it visits return what a launch of every row returns, the rest read
    SENTINEL."""
    sub, it = 8, 2
    chunk = sub * 128 * it
    easier = 0xFF00000000000000  # ~1 in 256 nonces: every row hits
    p = np.stack([
        search.pack_params(secrets.token_bytes(32), easier, 1000 * i)
        for i in range(4)
    ])
    kw = dict(kernel="pallas", sublanes=sub, iters=it, interpret=True)
    full = gang_chunk_batch(gang, p, chunk_per_shard=chunk, **kw)
    part = gang_chunk_batch(gang, p, chunk_per_shard=chunk, n_rows=n_rows, **kw)
    assert SENTINEL not in [int(o) for o in full]
    np.testing.assert_array_equal(part[:n_rows], full[:n_rows])
    assert all(int(o) == SENTINEL for o in part[n_rows:])


def test_batch_sharded_mesh_masks_shards_past_the_row_count():
    """On a (batch=2, nonce=n/2) mesh a row count of 1 leaves the second
    batch shard no live row: its kernel still visits one row (a grid needs
    one), and the mesh reads SENTINEL for it."""
    sub, it = 8, 2
    chunk = sub * 128 * it
    m = make_mesh(jax.devices(), batch_shards=2)
    easier = 0xFF00000000000000
    p = np.stack([
        search.pack_params(secrets.token_bytes(32), easier, 0) for _ in range(4)
    ])
    out = np.asarray(sharded_search_chunk_batch(
        replicate_params(p, m), pallas_kernel.row_count(1, 4),
        pallas_kernel.window_count(1, 1), mesh=m, chunk_per_shard=chunk,
        kernel="pallas", sublanes=sub, iters=it, interpret=True,
    ))
    assert int(out[0]) != SENTINEL
    assert [int(o) for o in out[1:]] == [SENTINEL] * 3


def test_gang_search_run_to_solution(gang):
    """The multi-step run path covers windows until a real solution at a
    moderate difficulty, and the winning nonce validates via hashlib."""
    h = secrets.token_bytes(32)
    diff = 0xFFFC000000000000  # ~2^14 expected hashes: a few tiny windows
    p = _params(h, diff, secrets.randbits(64))
    # Median number of ganged windows to a solution at this difficulty.
    median_hashes = math.log(2) / ((2**64 - diff) / 2**64)
    steps = max(1, math.ceil(median_hashes / (CHUNK * len(jax.devices()))))
    lo, hi = gang_run(
        gang, p, chunk_per_shard=CHUNK, max_steps=max(steps * 8, 64)
    )
    nonce = (int(np.asarray(hi)[0]) << 32) | int(np.asarray(lo)[0])
    assert nonce != (1 << 64) - 1, "search did not converge"
    work = search.work_hex_from_nonce(nonce)
    assert nc.work_value(h.hex(), work) >= diff


def test_gang_pallas_multiblock_matches_xla(gang):
    """Persistent-kernel mode per shard (nblocks>1, group>1) must return the
    same winner as the plain XLA scanner over the identical ganged window —
    the multi-chip path may not change semantics when it amortizes dispatch
    (VERDICT round-1 weak #3)."""
    sub, it, nb, grp = 8, 4, 2, 2
    chunk = sub * 128 * it * nb  # 8192 per shard
    h = secrets.token_bytes(32)
    base = 3 << 20
    n = len(jax.devices())
    # Plant the winner inside the SECOND window of a middle shard, so the
    # hit requires the in-dispatch window advance to be offset-correct.
    shard = min(2, n - 1)
    offset = shard * chunk + sub * 128 * it + 37
    diff = _plant_solution(h, base + offset)
    p = _params(h, diff, base)
    pall = gang_chunk_batch(
        gang, p, chunk_per_shard=chunk, kernel="pallas", sublanes=sub,
        iters=it, nblocks=nb, group=grp, interpret=True,
    )
    xla = gang_chunk_batch(gang, p, chunk_per_shard=chunk)
    got = int(np.asarray(pall)[0])
    assert got == int(np.asarray(xla)[0])
    assert got <= offset
    assert _plant_solution(h, search.nonce_from_offset(base, got)) >= diff


def test_gang_pallas_geometry_mismatch_rejected(gang):
    with pytest.raises(ValueError):
        gang_chunk_batch(
            gang, _params(bytes(32), 1, 0), chunk_per_shard=1024,
            kernel="pallas", sublanes=8, iters=4, nblocks=2, interpret=True,
        )


def test_gang_run_pallas_multiblock_to_solution(gang):
    """The run path with the persistent-kernel geometry converges and the
    winning nonce validates — the flagship 8-chip latency configuration
    end-to-end on the virtual devices."""
    sub, it, nb = 8, 2, 2
    chunk = sub * 128 * it * nb
    h = secrets.token_bytes(32)
    diff = 0xFFFC000000000000  # ~2^14 expected hashes
    p = _params(h, diff, secrets.randbits(64))
    lo, hi = gang_run(
        gang, p, chunk_per_shard=chunk, max_steps=32, kernel="pallas",
        sublanes=sub, iters=it, nblocks=nb, group=2, interpret=True,
    )
    nonce = (int(np.asarray(hi)[0]) << 32) | int(np.asarray(lo)[0])
    assert nonce != (1 << 64) - 1, "search did not converge"
    work = search.work_hex_from_nonce(nonce)
    assert nc.work_value(h.hex(), work) >= diff


def test_global_chunk_cap_enforced(gang):
    with pytest.raises(ValueError):
        gang_chunk_batch(
            gang, _params(bytes(32), 1, 0), chunk_per_shard=1 << 30
        )


def test_gang_run_active_mask_skips_padding(gang):
    """Padding rows (unreachable difficulty, active=False) must not hold the
    device-resident while_loop at max_steps once real rows have solved."""
    h = secrets.token_bytes(32)
    rows = np.stack(
        [
            _params(h, 0xFFF0000000000000, 4321)[0],
            _params(bytes(32), (1 << 64) - 1, 0)[0],  # engine batch padding
        ]
    )
    lo, hi = gang_run(
        gang, rows, np.array([True, False]), chunk_per_shard=CHUNK,
        max_steps=256,
    )
    solved = (int(hi[0]) << 32) | int(lo[0])
    assert solved != (1 << 64) - 1
    work = search.work_hex_from_nonce(solved)
    assert nc.work_value(h.hex(), work) >= 0xFFF0000000000000
    assert int(lo[1]) == 0xFFFFFFFF and int(hi[1]) == 0xFFFFFFFF


def test_fan_matches_shard_map_contract_on_partial_width(gang):
    """A 4-device gang (half the complement) still tiles its window with no
    gaps: a nonce planted in the LAST device's sub-range is found. Pins the
    width parameter end to end on both implementations."""
    h = secrets.token_bytes(32)
    base = 77
    planted = base + 3 * CHUNK + 9  # fourth shard's sub-range
    diff = _plant_solution(h, planted)
    out = np.asarray(
        gang_chunk_batch(
            gang, _params(h, diff, base), chunk_per_shard=CHUNK, n_devices=4
        )
    )
    off = int(out[0])
    assert off != 0xFFFFFFFF and off <= planted - base
    assert nc.work_value(h.hex(), search.work_hex_from_nonce(base + off)) >= diff


# -- multi-host topology (parallel/multihost.py) --------------------------


class _StubDev:
    def __init__(self, id, process_index):
        self.id = id
        self.process_index = process_index

    def __repr__(self):
        return f"dev{self.id}@h{self.process_index}"


def test_arrange_by_host_groups_ici_rows():
    from tpu_dpow.parallel import arrange_by_host

    devs = [
        _StubDev(5, 1), _StubDev(0, 0), _StubDev(4, 1),
        _StubDev(1, 0), _StubDev(2, 0), _StubDev(3, 1),
    ]
    arr = arrange_by_host(devs)
    assert arr.shape == (2, 3)
    # rows are hosts in order; columns sorted by device id within the host
    assert [d.id for d in arr[0]] == [0, 1, 2]
    assert [d.id for d in arr[1]] == [3, 4, 5]


def test_arrange_by_host_rejects_ragged_slice():
    from tpu_dpow.parallel import arrange_by_host

    with pytest.raises(ValueError):
        arrange_by_host([_StubDev(0, 0), _StubDev(1, 0), _StubDev(2, 1)])


def test_multihost_mesh_single_process_runs_search():
    """With one process the multihost mesh is (1, n_local) — and the ganged
    search must run on it exactly as on make_mesh's latency mode."""
    import jax

    from tpu_dpow.parallel import make_multihost_mesh

    mesh = make_multihost_mesh(jax.devices()[:4])
    assert mesh.shape[BATCH_AXIS] == 1 and mesh.shape[NONCE_AXIS] == 4
    h = secrets.token_bytes(32)
    base = 77
    planted = base + 2 * CHUNK + 9  # third shard's sub-range
    diff = _plant_solution(h, planted)
    p = _params(h, diff, base)
    out = np.asarray(
        sharded_search_chunk_batch(
            replicate_params(p, mesh), _all_rows(p),
            pallas_kernel.window_count(1, 1),
            mesh=mesh, chunk_per_shard=CHUNK,
        )
    )
    off = int(out[0])
    assert off != 0xFFFFFFFF and off <= planted - base
    assert nc.work_value(h.hex(), search.work_hex_from_nonce(base + off)) >= diff


def test_init_distributed_noop_without_coordinator(monkeypatch):
    from tpu_dpow.parallel import init_distributed

    monkeypatch.delenv("TPU_DPOW_COORDINATOR", raising=False)
    init_distributed()  # must not raise or touch jax.distributed
