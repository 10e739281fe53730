"""On-chip correctness: Mosaic lowering of the Blake2b search kernels.

These are the hardware counterparts of tests/test_blake2b.py and
tests/test_search.py (VERDICT round-1 weak #5: zero tests executed on the
real TPU). Everything validates against hashlib.blake2b — the crypto ground
truth the server also uses for final validation (reference
server/dpow_server.py:363-368 analog).
"""

import hashlib
import secrets

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _plant(block_hash: bytes, nonce: int) -> int:
    digest = hashlib.blake2b(
        nonce.to_bytes(8, "little") + block_hash, digest_size=8
    ).digest()
    return int.from_bytes(digest, "little")


@pytest.fixture(scope="module")
def tpu_device():
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu"
    return dev


def test_blake2b_bit_exact_on_device(tpu_device):
    """Device pow values == hashlib for random nonces (the 64-bit-limb
    emulation must be carry-exact under the real VPU lowering)."""
    import jax
    import jax.numpy as jnp

    from tpu_dpow.ops import blake2b

    h = secrets.token_bytes(32)
    nonces = [secrets.randbits(64) for _ in range(64)]
    lo = jnp.asarray([n & 0xFFFFFFFF for n in nonces], dtype=jnp.uint32)
    hi = jnp.asarray([n >> 32 for n in nonces], dtype=jnp.uint32)
    msg = [jnp.uint32(w) for w in blake2b.hash_to_message_words(h)]
    out_lo, out_hi = jax.jit(blake2b.pow_work_value)((lo, hi), msg)
    got = (np.asarray(out_hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
        out_lo
    ).astype(np.uint64)
    want = np.asarray([_plant(h, n) for n in nonces], dtype=np.uint64)
    np.testing.assert_array_equal(got, want)


def test_pallas_matches_xla_scanner_on_device(tpu_device):
    """Mosaic-lowered kernel == fused-jnp scanner over the same window."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    h = secrets.token_bytes(32)
    base = secrets.randbits(64)
    sub, it = 8, 16
    chunk = sub * 128 * it
    params = np.stack([search.pack_params(h, 0xFFF0000000000000, base)])
    pall = pallas_kernel.pallas_search_chunk_batch(
        jnp.asarray(params), pallas_kernel.row_count(1, 1),
        pallas_kernel.window_count(1, 1), sublanes=sub, iters=it,
    )
    xla = search.search_chunk_batch(jnp.asarray(params), chunk_size=chunk)
    assert int(np.asarray(pall)[0]) == int(np.asarray(xla)[0])


def test_pallas_multiblock_early_exit_on_device(tpu_device):
    """The persistent-kernel grid (SMEM found-flag across sequential grid
    steps) must return the planted second-window offset, not overshoot."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    h = secrets.token_bytes(32)
    base = 5 << 30
    sub, it, nb = 8, 8, 4
    window = sub * 128 * it
    offset = window + 123  # second window
    diff = _plant(h, base + offset)
    params = np.stack([search.pack_params(h, diff, base)])
    out = pallas_kernel.pallas_search_chunk_batch(
        jnp.asarray(params), pallas_kernel.row_count(1, 1),
        pallas_kernel.window_count(nb, nb),
        sublanes=sub, iters=it, nblocks=nb, group=4,
    )
    got = int(np.asarray(out)[0])
    assert got <= offset
    assert _plant(h, base + got) >= diff


def _runtime_count_rows(sub: int, it: int, last: int):
    """Rows for the runtime-count checks: one planted in window ``last - 1``
    (of ``sub * 128 * it`` nonces), a dry row, two difficulty-0 pads."""
    from tpu_dpow.ops import search

    window = sub * 128 * it
    h = secrets.token_bytes(32)
    base = 5 << 30
    offset = (last - 1) * window + 123
    diff = _plant(h, base + offset)
    rows = np.stack([
        search.pack_params(h, diff, base),
        search.pack_params(h, (1 << 64) - 1, base),
        search.pack_params(bytes(32), 0, 0),
        search.pack_params(bytes(32), 0, 0),
    ])
    return rows, h, base, offset, diff


def _reference_offsets(rows, span: int) -> np.ndarray:
    """The XLA scanner's lowest valid offset per row over ``span`` nonces."""
    import jax.numpy as jnp

    from tpu_dpow.ops import search

    return np.asarray(search.search_chunk_batch(jnp.asarray(rows), chunk_size=span))


def test_pallas_runtime_window_count_on_device(tpu_device):
    """The engine's plain-path launch: one program whose window count is a
    runtime grid bound returns, at 1x, 4x and 16x nblocks windows, row by
    row what the XLA scanner returns over that many windows — a row planted
    in the last window, a dry row, difficulty-0 pads — traced once."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    sub, it, nb = 8, 8, 2
    window = sub * 128 * it
    counts = (nb, 4 * nb, 16 * nb)
    rows, h, base, offset, diff = _runtime_count_rows(sub, it, counts[2])
    params = jnp.asarray(rows)
    geometry = dict(sublanes=sub, iters=it, group=4)
    fn = pallas_kernel.pallas_search_chunk_batch
    got, traced = {}, []
    for n in counts:
        got[n] = np.asarray(fn(
            params, pallas_kernel.row_count(len(rows), len(rows)),
            pallas_kernel.window_count(n, counts[-1]),
            nblocks=counts[-1], **geometry))
        traced.append(fn._cache_size())
    assert traced[0] == traced[1] == traced[2]
    for n in counts:
        np.testing.assert_array_equal(got[n], _reference_offsets(rows, n * window))
        assert int(got[n][1]) == search.SENTINEL
        assert int(got[n][2]) == int(got[n][3]) == 0
    hit = int(got[counts[2]][0])
    assert hit <= offset and _plant(h, base + hit) >= diff


def test_flagship_geometry_finds_and_validates(tpu_device):
    """The bench geometry (32x128x1024, nblocks, group 8) end-to-end at an
    easy difficulty: solution found and hashlib-valid."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    h = secrets.token_bytes(32)
    base = secrets.randbits(64)
    diff = 0xFFFFF00000000000  # ~2^20 expected: well inside one dispatch
    params = np.stack([search.pack_params(h, diff, base)])
    out = pallas_kernel.pallas_search_chunk_batch(
        jnp.asarray(params), pallas_kernel.row_count(1, 1),
        pallas_kernel.window_count(4, 4),
        sublanes=32, iters=1024, nblocks=4, group=8,
    )
    got = int(np.asarray(out)[0])
    assert got != int(search.SENTINEL), "no hit in 16.7M nonces at 2^20 difficulty"
    nonce = search.nonce_from_offset(base, got)
    assert _plant(h, nonce) >= diff


def test_backend_e2e_on_device():
    """JaxWorkBackend on the chip produces hashlib-valid work at easy
    difficulty (the full generate → launch → host-revalidate path)."""
    import asyncio

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    async def run():
        b = JaxWorkBackend(sublanes=32, iters=256, nblocks=1, group=8)
        await b.setup()
        h = secrets.token_bytes(32).hex().upper()
        easy = 0xFFF0000000000000
        work = await b.generate(WorkRequest(h, easy))
        nc.validate_work(h, work, easy)
        await b.close()

    asyncio.run(run())


def test_widened_grid_deep_hit_on_device(tpu_device):
    """Run-mode geometry: the UNIQUE solution in a widened dispatch's range
    sits many windows deep; the grid must reach it and return exactly it
    (a trivially-early random hit can't satisfy this — the difficulty is
    the range's maximum work value, computed on host)."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    sub, it, nb = 8, 8, 48
    window = sub * 128 * it
    span = nb * window
    base = secrets.randbits(64)
    while True:
        h = secrets.token_bytes(32)
        values = [
            _plant(h, (base + off) & ((1 << 64) - 1)) for off in range(span)
        ]
        argmax = int(np.argmax(values))
        if argmax >= 8 * window:  # deep enough to prove cross-window travel
            break
    diff = values[argmax]  # unique hit in range, by construction
    params = np.stack([search.pack_params(h, diff, base)])
    out = pallas_kernel.pallas_search_chunk_batch(
        jnp.asarray(params), pallas_kernel.row_count(1, 1),
        pallas_kernel.window_count(nb, nb),
        sublanes=sub, iters=it, nblocks=nb, group=4,
    )
    assert int(np.asarray(out)[0]) == argmax


def test_difficulty_zero_pad_rows_cost_nothing_and_report_zero(tpu_device):
    """Difficulty-0 rows (the engine's batch padding) must hit at offset 0 —
    the padding contract of every grid that visits its pads (persistent
    mode, the XLA path)."""
    import jax.numpy as jnp

    from tpu_dpow.ops import pallas_kernel, search

    pad = search.pack_params(bytes(32), 0, 0)
    real_h = secrets.token_bytes(32)
    real = search.pack_params(real_h, 0xFFF0000000000000, secrets.randbits(64))
    params = np.stack([real] + [pad] * 7)
    out = np.asarray(
        pallas_kernel.pallas_search_chunk_batch(
            jnp.asarray(params), pallas_kernel.row_count(8, 8),
            pallas_kernel.window_count(8, 8),
            sublanes=8, iters=16, nblocks=8, group=8,
        )
    )
    assert all(int(o) == 0 for o in out[1:])  # pads hit instantly


def test_sharded_pallas_path_on_device(tpu_device):
    """The mesh-ganged path (shard_map + per-shard Pallas kernel + pmin
    election) Mosaic-lowers and solves on the real chip. A (1,1) mesh is
    topology-trivial but compiles and executes the exact same program the
    v5e-8 latency gang runs — the CPU-mesh tests and the driver's virtual
    dryrun only ever see the interpret/XLA lowering of this code."""
    import jax

    from tpu_dpow.ops import pallas_kernel, search
    from tpu_dpow.parallel import (
        make_mesh, replicate_params, sharded_search_chunk_batch,
        sharded_search_run,
    )

    mesh = make_mesh([tpu_device])
    h = secrets.token_bytes(32)
    base = secrets.randbits(64)
    sublanes, iters, nblocks = 32, 256, 4
    chunk = sublanes * 128 * iters * nblocks
    # Deterministic: the planted nonce's own work value is the target, so
    # the window always holds at least one hit (no random-draw flakiness).
    offset = chunk // 2 + 17
    diff = _plant(h, (base + offset) & ((1 << 64) - 1))
    params = np.stack([search.pack_params(h, diff, base)])

    out = sharded_search_chunk_batch(
        replicate_params(params, mesh),
        pallas_kernel.row_count(1, 1),
        pallas_kernel.window_count(nblocks, nblocks),
        mesh=mesh, chunk_per_shard=chunk, kernel="pallas",
        sublanes=sublanes, iters=iters, nblocks=nblocks, group=8,
    )
    got = int(np.asarray(out)[0])
    assert got <= offset, "planted hit missed or overshot"
    nonce = search.nonce_from_offset(base, got)
    assert _plant(h, nonce) >= diff

    # The device-resident multi-step gang (while_loop over ganged windows).
    lo, hi = sharded_search_run(
        replicate_params(params, mesh),
        jax.numpy.asarray([True]),
        mesh=mesh, chunk_per_shard=chunk, max_steps=4, kernel="pallas",
        sublanes=sublanes, iters=iters, nblocks=nblocks, group=8,
    )
    nonce = (int(np.asarray(hi)[0]) << 32) | int(np.asarray(lo)[0])
    assert nonce != (1 << 64) - 1, "run-mode gang found nothing in 16.7M nonces"
    assert _plant(h, nonce) >= diff


@pytest.mark.parametrize("gang", ["fan", "mesh"])
def test_gang_runtime_window_count_on_device(gang):
    """The engine's chunked fan (devices=1) and mesh (mesh_devices=1)
    launches: one program, the window count a runtime operand, at 1x, 4x
    and 16x nblocks windows return row by row what the XLA
    scanner returns over the same span — a row planted in the last window,
    a dry row, difficulty-0 pads."""
    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.ops import search

    sub, it, nb, runs = 8, 8, 2, (1, 4, 16)
    gang_kw = {"devices": 1} if gang == "fan" else {"mesh_devices": 1}
    b = JaxWorkBackend(
        sublanes=sub, iters=it, nblocks=nb, group=4, run_steps=runs[-1],
        **gang_kw,
    )
    assert b.kernel == "pallas" and b._program(4, 4) == (b.max_batch, None)
    rows, h, base, offset, diff = _runtime_count_rows(sub, it, nb * runs[-1])
    window = sub * 128 * it
    for steps in runs:
        lo, hi = b._launch(rows, steps)
        lo, hi = np.asarray(lo).reshape(-1), np.asarray(hi).reshape(-1)
        want = _reference_offsets(rows, steps * nb * window)
        for i, row_off in enumerate(want):
            got = (int(hi[i]) << 32) | int(lo[i])
            if row_off == 0xFFFFFFFF:
                assert got == (1 << 64) - 1, (steps, i)
            else:
                row_base = (int(rows[i][search.BASE_HI]) << 32) | int(
                    rows[i][search.BASE_LO])
                assert got == row_base + int(row_off), (steps, i)
    hit = (int(hi[0]) << 32) | int(lo[0])
    assert hit <= base + offset and _plant(h, hit) >= diff


@pytest.mark.parametrize("path", ["plain", "fan", "mesh"])
def test_runtime_row_count_on_device(path):
    """The engine's one chunked program (max_batch 16 rows) launched with
    1, 5 and 16 live rows at 1x, 4x and 16x nblocks windows: each live row
    returns what the XLA scanner returns over the same span — a row
    planted in the last window, a dry row, difficulty-0 pads, easy rows."""
    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.ops import search

    sub, it, nb, runs = 8, 8, 2, (1, 4, 16)
    gang_kw = {"plain": {}, "fan": {"devices": 1}, "mesh": {"mesh_devices": 1}}
    b = JaxWorkBackend(
        sublanes=sub, iters=it, nblocks=nb, group=4, run_steps=runs[-1],
        max_batch=16, **gang_kw[path],
    )
    assert b.kernel == "pallas" and b._program(1, 4) == (16, None)
    planted, h, base, offset, diff = _runtime_count_rows(sub, it, nb * runs[-1])
    easy = [
        search.pack_params(secrets.token_bytes(32), 0xFFF0000000000000,
                           secrets.randbits(64))
        for _ in range(12)
    ]
    rows = np.concatenate([planted, np.stack(easy)])
    window = sub * 128 * it
    for steps in runs:
        want = _reference_offsets(rows, steps * nb * window)
        for n_rows in (1, 5, 16):
            lo, hi = b._launch(rows[:n_rows], steps)
            lo, hi = np.asarray(lo).reshape(-1), np.asarray(hi).reshape(-1)
            assert lo.shape == (n_rows,)
            for i in range(n_rows):
                got = (int(hi[i]) << 32) | int(lo[i])
                if want[i] == 0xFFFFFFFF:
                    assert got == (1 << 64) - 1, (steps, n_rows, i)
                else:
                    row_base = (int(rows[i][search.BASE_HI]) << 32) | int(
                        rows[i][search.BASE_LO])
                    assert got == row_base + int(want[i]), (steps, n_rows, i)
    hit = (int(hi[0]) << 32) | int(lo[0])
    assert hit <= base + offset and _plant(h, hit) >= diff


def test_backend_run_mode_and_warm_shapes_on_device():
    """The production defaults (widened runs + one-program warming) through
    generate(): singles and a batch burst, all hashlib-valid."""
    import asyncio

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    async def run():
        b = JaxWorkBackend(sublanes=8, iters=64, nblocks=2, max_batch=4)
        assert b.run_steps > 1 and b.warm_shapes  # TPU defaults engaged
        await b.setup()
        easy = 0xFFF0000000000000
        h = secrets.token_bytes(32).hex().upper()
        work = await b.generate(WorkRequest(h, easy))
        nc.validate_work(h, work, easy)
        if b._warm_task is not None:
            await b._warm_task  # small shapes: let warmup finish
        reqs = [
            WorkRequest(secrets.token_bytes(32).hex().upper(), easy)
            for _ in range(4)
        ]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, easy)
        # The plain Pallas path: one program, every row count and run length.
        assert b._warm == {(4, None)} and not b.cold_shapes()
        await b.close()

    asyncio.run(run())


def test_backend_overscan_bounded_on_device():
    """Round-3 regression, on the real chip: with more demand than one
    batch holds, pipelined dispatch must not re-scan covered jobs — total
    device hashes per solve stays near the 1/p hash bound. The uncapped
    speculation this pins against measured ~2x the bound (123M vs 67M
    hashes/solve at base difficulty, batch-64) and halved solves/s."""
    import asyncio

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    # p = 2^-24: ~16.7M expected hashes/solve, ~0.4s of device for the
    # whole batch at production-like geometry.
    difficulty = (1 << 64) - (1 << 40)
    n = 24

    async def run():
        b = JaxWorkBackend(sublanes=32, iters=1024, nblocks=2, group=8,
                           max_batch=8, pipeline=2, run_steps=4,
                           warm_shapes=False)
        await b.setup()
        reqs = [
            WorkRequest(secrets.token_bytes(32).hex().upper(), difficulty)
            for _ in range(n)
        ]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, difficulty)
        per_solve = b.total_hashes / n
        await b.close()
        bound = 1.6 * 2**24  # mean 1.0/p, sigma ~0.2/p at n=24: ~3 sigma
        assert per_solve < bound, (
            f"{per_solve/2**24:.2f}x the hash bound per solve - "
            "covered jobs are being re-scanned"
        )

    asyncio.run(run())


def test_backend_pipelined_launches_on_device():
    """Round-3 launch pipelining on the real chip: overlapping launches
    with speculative base advancement must still produce hashlib-valid
    work for a concurrent burst, and the overlap must actually engage
    (two launch threads on-device at once)."""
    import asyncio
    import threading

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    async def run():
        b = JaxWorkBackend(sublanes=8, iters=64, nblocks=2, max_batch=4,
                           pipeline=2)
        concurrent = [0]
        peak = [0]
        lock = threading.Lock()
        orig = b._launch

        def traced(params, steps):
            with lock:
                concurrent[0] += 1
                peak[0] = max(peak[0], concurrent[0])
            try:
                return orig(params, steps)
            finally:
                with lock:
                    concurrent[0] -= 1

        b._launch = traced
        await b.setup()
        easy = 0xFFF0000000000000
        # An unreachable-hard job keeps the engine dispatching continuously,
        # so the pipeline provably fills while the easy burst solves.
        hard_hash = secrets.token_bytes(32).hex().upper()
        t_hard = asyncio.ensure_future(
            b.generate(WorkRequest(hard_hash, (1 << 64) - 1))
        )
        await asyncio.sleep(0)
        reqs = [
            WorkRequest(secrets.token_bytes(32).hex().upper(), easy)
            for _ in range(6)
        ]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, easy)
        await b.cancel(hard_hash)
        try:
            await t_hard
        except Exception:
            pass  # WorkCancelled expected
        assert peak[0] >= 2, "pipelining never overlapped launches on-device"
        await b.close()

    asyncio.run(run())


def test_cancel_drain_bounded_on_device():
    """Cancel is the latency-critical control edge (SURVEY.md §3.5): after
    cancelling a hard job that filled the pipeline, a fresh easy request
    must not wait behind a full pipeline of full-width launches. Pins the
    head-only-full-width policy on the real chip: the head launch runs
    run_steps wide, every launch dispatched behind in-flight work is capped
    at shared_steps_cap — so the post-cancel residue is bounded by
    run_steps + (pipeline-1)*cap windows, not pipeline*run_steps."""
    import asyncio
    import time

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.models import WorkRequest
    from tpu_dpow.utils import nanocrypto as nc

    async def run():
        b = JaxWorkBackend(sublanes=32, iters=1024, nblocks=2, group=8,
                           max_batch=4, pipeline=2, run_steps=16,
                           warm_shapes=False)
        launches, completed = [], []
        orig = b._launch

        def traced(params, steps):
            launches.append(steps)
            out = orig(params, steps)
            completed.append(steps)
            return out

        b._launch = traced
        await b.setup()
        # p = 2^-20 (~0.7M median hashes): solidly on the steps-1 rung at
        # this nblocks=2 geometry (real base difficulty would rung at 16
        # here and blur the head-vs-successor width assertions below).
        easy = (1 << 64) - (1 << 44)
        # Pre-compile the easy (1,1) shape OUTSIDE the measured window —
        # warm_shapes is off, so first use of a shape compiles inline
        # (~16 s), which must not be mistaken for drain.
        await b.generate(
            WorkRequest(secrets.token_bytes(32).hex().upper(), easy)
        )
        # Setup's self-test and the easy pre-compile went through the traced
        # wrapper too — drop them so the width assertions below see only the
        # hard job's launches.
        launches.clear()
        completed.clear()
        hard = secrets.token_bytes(32).hex().upper()
        t_hard = asyncio.ensure_future(
            b.generate(WorkRequest(hard, (1 << 64) - 2))
        )
        # Wait until both hard shapes ((1,16) head + (1,4) successor) have
        # compiled AND completed at least once, then let the pipeline refill
        # with warm launches — the measurement below sees only warm residue.
        while len(completed) < 2:
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.2)
        t0 = time.perf_counter()
        await b.cancel(hard)
        h2 = secrets.token_bytes(32).hex().upper()
        work = await b.generate(WorkRequest(h2, easy))
        drain_s = time.perf_counter() - t0
        try:
            await t_hard
        except Exception:
            pass  # WorkCancelled expected
        await b.close()
        nc.validate_work(h2, work, easy)
        # Mechanism: the head launch is full width; every launch dispatched
        # while the pipe was non-empty is capped (the hard job is the only
        # rung, so any 16 after the first means the successor cap regressed).
        hard_launches = [s for s in launches if s > 1]
        assert hard_launches and hard_launches[0] == 16, launches
        assert all(s <= b.shared_steps_cap for s in hard_launches[1:]), launches
        # Sanity bound on the operational drain (window ≈ 8.4M hashes ≈
        # 8 ms at flagship throughput; residue ≤ 20 windows + floor + easy
        # solve ≪ 5 s).
        assert drain_s < 5.0, f"post-cancel drain {drain_s:.2f}s"

    asyncio.run(run())
