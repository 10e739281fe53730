"""JaxWorkBackend: generate/cancel/dedup/batch semantics on the CPU path."""

import asyncio
import math

import numpy as np
import pytest

from tpu_dpow.backend import WorkCancelled, WorkError, get_backend
from tpu_dpow.backend.jax_backend import JaxWorkBackend
from tpu_dpow.models import WorkRequest, WorkType
from tpu_dpow.ops import pallas_kernel
from tpu_dpow.utils import nanocrypto as nc


RNG = np.random.default_rng(5)
EASY = 0xFFF0000000000000  # ~1 in 4096 nonces: a few ms on the CPU path


def make_backend(**kw):
    return JaxWorkBackend(kernel="xla", sublanes=8, iters=8, **kw)


#: The engine's two gang flavors share one contract; the device-parallel
#: engine tests run once per flavor.
GANG_BACKENDS = [
    pytest.param("fan", id="fan"),
    pytest.param("mesh", id="shard_map"),
]


def make_gang_backend(impl, n=8, **kw):
    if impl == "fan":
        return make_backend(devices=n, **kw)
    return make_backend(mesh_devices=n, **kw)


def random_hash() -> str:
    return RNG.bytes(32).hex().upper()


@pytest.fixture()
def backend():
    b = make_backend()
    yield b


async def _setup(b):
    await b.setup()
    return b


def test_generate_produces_valid_work(backend):
    async def run():
        await backend.setup()
        h = random_hash()
        work = await backend.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await backend.close()

    asyncio.run(run())


def test_generate_concurrent_batch(backend):
    async def run():
        await backend.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(5)]
        works = await asyncio.gather(*(backend.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        assert backend.total_solutions == 5
        await backend.close()

    asyncio.run(run())


def test_generate_dedups_same_hash(backend):
    async def run():
        await backend.setup()
        h = random_hash()
        r = WorkRequest(h, EASY)
        w1, w2 = await asyncio.gather(backend.generate(r), backend.generate(r))
        assert w1 == w2
        assert backend.total_solutions == 1
        await backend.close()

    asyncio.run(run())


def test_cancel_in_flight(backend):
    async def run():
        await backend.setup()
        h = random_hash()
        # Hard difficulty: would take ~forever on CPU, must be cancellable.
        hard = nc.derive_work_difficulty(4.0)
        task = asyncio.ensure_future(backend.generate(WorkRequest(h, hard)))
        await asyncio.sleep(0.2)
        assert not task.done()
        await backend.cancel(h)
        with pytest.raises(WorkCancelled):
            await task
        await backend.close()

    asyncio.run(run())


def test_cancel_unknown_hash_is_noop(backend):
    async def run():
        await backend.setup()
        await backend.cancel("AB" * 32)
        await backend.close()

    asyncio.run(run())


def test_close_cancels_everything(backend):
    async def run():
        await backend.setup()
        hard = nc.derive_work_difficulty(4.0)
        task = asyncio.ensure_future(backend.generate(WorkRequest(random_hash(), hard)))
        await asyncio.sleep(0.1)
        await backend.close()
        with pytest.raises(WorkCancelled):
            await task

    asyncio.run(run())


def test_engine_restarts_after_idle():
    async def run():
        b = make_backend()
        await b.setup()
        h1 = random_hash()
        w = await b.generate(WorkRequest(h1, EASY))
        nc.validate_work(h1, w, EASY)
        # engine goes idle; a later request must restart it
        await asyncio.sleep(0.05)
        h2 = random_hash()
        w2 = await b.generate(WorkRequest(h2, EASY))
        nc.validate_work(h2, w2, EASY)
        await b.close()

    asyncio.run(run())


def test_one_solve_opens_the_launch_cycle_spans_in_order(monkeypatch):
    """The engine's profiler spans (obs.span → jax.profiler.TraceAnnotation)
    partition one launch cycle: the engine packs and submits, the launch
    thread dispatches, waits on the device and reads back, the engine
    applies."""
    import threading

    import jax

    opened, lock = [], threading.Lock()

    class Recorder:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            with lock:
                opened.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    async def run():
        b = make_backend()
        await b.setup()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
        h = random_hash()
        nc.validate_work(h, await b.generate(WorkRequest(h, EASY)), EASY)
        await b.close()

    asyncio.run(run())
    cycle = ["dpow.engine.dispatch", "dpow.launch.dispatch", "dpow.launch.wait",
             "dpow.launch.readback", "dpow.engine.apply"]
    for name in cycle:
        assert name in opened, (name, opened)
    first = [opened.index(name) for name in cycle]
    assert first == sorted(first), opened
    assert all(name.startswith("dpow.") for name in opened), opened


def test_waiter_timeout_does_not_spin_engine(backend):
    # Regression: a waiter abandoning via wait_for timeout left a job that
    # was neither done nor active, and the engine busy-spun on it.
    async def run():
        await backend.setup()
        hard = nc.derive_work_difficulty(4.0)
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(
                backend.generate(WorkRequest(random_hash(), hard)), timeout=0.3
            )
        # The event loop must still be responsive and the job gone.
        t0 = asyncio.get_running_loop().time()
        await asyncio.sleep(0.05)
        assert asyncio.get_running_loop().time() - t0 < 1.0
        for _ in range(100):
            if not backend._jobs:
                break
            await asyncio.sleep(0.02)
        assert not backend._jobs
        await backend.close()

    asyncio.run(run())


def test_dedup_upgrades_difficulty(backend):
    # Regression: a second request for the same hash at a HIGHER difficulty
    # must not be satisfied by weaker work.
    async def run():
        await backend.setup()
        h = random_hash()
        low, high = 0xF000000000000000, EASY  # EASY is stricter than low
        t1 = asyncio.ensure_future(backend.generate(WorkRequest(h, low)))
        await asyncio.sleep(0)
        t2 = asyncio.ensure_future(backend.generate(WorkRequest(h, high)))
        w1, w2 = await asyncio.gather(t1, t2)
        assert w1 == w2
        nc.validate_work(h, w2, high)  # meets the stronger target
        await backend.close()

    asyncio.run(run())


def test_registry():
    assert isinstance(get_backend("jax", kernel="xla"), JaxWorkBackend)
    with pytest.raises(ValueError):
        get_backend("quantum")


def test_one_waiter_timeout_does_not_kill_dedup_waiters(backend):
    """A shared job survives one waiter's cancellation (waiter refcount)."""

    async def run():
        await backend.setup()
        h = random_hash()
        # Waiter A is cancelled outright; waiter B (sharing the job) stays.
        task_a = asyncio.ensure_future(backend.generate(WorkRequest(h, EASY)))
        await asyncio.sleep(0)
        task_b = asyncio.ensure_future(backend.generate(WorkRequest(h, EASY)))
        await asyncio.sleep(0)
        task_a.cancel()
        try:
            await task_a  # may have won the race and completed — fine
        except asyncio.CancelledError:
            pass
        work = await asyncio.wait_for(task_b, timeout=30)
        nc.validate_work(h, work, EASY)
        await backend.close()

    asyncio.run(run())


# -- device-ganged mode -------------------------------------------------
# devices >= 1 (pmap fan) or mesh_devices >= 1 (shard_map mesh) puts N
# (virtual CPU) devices on every hash — the flagship multi-chip latency
# configuration (SURVEY.md §7 stage 7).


@pytest.mark.parametrize("impl", GANG_BACKENDS)
def test_gang_backend_generates_valid_work(impl):
    async def run():
        b = make_gang_backend(impl)
        assert b.chunk == 8 * b.chunk_per_shard  # ganged window
        await b.setup()
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()

    asyncio.run(run())


@pytest.mark.parametrize("impl", GANG_BACKENDS)
def test_gang_backend_concurrent_and_cancel(impl):
    async def run():
        b = make_gang_backend(impl)
        await b.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(3)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        # cancel an unreachable-difficulty job mid-flight: the engine drops
        # the job from the next pack, which stops EVERY device shard at its
        # next window boundary.
        hard = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(hard, (1 << 64) - 2)))
        await asyncio.sleep(0.2)
        await b.cancel(hard)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(run())


@pytest.mark.parametrize("impl", GANG_BACKENDS)
def test_gang_width_one_builds_real_gang(impl):
    """devices=1 / mesh_devices=1 must run the ACTUAL gang machinery on a
    one-device complement — the engine-level A/B that prices the gang
    plumbing against the plain path on real hardware. A `> 1` guard used
    to silently downgrade the mesh flavor to the plain path, so the r4
    latency_mesh1 capture measured plain-vs-plain session drift and called
    it the gang tax."""

    async def run():
        b = make_gang_backend(impl, n=1)
        if impl == "fan":
            assert b.fan is not None and len(b.fan) == 1
        else:
            assert b.mesh is not None
        assert b.chunk == b.chunk_per_shard  # one shard, ungrown window
        await b.setup()
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()
        # Default stays the plain path: an unganged engine has neither.
        assert make_backend().mesh is None and make_backend().fan is None

    asyncio.run(run())


def test_gang_backend_rejects_oversubscription():
    import jax

    from tpu_dpow.backend import WorkError

    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", mesh_devices=len(jax.devices()) + 1)
    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", devices=len(jax.devices()) + 1)


def test_gang_flavors_mutually_exclusive():
    from tpu_dpow.backend import WorkError

    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", devices=2, mesh_devices=2)
    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", devices=2, device_shard="bogus")


# -- device-resident run mode (run_steps > 1) -----------------------------
# One launch covers up to run_steps windows in a lax.while_loop with early
# exit (ops/runloop.py) — the TPU default that pays the dispatch round trip
# once per run instead of once per window.


def test_run_mode_generates_valid_work():
    async def run():
        b = make_backend(run_steps=16)
        assert b._step_counts() == [1, 4, 16]
        await b.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(4)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        assert b.total_solutions == 4
        await b.close()

    asyncio.run(run())


def test_run_mode_adaptive_steps():
    b = make_backend(run_steps=16)
    # Easy difficulty solves inside one window -> no run-mode overshoot;
    # near-unreachable difficulty asks for the full cap.
    assert b._steps_for(EASY) == 1
    assert b._steps_for((1 << 64) - 2) == 16
    # The ladder never exceeds the configured cap.
    b2 = make_backend(run_steps=4)
    assert b2._step_counts() == [1, 4]
    assert b2._steps_for((1 << 64) - 2) == 4


def test_run_mode_cancel_between_runs():
    async def run():
        b = make_backend(run_steps=4)
        await b.setup()
        hard = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(hard, (1 << 64) - 2)))
        await asyncio.sleep(0.2)
        await b.cancel(hard)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(run())


@pytest.mark.parametrize("impl", GANG_BACKENDS)
def test_run_mode_gang_generates_valid_work(impl):
    async def run():
        b = make_gang_backend(impl, run_steps=4)
        await b.setup()
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()

    asyncio.run(run())


def test_run_mode_dedup_difficulty_raise_midflight():
    """A dedup that raises the target while a run launch is in flight must
    keep searching past a nonce that only satisfies the launched target."""

    async def run():
        b = make_backend(run_steps=4)
        await b.setup()
        h = random_hash()
        t1 = asyncio.ensure_future(b.generate(WorkRequest(h, EASY)))
        await asyncio.sleep(0)  # let the engine pick the job up
        t2 = asyncio.ensure_future(b.generate(WorkRequest(h, 0xFFFF000000000000)))
        w1, w2 = await asyncio.gather(t1, t2)
        assert w1 == w2
        nc.validate_work(h, w1, 0xFFFF000000000000)
        await b.close()

    asyncio.run(run())


# -- launch-shape warming -------------------------------------------------
# On TPU every distinct (batch, steps) shape is a separate multi-second
# compile; with warm_shapes on, the engine only launches warmed shapes and
# a background task grows the warm set after setup.


def test_pick_shape_falls_back_to_warmed():
    b = make_backend(run_steps=16, warm_shapes=True, max_batch=16)
    b._warm = {(1, 1), (1, 4), (2, 1)}
    assert b._pick_shape(1, 1) == (1, 1)
    assert b._pick_shape(1, 16) == (1, 4)  # steps fall back down the ladder
    assert b._pick_shape(2, 4) == (2, 1)  # (2,4) cold -> fewer steps
    # batch 8 not warmed at all -> largest warmed batch carries the load
    assert b._pick_shape(8, 1) == (2, 1)
    b._warm.add((8, 1))
    assert b._pick_shape(5, 1) == (8, 1)


def test_warm_shapes_burst_completes_and_warm_set_grows():
    async def run():
        b = make_backend(warm_shapes=True, max_batch=8)
        await b.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(6)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        if b._warm_task is not None:
            await b._warm_task  # CPU compiles are cheap: let it finish
        assert (8, 1) in b._warm
        await b.close()

    asyncio.run(run())


def test_warm_shapes_off_is_transparent():
    b = make_backend(warm_shapes=False, max_batch=16)
    assert b._pick_shape(5, 4) == (8, 4)
    assert b._pick_shape(30, 16) == (16, 16)


def test_persistent_launch_shape_is_in_the_warm_ladder():
    """Regression pin (ISSUE 10 satellite; the PR-4 cold-XLA-compile
    lesson): persistent mode's span-sized launch shape must sit in the
    warm ladder — both the singleton and the batched rung — so no
    unwarmed shape is ever on the dispatch path. The steerable mega-shape
    is the ONLY run rung besides the probe singleton: quantization is
    pointless when the while_loop early-exits per row."""
    b = make_backend(
        run_mode="persistent", persistent_steps=16, warm_shapes=True,
        max_batch=16,
    )
    assert b._step_counts() == [1, 16]
    # the rung every difficulty maps to IS the persistent shape
    assert b._steps_for(EASY) == 16
    assert b._steps_for((1 << 64) - 2) == 16
    # warm both rungs -> dispatch picks the mega-shape, never a cold one
    b._warm = {(1, 1), (1, 16), (16, 1), (16, 16)}
    assert b._pick_shape(1, b._steps_for(EASY)) == (1, 16)
    assert b._pick_shape(9, b._steps_for(EASY)) == (16, 16)
    # cold mega-rung -> falls back to a warmed shape, not an inline compile
    b._warm = {(1, 1), (16, 1)}
    assert b._pick_shape(1, 16) == (1, 1)


def test_persistent_warm_engine_never_compiles_on_the_dispatch_path():
    """The dispatch-path warm guard, persistent flavor: a cold persistent
    engine under a burst must only launch shapes already warmed (the
    controlled while_loop compiles are MORE expensive than the chunked
    grid's, so an inline compile would park the whole batch behind it)."""

    async def run():
        b = make_backend(
            run_mode="persistent", warm_shapes=True, max_batch=16
        )
        await b.setup()
        real_dispatch = b._dispatch_next
        cold_dispatches = []

        def recording_dispatch(*args, **kw):
            rec = real_dispatch(*args, **kw)
            if rec is not None and rec.shape not in b._warm:
                cold_dispatches.append(rec.shape)
            return rec

        b._dispatch_next = recording_dispatch
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(13)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        assert not cold_dispatches, (
            f"dispatch path launched unwarmed persistent shapes "
            f"{cold_dispatches}"
        )
        if b._warm_task is not None:
            await b._warm_task  # CPU compiles are cheap: let it finish
        assert (1, b.persistent_steps) in b._warm
        assert (16, b.persistent_steps) in b._warm
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


def make_pallas_backend(**kw):
    """The chunked Pallas path (interpreted; plain unless ``kw`` names a
    gang) at a tiny geometry: a window is 8*128*2 nonces, a launch 2
    windows per step."""
    return JaxWorkBackend(
        kernel="pallas", interpret=True, sublanes=8, iters=2, nblocks=2,
        group=1, **kw,
    )


def _difficulty_for_steps(b, steps):
    """A difficulty _steps_for maps to run length ``steps``: its median
    solve is 0.4 ``steps`` launch windows (the rung holds up to 0.5)."""
    p = math.log(2) / (b.chunk * steps * 0.4)
    return int((1 - p) * 2**64)


@pytest.mark.parametrize(
    "gang", [{}, {"devices": 1}, {"mesh_devices": 1}], ids=["plain", "fan", "mesh"]
)
def test_pallas_ladder_holds_one_program_per_batch(gang):
    """On every chunked Pallas path (plain, fan, mesh) the window count is
    a runtime operand: the warm ladder is one program per batch size, cold
    until batch 1 (setup) and max_batch (the warm task) are compiled, and
    any run length runs on a warm batch's program."""

    async def run():
        b = make_pallas_backend(
            warm_shapes=True, max_batch=4, run_steps=16, **gang
        )
        assert b._step_counts() == [1, 4, 16]
        assert b.cold_shapes() == [(1, None), (4, None)]
        await b.setup()
        await b._warm_task
        assert b.cold_shapes() == []
        assert b._warm == {(1, None), (4, None)}
        for steps in (1, 4, 16):
            assert b._steps_for(_difficulty_for_steps(b, steps)) == steps
            assert b._pick_shape(1, steps) == (1, steps)
            assert b._pick_shape(3, steps) == (4, steps)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_engine_launch_programs_gauge_counts_the_warm_set():
    """dpow_engine_launch_programs reads the programs the engine holds:
    2 on the plain Pallas path, one per (batch, steps) on a static grid."""

    async def run():
        b = make_pallas_backend(warm_shapes=True, max_batch=4, run_steps=16)
        await b.setup()
        await b._warm_task
        assert b._m_programs.value() == 2
        await b.close()
        b = make_backend(warm_shapes=True, max_batch=4, run_steps=16)
        await b.setup()
        await b._warm_task
        assert len(b._warm) == 6 and b._m_programs.value() == 6
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


def test_engine_launches_counter_by_batch_and_steps():
    """dpow_engine_launches_total counts each launch under its padded batch
    and run length: one batch-1 program serves steps 1, 4 and 16."""

    async def run():
        b = make_pallas_backend(warm_shapes=True, max_batch=4, run_steps=16)
        before = {s: b._m_launches.value("1", str(s)) for s in (1, 4, 16)}
        warm_before = b._m_launches.value("4", "1")
        await b.setup()
        await b._warm_task
        assert b._m_launches.value("1", "1") == before[1] + 1  # self-test
        assert b._m_launches.value("4", "1") == warm_before + 1  # warm task
        for steps in (4, 16):
            h = random_hash()
            d = _difficulty_for_steps(b, steps)
            nc.validate_work(h, await b.generate(WorkRequest(h, d)), d)
        for steps in (4, 16):
            assert b._m_launches.value("1", str(steps)) > before[steps]
        assert b._m_programs.value() == 2
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_warm_engine_never_compiles_on_the_dispatch_path(kernel):
    """Regression guard for the e2e soak flake: a COLD warm_shapes engine
    hit by a burst must serve every request from shapes already in the
    warm set at dispatch time — never launch an unwarmed shape inline.
    The inline compile of a batched blake2b shape costs seconds on this
    host; parked on the dispatch path it stalls every in-flight request
    past the server's 5 s default service timeout, which is exactly how
    test_e2e_soak_with_cancels_and_timeouts used to time out whenever
    earlier tests perturbed arrival timing into an uncached shape.

    On the plain Pallas path, once warm, launches at run lengths 1, 4 and
    16 trace nothing: the kernel's jit cache does not grow."""

    async def run():
        if kernel == "xla":
            b = make_backend(warm_shapes=True, max_batch=16)
        else:
            b = make_pallas_backend(warm_shapes=True, max_batch=16, run_steps=16)
        await b.setup()
        real_dispatch = b._dispatch_next
        cold_dispatches, shapes = [], set()

        def recording_dispatch(*args, **kw):
            rec = real_dispatch(*args, **kw)
            if rec is not None:
                shapes.add(rec.shape)
                if b._program(*rec.shape) not in b._warm:
                    cold_dispatches.append(rec.shape)
            return rec

        b._dispatch_next = recording_dispatch
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(13)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        assert not cold_dispatches, (
            f"dispatch path launched unwarmed shapes {cold_dispatches}"
        )
        if kernel == "pallas":
            await b._warm_task
            compiled = pallas_kernel.pallas_search_chunk_batch._cache_size()
            for steps in (1, 4, 16):
                d = _difficulty_for_steps(b, steps)
                reqs = [WorkRequest(random_hash(), d) for _ in range(2)]
                works = await asyncio.gather(*(b.generate(r) for r in reqs))
                for r, w in zip(reqs, works):
                    nc.validate_work(r.block_hash, w, d)
            assert {s for _, s in shapes} >= {1, 4, 16}
            assert not cold_dispatches
            assert pallas_kernel.pallas_search_chunk_batch._cache_size() == compiled
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


# -- launch timeout (hang protection) -------------------------------------


def test_launch_timeout_fails_waiters_and_recovers():
    """A wedged device launch must surface as WorkError (not a silent hang),
    close() must still tear down cleanly, and a later generate must work."""
    import time as _time

    from tpu_dpow.backend import WorkError

    async def run():
        b = make_backend(launch_timeout=0.2)
        await b.setup()
        real_launch = b._launch
        slow = {"on": True}

        def wedged(params, steps):
            if slow["on"]:
                _time.sleep(1.0)  # longer than launch_timeout
            return real_launch(params, steps)

        b._launch = wedged
        with pytest.raises(WorkError):
            await b.generate(WorkRequest(random_hash(), EASY))
        slow["on"] = False  # the device recovers
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()  # engine died once; teardown must not re-raise

    asyncio.run(run())


# -- difficulty-rung scheduling -------------------------------------------


def test_next_rung_round_robins():
    b = make_backend(run_steps=16)
    rungs = {1: ["e"], 4: ["m"], 16: ["h"]}
    seq = [b._next_rung(rungs) for _ in range(6)]
    assert seq == [1, 4, 16, 1, 4, 16]
    # a rung disappearing mid-cycle doesn't wedge the cursor
    assert b._next_rung({4: ["m"]}) == 4
    assert b._next_rung({1: ["e"], 16: ["h"]}) == 16
    assert b._next_rung({1: ["e"], 16: ["h"]}) == 1


def test_mixed_difficulty_launches_split_by_rung():
    """An unreachable-hard job must not widen the easy jobs' launches: the
    engine alternates rung launches instead of one maximal pack."""

    async def run():
        b = make_backend(run_steps=16)
        launches = []
        orig = b._launch

        def traced(params, steps):
            launches.append((params.shape[0], steps))
            return orig(params, steps)

        b._launch = traced
        await b.setup()
        launches.clear()
        hard = random_hash()
        t_hard = asyncio.ensure_future(b.generate(WorkRequest(hard, (1 << 64) - 2)))
        await asyncio.sleep(0)  # hard job enters the engine
        works = await asyncio.gather(
            *(b.generate(WorkRequest(random_hash(), EASY)) for _ in range(3))
        )
        assert len(works) == 3
        await b.cancel(hard)
        with pytest.raises(WorkCancelled):
            await t_hard
        # the easy jobs were served by steps-1 launches even while the
        # hard (steps-16) job was active; both rungs got device time
        steps_seen = {s for _, s in launches}
        assert 1 in steps_seen and 16 in steps_seen
        assert not any(bsize > 1 and steps == 16 for bsize, steps in launches), launches
        await b.close()

    asyncio.run(run())


def test_cold_shapes_lists_the_unwarmed_ladder_until_warmup_ends():
    """cold_shapes() is what chip_smoke.py and the bench bootstrap refuse
    to measure with: every (batch, steps) rung of the ladder not compiled."""

    async def run():
        b = make_backend(warm_shapes=True, max_batch=4, run_steps=4)
        assert b.cold_shapes() == [(1, 1), (1, 4), (4, 1), (4, 4)]
        await b.setup()
        await b._warm_task
        assert b.cold_shapes() == []
        await b.close()

    asyncio.run(run())


def test_explicit_pallas_off_tpu_is_refused_not_swapped():
    """kernel='pallas' on a non-TPU device without interpret=True must fail
    at construction, not quietly run the XLA scanner instead; the
    interpreter and the auto default (XLA off-TPU) stay available."""
    from tpu_dpow.backend.jax_backend import JaxWorkBackend

    with pytest.raises(WorkError, match="needs a TPU"):
        JaxWorkBackend(kernel="pallas")
    assert JaxWorkBackend(kernel="pallas", interpret=True).kernel == "pallas"
    assert JaxWorkBackend().kernel == "xla"


def test_jax_backend_rejects_oversize_window_at_construction():
    """A geometry whose per-dispatch window crosses the kernel's 2^31-offset
    cap must fail at __init__ with the actual constraint, not from deep
    inside the first launch."""
    from tpu_dpow.backend.jax_backend import JaxWorkBackend

    with pytest.raises(WorkError, match="2\\^31"):
        JaxWorkBackend(kernel="pallas", sublanes=32, iters=4096, nblocks=128)


# -- launch pipelining --------------------------------------------------------


def test_pipeline_overlaps_launches():
    """With pipeline=2, a second launch must be dispatched while the first is
    still executing — observed via a barrier both launch threads must reach
    concurrently (a serialized engine would deadlock the barrier and time
    out)."""
    import threading

    b = make_backend(pipeline=2)
    barrier = threading.Barrier(2, timeout=10)
    overlapped = []
    real_launch = b._launch

    def instrumented(params, steps):
        try:
            barrier.wait(timeout=5)
            overlapped.append(True)
        except threading.BrokenBarrierError:
            pass  # solo launch (e.g. first pass before the pipe fills)
        return real_launch(params, steps)

    b._launch = instrumented

    async def run():
        # Unreachable difficulty keeps the job scanning across many launches.
        hard = WorkRequest(random_hash(), (1 << 64) - 1)
        task = asyncio.ensure_future(b.generate(hard))
        for _ in range(200):
            await asyncio.sleep(0.02)
            if overlapped:
                break
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, WorkCancelled):
            pass
        await b.close()
        assert overlapped, "no two launches were ever in flight concurrently"

    asyncio.run(run())


def test_pipeline_speculative_bases_disjoint():
    """Consecutive pipelined launches for one unsolved job must scan
    consecutive disjoint spans (the speculative base advance), never the
    same window twice."""
    from tpu_dpow.ops import search

    b = make_backend(pipeline=2)
    seen = []
    real_launch = b._launch

    def recording(params, steps):
        seen.append((int(params[0, search.BASE_HI]) << 32)
                    | int(params[0, search.BASE_LO]))
        return real_launch(params, steps)

    b._launch = recording

    async def run():
        hard = WorkRequest(random_hash(), (1 << 64) - 1)
        task = asyncio.ensure_future(b.generate(hard))
        while len(seen) < 6:
            await asyncio.sleep(0.01)
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, WorkCancelled):
            pass
        await b.close()

    asyncio.run(run())
    # Drop the setup() self-test probes (base 0 or tiny); the job's bases
    # start at its random 64-bit offset and step by exactly one span.
    span = b.chunk * b.run_steps if b.run_steps else b.chunk
    job_bases = seen[-6:]
    deltas = {(b2 - b1) & ((1 << 64) - 1) for b1, b2 in zip(job_bases, job_bases[1:])}
    assert len(deltas) == 1, f"non-uniform span advance: {deltas}"
    assert deltas.pop() % b.chunk == 0


def test_pipeline_solve_correct_under_speculation(backend):
    """A solvable job under pipeline=2 still returns valid work and the
    speculative successor launch's result for the solved row is discarded."""

    async def run():
        b = make_backend(pipeline=2)
        await b.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(4)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        await b.close()

    asyncio.run(run())


async def _gated_recording_backend(**kw):
    """Backend whose launches block on a gate until released, recording the
    job hashes of every dispatched launch — the harness for pinning WHICH
    jobs each pipelined launch carries while earlier ones are in flight."""
    import threading

    b = make_backend(**kw)
    await b.setup()
    gate = threading.Event()
    real_launch = b._launch

    def gated(params, steps):
        # A timed-out wait must fail LOUDLY: proceeding ungated would fail
        # the dispatch-record assertions downstream with an error that reads
        # like a dispatch-policy regression instead of a slow-CI timeout.
        if not gate.wait(timeout=10):
            raise TimeoutError("gated-launch gate never released within 10s")
        return real_launch(params, steps)

    b._launch = gated
    real_dispatch = b._dispatch_next
    records = []

    def recording(*args, **kwargs):
        rec = real_dispatch(*args, **kwargs)
        if rec is not None:
            records.append([j.block_hash for j in rec.jobs])
        return rec

    b._dispatch_next = recording
    return b, gate, records


def test_pipeline_successor_serves_queue_not_rescan():
    """Round-3 on-chip finding: with more demand than one batch holds, a
    pipelined successor launch must serve the UNCOVERED queued jobs, not
    speculatively re-scan the batch already on the device (that overscan
    measured 1.8x device hashes/solve and halved flood throughput)."""

    async def run():
        b, gate, records = await _gated_recording_backend(max_batch=2, pipeline=2)
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(4)]
        tasks = [asyncio.ensure_future(b.generate(r)) for r in reqs]
        while len(records) < 2:
            await asyncio.sleep(0.01)
        gate.set()
        works = await asyncio.gather(*tasks)
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        await b.close()
        # EASY jobs are covered (miss 0.135 < threshold) once dispatched, so
        # the second in-flight launch must hold the OTHER two jobs.
        assert not set(records[0]) & set(records[1]), records[:2]
        assert set(records[0]) | set(records[1]) == {r.block_hash for r in reqs}

    asyncio.run(run())


def test_pipeline_idle_speculation_kept_for_lone_job():
    """With no queued demand, the engine still speculates a covered lone
    job's next span (hides the readback round trip from the unlucky tail)
    — but stops at the speculation floor instead of piling ever-deeper
    speculative launches into extra pipeline slots."""

    async def run():
        # pipeline=3 exposes the floor: a third speculative launch would
        # put the job at 0.135^3 ≈ 0.002 < SPEC_MISS_FLOOR, so only two
        # may ever be in flight for one EASY job.
        b, gate, records = await _gated_recording_backend(max_batch=2, pipeline=3)
        r = WorkRequest(random_hash(), EASY)
        task = asyncio.ensure_future(b.generate(r))
        while len(records) < 2:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.1)  # time for a (wrong) third dispatch
        n_before_release = len(records)
        gate.set()
        nc.validate_work(r.block_hash, await task, EASY)
        await b.close()
        assert records[0] == [r.block_hash]
        assert records[1] == [r.block_hash], "idle speculation was lost"
        assert n_before_release == 2, records

    asyncio.run(run())


def test_pipeline_speculation_waste_is_bounded():
    """When one launch swallows the whole queue (batch-wide max_batch), the
    speculative successor must NOT re-dispatch every covered row — expected
    wasted rows are capped (SPEC_WASTE_ROWS) so speculation never costs more
    device time than the readback round trip it hides. Round-3 on-chip
    batch-64: the uncapped successor halved solves/s."""

    async def run():
        b, gate, records = await _gated_recording_backend(max_batch=8, pipeline=2)
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(8)]
        tasks = [asyncio.ensure_future(b.generate(r)) for r in reqs]
        while len(records) < 2:
            await asyncio.sleep(0.01)
        gate.set()
        works = await asyncio.gather(*tasks)
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        await b.close()
        assert len(records[0]) == 8, records[0]
        # EASY solve probability per covered row is 1 - 0.135 ≈ 0.86, so the
        # 2.0-expected-wasted-rows cap admits exactly 2 speculative rows.
        assert len(records[1]) == 2, records[1]

    asyncio.run(run())


def test_difficulty_raise_resets_coverage():
    """Raising a covered job's difficulty must make it immediately eligible
    for dispatch again: the in-flight spans were launched at the old,
    easier target and are now unlikely to solve it — treating the job as
    still covered would stall the raised request behind stale launches."""

    async def run():
        # A lone EASY job with two speculative launches in flight sits at
        # miss ≈ 0.018 < SPEC_MISS_FLOOR: _dispatch_next refuses it.
        b, gate, records = await _gated_recording_backend(max_batch=2, pipeline=2)
        r = WorkRequest(random_hash(), EASY)
        task = asyncio.ensure_future(b.generate(r))
        while len(records) < 2:
            await asyncio.sleep(0.01)
        assert b._dispatch_next() is None, "below-floor job must not dispatch"
        # The raise resets coverage: the very next dispatch decision must
        # pick the job up again (WITHOUT the reset it stays below floor).
        assert await b.raise_difficulty(r.block_hash, EASY + (1 << 50))
        rec3 = b._dispatch_next()
        assert rec3 is not None, "raised job was not re-dispatched"
        assert [j.block_hash for j in rec3.jobs] == [r.block_hash]
        gate.set()
        work = await task
        nc.validate_work(r.block_hash, work, EASY + (1 << 50))
        await b.close()

    asyncio.run(run())


def test_compilation_cache_populates(tmp_path, monkeypatch):
    """enable_compilation_cache must actually produce on-disk executables a
    restarted worker can reload — the cache exists to skip the per-shape
    compile wall (~16 s per Pallas launch shape at the default geometry)."""
    import jax
    import jax.numpy as jnp

    from tpu_dpow.utils import enable_compilation_cache

    prior = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    try:
        assert enable_compilation_cache(min_compile_secs=0.0) == str(tmp_path)
        jax.jit(lambda a: jnp.sin(a) @ a.T)(
            np.ones((32, 32), np.float32)
        ).block_until_ready()
        assert any(tmp_path.iterdir()), "no cache entry written"
    finally:  # global jax config: restore for the rest of the suite
        for k, v in prior.items():
            jax.config.update(k, v)


def test_enable_default_compilation_cache_env_contract(monkeypatch):
    """One cache, placed from outside: $JAX_COMPILATION_CACHE_DIR when it is
    set (and then no code sets another), else the fixed <checkout>/.jax_cache
    — never a home-directory or temp-named dir, which would never hit again
    on the next machine. Wired through jax's env-var knobs (children
    inherit) and, where jax is already imported, the live config."""
    import os

    import jax

    from tpu_dpow.utils import compilation_cache_dir, enable_compilation_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                "JAX_TRACEBACK_IN_LOCATIONS_LIMIT"):
        monkeypatch.delenv(var, raising=False)
    prior = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_traceback_in_locations_limit")}
    try:
        default = os.path.join(repo, ".jax_cache")
        assert compilation_cache_dir() == default
        assert enable_compilation_cache(min_compile_secs=0.5) == default
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == default
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0.5"
        assert jax.config.jax_compilation_cache_dir == default
        # No source paths in lowered modules: a kernel compiled from another
        # checkout must hit the same cache entry.
        assert jax.config.jax_traceback_in_locations_limit == 0

        # A directory placed from outside wins, in env and live config.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/custom/dir")
        assert compilation_cache_dir() == "/custom/dir"
        assert enable_compilation_cache() == "/custom/dir"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/custom/dir"
        assert jax.config.jax_compilation_cache_dir == "/custom/dir"
    finally:
        # monkeypatch restores the env vars it deleted above; the live
        # jax config is restored here.
        for k, v in prior.items():
            jax.config.update(k, v)


def test_mixed_load_rung_fairness_under_flood():
    """Adversarial mix (the benchmarks/fairness.py shape, deterministic):
    a sustained easy flood plus one unreachable-hard job. Round-robin rung
    service must give BOTH rungs a bounded share of launches — the hard job
    is never starved by the flood, and the flood never stalls behind the
    hard job's wide launches."""

    async def run():
        b = make_backend(run_steps=16, pipeline=2)
        launches = []
        orig = b._launch

        def traced(params, steps):
            launches.append(steps)
            return orig(params, steps)

        b._launch = traced
        await b.setup()

        hard = random_hash()
        t_hard = asyncio.ensure_future(b.generate(WorkRequest(hard, (1 << 64) - 2)))
        stop = asyncio.Event()

        async def flooder():
            while not stop.is_set():
                w = await b.generate(WorkRequest(random_hash(), EASY))
                assert w

        floods = [asyncio.ensure_future(flooder()) for _ in range(3)]
        await asyncio.sleep(0)
        launches.clear()  # measure only the mixed phase
        while len(launches) < 24:
            await asyncio.sleep(0.01)
        window = list(launches[:24])
        stop.set()
        for f in floods:
            f.cancel()
        await asyncio.gather(*floods, return_exceptions=True)
        await b.cancel(hard)
        with pytest.raises(WorkCancelled):
            await t_hard
        await b.close()

        # The hard rung launches NARROWED under contention (shared_steps_cap,
        # default run_steps/4 = 4) — a full-width 16 in the mixed window
        # would mean the flood waited half a second behind one launch.
        hard_n = sum(1 for s in window if s > 1)
        easy_n = sum(1 for s in window if s == 1)
        # A few full-width stragglers tolerated: a 16 can slip in while
        # every flooder is momentarily between requests — the hard rung is
        # then truly alone (no alive easy job), which by design gets full
        # width; the corpse-aware width policy widened that moment from
        # "drained pipe" to "only dead launches in the pipe", so gaps are
        # a bit likelier under CI/host contention. The regression signal
        # is gross: pre-cap, ~half the window was 16s.
        assert sum(1 for s in window if s == 16) <= 6, window
        # Round-robin over two live rungs → each gets ~half the launches;
        # a quarter is the regression bound (serving one rung only would
        # put the other at 0; flooder gaps under host load eat a few).
        assert hard_n >= len(window) // 4, window
        assert easy_n >= len(window) // 4, window
        # And no rung monopolizes: no long consecutive same-rung streaks
        # while both are pending (host-contention jitter gets one of slack,
        # and a flood-gap full-width launch can extend a hard streak by
        # one).
        run_len, worst, prev = 0, 0, None
        for s in window:
            run_len = run_len + 1 if s == prev else 1
            worst = max(worst, run_len)
            prev = s
        assert worst <= 5, window

    asyncio.run(run())


def test_shared_steps_cap_narrows_contended_launches():
    """A full-width launch parks run_steps windows of scan in front of every
    other rung on the serial device queue — the entire cancel-latency /
    mixed-load fairness tax. Under contention (another rung has live jobs)
    the hard rung must narrow to shared_steps_cap (default run_steps/4); a
    LONE hard job keeps the full-width single-round-trip launch (that launch
    IS the <50 ms design)."""

    async def run():
        b = make_backend(run_steps=16, pipeline=2)
        assert b.shared_steps_cap == 4
        launches = []
        orig = b._launch

        def traced(params, steps):
            launches.append(steps)
            return orig(params, steps)

        b._launch = traced
        await b.setup()
        launches.clear()
        hard = random_hash()
        t_hard = asyncio.ensure_future(b.generate(WorkRequest(hard, (1 << 64) - 2)))
        t_easy = asyncio.ensure_future(b.generate(WorkRequest(random_hash(), EASY)))
        while len(launches) < 2:
            await asyncio.sleep(0.01)
        # Round-robin starts at the easy rung; the hard launch right behind
        # it is contended, so it is capped at 4, not 16.
        assert launches[0] == 1 and launches[1] == 4, launches
        assert await t_easy
        await b.cancel(hard)
        with pytest.raises(WorkCancelled):
            await t_hard
        # While the pipe stayed busy, every successor was capped — no 16
        # ever queued behind in-flight work.
        assert launches.count(16) == 0, launches
        # A fresh hard job arriving on a DRAINED pipe gets the full-width
        # single-round-trip head launch back.
        await asyncio.sleep(0.3)  # in-flight CPU launches drain
        launches.clear()
        h2 = random_hash()
        t2 = asyncio.ensure_future(b.generate(WorkRequest(h2, (1 << 64) - 2)))
        while not launches:
            await asyncio.sleep(0.01)
        assert launches[0] == 16, launches
        await b.cancel(h2)
        with pytest.raises(WorkCancelled):
            await t2
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_speculative_successor_launch_is_narrow():
    """A pipelined successor for an already-covered job is pure speculation
    (it only hides the readback bubble from the unlucky tail) — it must be
    narrowed to shared_steps_cap: a second full-width launch would double
    the wait for any arrival or cancel behind it for, at most, one round
    trip of hidden latency."""
    import math

    async def run():
        b = make_backend(run_steps=16, pipeline=2)
        # Difficulty whose 2x-median wants ~12 windows: _steps_for picks the
        # 16 rung, and one full launch covers the job to miss ≈ 0.16 —
        # below SPEC_MISS_THRESHOLD (successor is speculative), above
        # SPEC_MISS_FLOOR (successor still allowed).
        p = math.log(2) / (6 * b.chunk)
        d = (1 << 64) - int(p * (1 << 64))
        assert b._steps_for(d) == 16
        launches = []
        orig = b._launch

        def traced(params, steps):
            launches.append(steps)
            return orig(params, steps)

        b._launch = traced
        await b.setup()
        launches.clear()
        work = await b.generate(WorkRequest(random_hash(), d))
        assert work
        await b.close()
        # First dispatch: lone uncovered job, full width. Its pipelined
        # successor (dispatched in the same engine pass): speculative → 4.
        assert launches[0] == 16, launches
        if len(launches) > 1:  # the job can solve before a successor runs
            assert launches[1] == 4, launches

    asyncio.run(asyncio.wait_for(run(), 30))


def test_fresh_head_full_width_behind_dead_launches():
    """A launch whose every covered job was resolved or cancelled while it
    was on the wire still occupies a pipeline slot — but it must not demote
    the next arrival's head launch to successor width. The fresh arrival is
    the effective head of the queue (nothing live executes in front of it),
    and its full width is what solves it in one round trip instead of
    chaining capped passes behind a corpse (measured on-chip r4: 83 ms p50
    queue-wait tax on sequential traffic)."""
    import threading

    async def run():
        b = make_backend(run_steps=16, pipeline=2)
        b.record_timeline = True
        await b.setup()
        lock = threading.Lock()
        gates = [threading.Event() for _ in range(8)]
        launches = []
        real_launch = b._launch

        def gated(params, steps):
            with lock:
                gate = gates[len(launches)]
                launches.append(steps)
            if not gate.wait(timeout=10):
                raise TimeoutError("per-launch gate never released in 10s")
            return real_launch(params, steps)

        b._launch = gated
        try:
            hard = random_hash()
            t1 = asyncio.ensure_future(
                b.generate(WorkRequest(hard, (1 << 64) - 2))
            )
            while len(launches) < 2:  # head + capped successor in flight
                await asyncio.sleep(0.01)
            assert launches == [16, 4], launches
            # Both in-flight launches become corpses; the successor (gate
            # 1) stays physically in flight across the next dispatch.
            await b.cancel(hard)
            with pytest.raises(WorkCancelled):
                await t1
            h2 = random_hash()
            t2 = asyncio.ensure_future(
                b.generate(WorkRequest(h2, (1 << 64) - 2))
            )
            gates[0].set()  # head returns; run loop refills the pipe
            while len(launches) < 3:
                await asyncio.sleep(0.01)
            # Old policy: len(inflight)=1 (the corpse) -> capped 4. The
            # corpse serves nothing, so the fresh head keeps full width.
            assert launches[2] == 16, launches
            await b.cancel(h2)
            with pytest.raises(WorkCancelled):
                await t2
        finally:
            for g in gates:
                g.set()
        # The timeline (stamped at result-apply, FIFO) must record the
        # PHYSICAL queue depth: the overhead decomposition buckets
        # head-vs-successor device time by what is actually in front on
        # the device — the corpse counts, even though the width policy
        # ignores it.
        def stamped():
            return [t["inflight"] for kind, t in b.timeline if kind == "launch"]

        while len(stamped()) < 3:
            await asyncio.sleep(0.01)
        assert stamped()[2] == 1, stamped()
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_fresh_demand_dispatches_while_head_launch_in_flight():
    """A fresh request arriving while the oldest launch's readback is on
    the wire must be dispatched into a free pipeline slot immediately —
    the engine loop's await is wakeup-interruptible. Before this, the loop
    sat blocked in await and the fresh head launch started only after the
    full wire round trip (the second half of the r4 83 ms queue-wait tax)."""
    import threading

    async def run():
        # pipeline=3 with one EASY job fills exactly two slots (head +
        # one speculative re-scan; the floor stops a third — pinned by
        # test_pipeline_idle_speculation_kept_for_lone_job), leaving one
        # slot free while the head is in flight.
        b = make_backend(pipeline=3)
        await b.setup()
        lock = threading.Lock()
        gates = [threading.Event() for _ in range(8)]
        launches = []
        real_launch = b._launch

        def gated(params, steps):
            with lock:
                gate = gates[len(launches)]
                launches.append(steps)
            if not gate.wait(timeout=10):
                raise TimeoutError("per-launch gate never released in 10s")
            return real_launch(params, steps)

        b._launch = gated
        try:
            r1 = WorkRequest(random_hash(), EASY)
            t1 = asyncio.ensure_future(b.generate(r1))
            while len(launches) < 2:
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
            assert len(launches) == 2, launches  # speculation floor held
            r2 = WorkRequest(random_hash(), EASY)
            t2 = asyncio.ensure_future(b.generate(r2))
            # The head launch is still gated; the new job's launch must
            # appear anyway.
            deadline = asyncio.get_running_loop().time() + 5.0
            while len(launches) < 3:
                assert asyncio.get_running_loop().time() < deadline, (
                    "fresh job not dispatched while head launch in flight",
                    launches,
                )
                await asyncio.sleep(0.01)
        finally:
            for g in gates:
                g.set()
        for r, w in zip((r1, r2), await asyncio.gather(t1, t2)):
            nc.validate_work(r.block_hash, w, EASY)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_timeline_records_launch_stages_and_solves():
    """record_timeline must stamp every launch's stage boundaries (the
    overhead decomposition in benchmarks/overhead.py reads them) and one
    solve record per resolved job — and stay empty when off (the default:
    no per-launch cost for production)."""

    async def run():
        b = make_backend()
        b.record_timeline = True
        await b.setup()
        works = await asyncio.gather(
            *(b.generate(WorkRequest(random_hash(), EASY)) for _ in range(3))
        )
        assert all(works)
        tl = list(b.timeline)
        await b.close()
        launches = [t for k, t in tl if k == "launch"]
        solves = [t for k, t in tl if k == "solve"]
        assert launches and len(solves) == 3
        for t in launches:
            assert (
                t["t_dispatch"] <= t["t_thread"] <= t["t_done"] <= t["t_apply"]
            ), t
            assert t["batch"] >= 1 and t["steps"] >= 1 and t["inflight"] >= 0
        for s in solves:
            assert 0 <= s["queue_wait"] <= s["total"]
            # Every solve consumed at least one applied launch; the count
            # feeds latency.py's launches-per-solve histogram. Counted at
            # apply, so an in-flight speculative successor cannot inflate
            # it past the solving readback's position.
            assert s["launches"] >= 1

        b2 = make_backend()
        await b2.setup()
        await b2.generate(WorkRequest(random_hash(), EASY))
        assert not list(b2.timeline)  # off by default
        await b2.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_step_ladder_options():
    """x2 ladder halves the run-length quantum; x4 stays the default."""
    b4 = make_backend(run_steps=16)
    assert b4._step_counts() == [1, 4, 16]
    b2 = make_backend(run_steps=16, step_ladder="x2")
    assert b2._step_counts() == [1, 2, 4, 8, 16]
    # _steps_for picks the finer rung when available: a difficulty whose
    # 2x-median lands between 1 and 4 windows gets 2 on the x2 ladder.
    target = None
    for exp in range(10, 30):
        d = (1 << 64) - (1 << exp)
        if 1 < 2 * 0.693 * (2**64 - d) ** -1 * 2**64 / b2.chunk <= 2:
            target = d
            break
    if target is not None:
        assert b2._steps_for(target) == 2
        assert b4._steps_for(target) == 4
    with pytest.raises(WorkError):
        make_backend(step_ladder="bogus")


def test_step_ladder_x2_generates_valid_work():
    async def run():
        b = make_backend(run_steps=4, step_ladder="x2")
        await b.setup()
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()

    asyncio.run(run())


def test_pipelined_launch_timeout_fails_clean_and_recovers():
    """With two launches in flight, the OLDEST timing out must fail every
    waiter with WorkError, abandon both wedged threads with the executor,
    and leave the engine restartable — a straggler thread completing later
    must not corrupt the fresh engine's state."""
    import threading
    import time as _time

    async def run():
        b = make_backend(launch_timeout=0.3, pipeline=2)
        await b.setup()
        real_launch = b._launch
        gate = threading.Event()  # stragglers park here, released at the end
        wedge = {"on": True}

        def wedged(params, steps):
            if wedge["on"]:
                gate.wait(timeout=10)
            return real_launch(params, steps)

        b._launch = wedged
        # Unreachable-hard job keeps BOTH pipeline slots occupied.
        with pytest.raises(WorkError):
            await b.generate(WorkRequest(random_hash(), (1 << 64) - 1))
        wedge["on"] = False
        # Fresh engine on a fresh executor solves immediately...
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        # ...and releasing the two abandoned straggler threads afterwards
        # must not disturb anything (their results go to dropped futures).
        gate.set()
        await asyncio.sleep(0.1)
        h2 = random_hash()
        work2 = await b.generate(WorkRequest(h2, EASY))
        nc.validate_work(h2, work2, EASY)
        await b.close()

    asyncio.run(run())


# -- device fan: per-device shards, scan clocks, attribution ---------------
# The fan engine sub-partitions one WorkRequest's nonce shard into disjoint
# per-device ranges (the fleet partition idiom one level down) and keeps
# per-device scan clocks on the injectable resilience Clock, so fleet
# re-covers and EMA attribution work per DEVICE, not just per process.


def test_fan_cover_range_rebases_all_device_shards():
    """A fleet cover_range re-cover against the multi-device engine must
    rebase EVERY device shard into the orphaned range — not just device 0.
    (A single-frontier rebase would leave 7 of 8 sub-ranges scanning the
    dead worker's old region.)"""
    from tpu_dpow.ops import search as ops_search
    from tpu_dpow.resilience.clock import FakeClock

    async def run():
        n = 4
        b = make_backend(devices=n, device_shard="split", clock=FakeClock())
        await b.setup()
        seen = []  # per-launch [n] device base snapshots
        real_launch = b._launch

        def recording(params, steps):
            if params.ndim == 3:
                bases = [
                    (int(params[d, 0, ops_search.BASE_HI]) << 32)
                    | int(params[d, 0, ops_search.BASE_LO])
                    for d in range(params.shape[0])
                ]
                seen.append(bases)
            return real_launch(params, steps)

        b._launch = recording
        h = random_hash()
        start_a, length = 1 << 30, 1 << 20
        stride = length // n
        t = asyncio.ensure_future(
            b.generate(WorkRequest(h, (1 << 64) - 2, nonce_range=(start_a, length)))
        )
        while not seen:
            await asyncio.sleep(0.01)
        # Initial partition: device d scans from start_a + d*stride.
        assert seen[0] == [start_a + d * stride for d in range(n)], seen[0]
        start_b = 1 << 50
        assert await b.cover_range(h, (start_b, length))
        deadline = asyncio.get_running_loop().time() + 10.0
        want = [start_b + d * stride for d in range(n)]
        while not any(s == want for s in seen):
            assert asyncio.get_running_loop().time() < deadline, (
                "no launch rebased every device shard into the new range",
                seen[-3:],
            )
            await asyncio.sleep(0.01)
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_fan_win_attributed_with_device_scan_clock():
    """A win landing in device k's sub-range must EMA-attribute with THAT
    device's scan clock (FakeClock-driven): hashes = nonces scanned from
    k's shard start, elapsed = k's first-dispatch → apply on the injectable
    clock — the engine-level twin of the fleet registry's observe_result."""
    import hashlib
    import threading

    from tpu_dpow import obs
    from tpu_dpow.resilience.clock import FakeClock

    def value_of(h_bytes, nonce):
        return int.from_bytes(
            hashlib.blake2b(
                nonce.to_bytes(8, "little") + h_bytes, digest_size=8
            ).digest(),
            "little",
        )

    async def run():
        n = 4
        clock = FakeClock()
        b = make_backend(devices=n, device_shard="split", clock=clock)
        await b.setup()
        # Host-side: find the MAX-value nonce across every device's first
        # window and target exactly it — the unique hit of the first fanned
        # launch, so the winning device is deterministic.
        h = random_hash()
        hb = bytes.fromhex(h)
        start, length = 1 << 40, n * (1 << 20)
        stride = length // n
        best = None
        for d in range(n):
            for j in range(b.chunk_per_shard):
                v = value_of(hb, start + d * stride + j)
                if best is None or v > best[0]:
                    best = (v, d, j)
        diff, k, off = best
        gate = threading.Event()
        real_launch = b._launch

        def gated(params, steps):
            if not gate.wait(timeout=10):
                raise TimeoutError("fan launch gate never released")
            return real_launch(params, steps)

        b._launch = gated
        wins_before = (
            obs.snapshot()
            .get("dpow_backend_device_wins_total", {})
            .get("series", {})
            .get(str(k), 0)
        )
        task = asyncio.ensure_future(
            b.generate(WorkRequest(h, diff, nonce_range=(start, length)))
        )
        # Let the engine dispatch (stamping the per-device scan clocks at
        # t=0), advance the fake clock 2 s, then release the launch.
        while not b._jobs or next(iter(b._jobs.values())).dev_t0 is None:
            await asyncio.sleep(0.01)
        await clock.advance(2.0)
        gate.set()
        work = await asyncio.wait_for(task, timeout=20)
        nc.validate_work(h, work, diff)
        assert b.last_win is not None
        assert b.last_win["device"] == k, b.last_win
        assert b.last_win["hashes"] == off + 1, b.last_win
        assert b.last_win["elapsed"] == pytest.approx(2.0), b.last_win
        assert b.device_ema[k] == pytest.approx((off + 1) / 2.0)
        assert all(b.device_ema[d] == 0.0 for d in range(n) if d != k)
        wins_after = (
            obs.snapshot()["dpow_backend_device_wins_total"]["series"][str(k)]
        )
        assert wins_after == wins_before + 1
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_fan_raise_difficulty_applies_to_every_device_shard():
    """raise_difficulty against the fan engine must retarget EVERY device
    shard: the next fanned launch carries the raised difficulty words in
    all device slices, and coverage resets so the raised job re-dispatches
    immediately (same contract as the single-device engine)."""
    from tpu_dpow.ops import search as ops_search
    from tpu_dpow.resilience.clock import FakeClock

    async def run():
        n = 4
        b = make_backend(devices=n, device_shard="split", clock=FakeClock())
        await b.setup()
        diffs_seen = []  # per-launch [n] difficulty snapshots
        real_launch = b._launch

        def recording(params, steps):
            if params.ndim == 3:
                diffs_seen.append([
                    (int(params[d, 0, ops_search.DIFF_HI]) << 32)
                    | int(params[d, 0, ops_search.DIFF_LO])
                    for d in range(params.shape[0])
                ])
            return real_launch(params, steps)

        b._launch = recording
        h = random_hash()
        low = (1 << 64) - (1 << 30)
        raised = (1 << 64) - (1 << 20)
        t = asyncio.ensure_future(b.generate(WorkRequest(h, low)))
        while not diffs_seen:
            await asyncio.sleep(0.01)
        assert diffs_seen[0] == [low] * n
        assert await b.raise_difficulty(h, raised)
        deadline = asyncio.get_running_loop().time() + 10.0
        while not any(ds == [raised] * n for ds in diffs_seen):
            assert asyncio.get_running_loop().time() < deadline, diffs_seen[-3:]
            await asyncio.sleep(0.01)
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_fan_per_device_metrics_exported():
    """The fan exports the dpow_backend_device_* families with one series
    per device (docs/observability.md catalogue): launches, scanned
    nonces, last-launch H/s, busy fraction in [0, 1]."""
    from tpu_dpow import obs

    async def run():
        n = 8
        snap0 = obs.snapshot()

        def series(snap, fam):
            return snap.get(fam, {}).get("series", {})

        launches0 = dict(series(snap0, "dpow_backend_device_launches_total"))
        b = make_backend(devices=n)
        await b.setup()
        reqs = [WorkRequest(random_hash(), EASY) for _ in range(3)]
        works = await asyncio.gather(*(b.generate(r) for r in reqs))
        for r, w in zip(reqs, works):
            nc.validate_work(r.block_hash, w, EASY)
        await b.close()
        snap = obs.snapshot()
        for d in range(n):
            lab = str(d)
            assert (
                series(snap, "dpow_backend_device_launches_total").get(lab, 0)
                > launches0.get(lab, 0)
            ), f"device {d} recorded no launches"
            assert series(snap, "dpow_backend_device_hashes_total").get(lab, 0) > 0
            busy = series(snap, "dpow_backend_device_busy_fraction").get(lab)
            assert busy is not None and 0.0 <= busy <= 1.0
            assert series(snap, "dpow_backend_device_hash_rate_hs").get(lab, 0) >= 0

    asyncio.run(asyncio.wait_for(run(), 60))


def test_work_handler_fleet_recover_rebases_fan_engine():
    """Fleet re-cover through the client dispatch boundary: a duplicate
    work message carrying a DIFFERENT shard must rebase the RUNNING fan
    job's every device sub-range (work_handler → backend.cover_range) and
    count as 'recovered'."""
    from tpu_dpow.client.work_handler import WorkHandler
    from tpu_dpow.ops import search as ops_search

    async def run():
        n = 4
        b = make_backend(devices=n, device_shard="split")
        seen = []
        real_launch = b._launch

        def recording(params, steps):
            if params.ndim == 3:
                seen.append([
                    (int(params[d, 0, ops_search.BASE_HI]) << 32)
                    | int(params[d, 0, ops_search.BASE_LO])
                    for d in range(params.shape[0])
                ])
            return real_launch(params, steps)

        b._launch = recording

        async def on_result(request, work):
            pass

        handler = WorkHandler(b, on_result, concurrency=2)
        await handler.start()
        h = random_hash()
        start_a, start_b, length = 1 << 30, 1 << 50, 1 << 20
        stride = length // n
        await handler.queue_work(
            WorkRequest(h, (1 << 64) - 2, nonce_range=(start_a, length))
        )
        while not seen:
            await asyncio.sleep(0.01)
        await handler.queue_work(
            WorkRequest(h, (1 << 64) - 2, nonce_range=(start_b, length))
        )
        assert handler.stats["recovered"] == 1
        want = [start_b + d * stride for d in range(n)]
        deadline = asyncio.get_running_loop().time() + 10.0
        while not any(s == want for s in seen):
            assert asyncio.get_running_loop().time() < deadline, seen[-3:]
            await asyncio.sleep(0.01)
        await handler.queue_cancel(h)
        await handler.stop()

    asyncio.run(asyncio.wait_for(run(), 30))


def test_plain_weak_hit_cannot_rewind_a_cover_range_rebase():
    """Single-device twin of the fan's epoch fence: a launch dispatched at
    the OLD base whose hit goes weak (target raised mid-flight) must NOT
    rewind the frontier after a cover_range re-cover — the rebase into the
    orphaned range wins, and the engine keeps scanning there."""
    import hashlib
    import threading

    from tpu_dpow.ops import search as ops_search

    def value_of(h_bytes, nonce):
        return int.from_bytes(
            hashlib.blake2b(
                nonce.to_bytes(8, "little") + h_bytes, digest_size=8
            ).digest(),
            "little",
        )

    async def run():
        b = make_backend()  # plain path: no fan, no mesh
        await b.setup()
        h = random_hash()
        hb = bytes.fromhex(h)
        base_a, base_b = 1 << 30, 1 << 50
        # The max-value nonce of the first window is the unique hit at
        # difficulty == its value; raising to near-unreachable afterwards
        # turns exactly that hit weak at apply time.
        v_max, j = max(
            (value_of(hb, base_a + j), j) for j in range(b.chunk)
        )
        gate = threading.Event()
        bases = []
        real_launch = b._launch

        def gated(params, steps):
            bases.append(
                (int(params[0, ops_search.BASE_HI]) << 32)
                | int(params[0, ops_search.BASE_LO])
            )
            if not gate.wait(timeout=10):
                raise TimeoutError("launch gate never released")
            return real_launch(params, steps)

        b._launch = gated
        t = asyncio.ensure_future(
            b.generate(WorkRequest(h, v_max, nonce_range=(base_a, 1 << 20)))
        )
        while not bases:
            await asyncio.sleep(0.01)
        assert bases[0] == base_a
        # Let the engine finish filling its pipeline against the gate so
        # every pre-cover dispatch is recorded before the snapshot.
        await asyncio.sleep(0.2)
        n_pre = len(bases)
        # Raise past every nonce, then re-cover to the far range — both
        # while launch 1 (aimed at base_a, carrying the weak hit) is wired.
        assert await b.raise_difficulty(h, (1 << 64) - 2)
        assert await b.cover_range(h, (base_b, 1 << 20))
        gate.set()
        # The weak hit applies; the frontier must stay in the re-covered
        # range: every later dispatch starts at/after base_b, never at the
        # rewind target base_a + j + 1.
        deadline = asyncio.get_running_loop().time() + 10.0
        while len(bases) < n_pre + 3:
            assert asyncio.get_running_loop().time() < deadline, bases
            await asyncio.sleep(0.01)
        post = bases[n_pre:]
        assert base_a + j + 1 not in post, (bases, j)
        assert all(x >= base_b for x in post), post
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))
