"""Device fault domains (ISSUE 12): hung-launch watchdog, range
evacuation, quarantine + probe re-admission, and the zero-healthy-devices
escalation — docs/resilience.md "Device fault domains".

The fault under test is the one production TPU serving actually sees: ONE
device stops polling (preemption, XLA hang, wedged io_callback) while its
siblings keep going — so the whole pmap launch never returns, and without
fault domains the batch rows it pins are stranded until every waiter's
deadline. Chaos drives it through the FaultyDevice seam at the
launch-thread / control-poll boundaries (tpu_dpow/chaos/device.py), and
every timer rides FakeClock, so hours of suspect/probe choreography play
out in milliseconds.

Planted-difficulty technique (test_persistent.py): the floor is the max
work value over every nonce any device can scan BEFORE the interesting
moment, so the solve can only come from the region evacuated after it.
"""

import asyncio
import itertools

import numpy as np
import pytest

from tpu_dpow import obs
from tpu_dpow.backend import (
    DevicesExhausted,
    WorkBackend,
    WorkCancelled,
    WorkError,
)
from tpu_dpow.backend.jax_backend import JaxWorkBackend
from tpu_dpow.chaos import FaultyDevice
from tpu_dpow.models import WorkRequest
from tpu_dpow.ops import control as ctl
from tpu_dpow.resilience import (
    FailoverBackend,
    FakeClock,
    HEALTHY,
    QUARANTINED,
    SUSPECT,
)
from tpu_dpow.resilience.devfault import DeviceFaultDomains
from tpu_dpow.utils import nanocrypto as nc


RNG = np.random.default_rng(12)
EASY = 0xFFF0000000000000
UNREACH = (1 << 64) - 2
_MASK64 = (1 << 64) - 1


#: planted-difficulty arithmetic on raw nonces (shared formula — a copy
#: diverging by one byte would plant the solution in the wrong region)
val = nc.work_value_int


def plant_above(h: bytes, start: int, floor: int) -> int:
    return next(n for n in itertools.count(start) if val(h, n) > floor)


def random_hash() -> str:
    return RNG.bytes(32).hex().upper()


def _metric(name, *labels):
    series = obs.snapshot().get(name, {}).get("series", {})
    key = ",".join(labels)
    v = series.get(key, 0)
    return v.get("count", 0) if isinstance(v, dict) else v


async def _spin_until(cond, timeout=30.0, msg="condition"):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, (
            f"timed out waiting for {msg}"
        )
        await asyncio.sleep(0.005)


# -- DeviceFaultDomains unit ------------------------------------------------


def test_fault_domain_state_machine():
    """healthy → suspect → quarantined → (probe fails → stays) →
    (probe succeeds → healthy), with single-probe admission and the
    health/transition metrics moving."""
    clock = FakeClock()
    dfd = DeviceFaultDomains(
        4, suspect_after=10.0, probe_interval=30.0, clock=clock, name="t1"
    )
    assert dfd.healthy_devices() == [0, 1, 2, 3]
    assert dfd.mark_suspect(2)
    assert not dfd.mark_suspect(2), "suspect must be edge-triggered"
    assert dfd.state(2) == SUSPECT
    assert dfd.healthy_devices() == [0, 1, 3]
    dfd.quarantine(2)
    assert dfd.state(2) == QUARANTINED
    assert not dfd.exhausted()
    # no probe before the interval elapses
    assert not dfd.probe_due(2)
    clock._now += 31.0
    assert dfd.probe_due(2)
    assert not dfd.probe_due(2), "half-open admits exactly one probe"
    dfd.probe_result(2, False)
    assert dfd.state(2) == QUARANTINED
    assert not dfd.probe_due(2), "failed probe re-opens the full interval"
    clock._now += 31.0
    assert dfd.probe_due(2)
    dfd.probe_result(2, True)
    assert dfd.state(2) == HEALTHY
    assert dfd.healthy_devices() == [0, 1, 2, 3]
    snap = obs.snapshot()["dpow_backend_quarantine_total"]["series"]
    assert snap.get("healthy->suspect", 0) >= 1
    assert snap.get("suspect->quarantined", 0) >= 1
    assert snap.get("quarantined->healthy", 0) >= 1
    # exhaustion: quarantine everyone
    for d in (0, 1, 3):
        dfd.mark_suspect(d)
        dfd.quarantine(d)
    dfd.mark_suspect(2)
    dfd.quarantine(2)
    assert dfd.exhausted() and dfd.healthy_devices() == []


# -- FaultyDevice seam ------------------------------------------------------


def test_faulty_device_seam_maps_physical_index_and_releases():
    """The poll hook sees the PHYSICAL fan index through the control
    block's fan_map, injections are recorded/counted, and uninstall always
    lifts every hang (no stranded device threads)."""

    class Tick:
        t = 0.0

        def time(self):
            return self.t

    c = ctl.LaunchControl(1, clock=Tick(), n_dev=2, fan_map=[5, 7])
    slot = ctl.register(c)
    fd = FaultyDevice()
    try:
        fd.install()
        fd.slow_poll(7, 0.0)
        ctl.poll_slot(slot, 1, 3, np.array([False]))  # axis 1 == physical 7
        assert ("poll", 7, 3) in fd.events
        hung = fd._rules  # hang with no release: uninstall must lift it
        fd.hang_at_poll(5, 0)
        assert 5 in hung
    finally:
        fd.uninstall()
        ctl.release(slot)
    assert not fd._rules, "uninstall must clear and release every rule"
    assert ctl._poll_hook is None and ctl._launch_hook is None


# -- the chaos acceptance test ---------------------------------------------


def test_hung_device_evacuation_quarantine_and_probe_readmission():
    """THE acceptance scenario (FakeClock, 8-device fan, persistent):
    device 3 hangs mid-launch at its control poll → the watchdog declares
    it suspect, evacuates its uncovered remainder exactly once
    (dpow_backend_evacuations_total == 1) onto the 7 healthy devices, the
    request is served with a bit-valid winner from the evacuated range
    inside its deadline, the zombie wake-up cannot rewind the evacuated
    frontier (epoch fence), and the device is re-admitted only after a
    successful probe."""

    async def run():
        clock = FakeClock()
        b = JaxWorkBackend(
            kernel="xla", sublanes=8, iters=8, devices=8, max_batch=1,
            run_mode="persistent", persistent_steps=4, control_poll_steps=1,
            pipeline=1, clock=clock,
            device_suspect_after=10.0, device_probe_interval=30.0,
        )
        await b.setup()
        span_dev = b.chunk_per_shard  # one window per device per poll
        assert span_dev == 8 * 128 * 8

        hx = random_hash()
        h = bytes.fromhex(hx)
        S, stride = 1 << 40, 1 << 20
        L = 8 * stride
        launch_span = 4 * span_dev  # persistent_steps windows per device
        # Floor over EVERYTHING scannable before the evacuation: the 7
        # healthy devices' full launch spans and the hung device's two
        # pre-hang windows (it blocks at its k=2 poll).
        pre = []
        for d in range(8):
            width = launch_span if d != 3 else 2 * span_dev
            pre.extend(range(S + d * stride, S + d * stride + width))
        floor = max(val(h, n) for n in pre)
        f3 = S + 3 * stride + span_dev  # base + 1 provably-dry window
        planted = plant_above(h, f3, floor)
        diff = val(h, planted)

        evac_before = _metric("dpow_backend_evacuations_total", "stalled_poll")
        with FaultyDevice() as fd:
            fd.hang_at_poll(3, 2)
            t = asyncio.ensure_future(
                b.generate(WorkRequest(hx, diff, nonce_range=(S, L)))
            )
            # the launch is live, device 3 is wedged at its k=2 poll, and
            # every healthy device has cleared its final poll block
            await _spin_until(
                lambda: any(r.control is not None for r in b._inflight),
                msg="persistent launch",
            )
            rec = next(r for r in b._inflight if r.control is not None)
            await _spin_until(
                lambda: ("poll", 3, 2) in fd.events, msg="device 3 hang"
            )
            await _spin_until(
                lambda: all(
                    rec.control.device_accounted(s, 4, 1)
                    for s in range(8) if s != 3
                ),
                msg="healthy devices accounted",
            )
            assert not rec.control.device_accounted(3, 4, 1)
            assert rec.control.confirmed_no_hit_windows(0, 3, 1) == 1

            # one suspect_after elapses: suspect → evacuate → quarantine
            await clock.advance(13.0)
            assert b._dfd.state(3) == QUARANTINED
            assert rec.abandoned and rec not in b._inflight
            assert b._fan_active == [0, 1, 2, 4, 5, 6, 7]
            assert (
                _metric("dpow_backend_evacuations_total", "stalled_poll")
                - evac_before
            ) == 1
            assert _metric("dpow_backend_device_health", "3") == 2.0
            job = b._jobs[hx]
            epoch_evac = job.dev_epoch
            # the evacuated partition starts at the dead device's provable
            # frontier: base + 1 confirmed-dry window (the degraded-width
            # launch the engine dispatched right away may have advanced
            # the frontiers speculatively by up to one launch span)
            assert job.part_start == f3
            assert (
                (min(job.dev_bases[d] for d in b._fan_active) - f3) & _MASK64
            ) <= launch_span

            # ZOMBIE wake-up: device 3 resumes against the kill fence —
            # the wedged launch drains, is never applied, and cannot touch
            # the evacuated frontier
            fd.release(3)
            await _spin_until(
                lambda: rec.thread_done.is_set(), msg="zombie drain"
            )
            assert job.dev_epoch == epoch_evac, "zombie moved the epoch"
            assert all(
                ((job.dev_bases[d] - f3) & _MASK64) < L
                for d in b._fan_active
            ), "zombie rewound an evacuated frontier"

            # the request is served from the evacuated range, bit-valid,
            # well inside its deadline — at degraded width
            work = await asyncio.wait_for(t, 60)
            nonce = int(work, 16)
            nc.validate_work(hx, work, diff)
            assert f3 <= nonce < S + L + launch_span, (
                f"winner {work} not from the evacuated remainder"
            )

            # a later sweep must NOT evacuate again (edge-triggered)
            await clock.advance(13.0)
            assert (
                _metric("dpow_backend_evacuations_total", "stalled_poll")
                - evac_before
            ) == 1

            # re-admission: only after a probe interval AND a successful
            # single-probe launch (the fault is lifted, so it succeeds).
            # Advance only until the probe SPAWNS — pushing time past its
            # own fake-clock bound would fail a probe that merely needed
            # real milliseconds of compile — then let it finish real-time.
            assert b._dfd.state(3) == QUARANTINED
            deadline = asyncio.get_running_loop().time() + 60
            while b._dfd.state(3) != HEALTHY and not any(
                not p.done() for p in b._probe_tasks.values()
            ):
                assert asyncio.get_running_loop().time() < deadline
                await clock.advance(2.6)
            await _spin_until(
                lambda: b._dfd.state(3) == HEALTHY, timeout=60,
                msg="probe re-admission",
            )
            assert b._fan_active == list(range(8))
            assert _metric("dpow_backend_device_health", "3") == 0.0
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 180))


# -- zero-healthy-devices escalation (plain engine) -------------------------


def test_exhausted_devices_fail_fast_and_probe_readmits():
    """Plain persistent engine, its ONE device dies: the live waiter fails
    with DevicesExhausted immediately (no hang-timeout wait), new
    generates refuse on arrival, and after the fault lifts a successful
    probe re-admits the device and the engine serves again."""

    async def run():
        clock = FakeClock()
        b = JaxWorkBackend(
            kernel="xla", sublanes=8, iters=8, run_mode="persistent",
            persistent_steps=4, control_poll_steps=1, pipeline=1,
            clock=clock, device_suspect_after=5.0, device_probe_interval=20.0,
        )
        await b.setup()
        with FaultyDevice() as fd:
            fd.hang_at_poll(0, 1)
            h = random_hash()
            t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
            await _spin_until(
                lambda: any(("poll", 0, k) in fd.events for k in (1, 2)),
                msg="device hang",
            )
            await clock.advance(7.0)
            with pytest.raises(DevicesExhausted):
                await t
            # escalation is immediate for NEW arrivals too
            with pytest.raises(DevicesExhausted):
                await b.generate(WorkRequest(random_hash(), EASY))
            assert b._dfd.exhausted()
            fd.release(0)
            await clock.advance(21.0)
            await _spin_until(
                lambda: b._dfd.state(0) == HEALTHY, msg="probe re-admission"
            )
            work = await asyncio.wait_for(
                b.generate(WorkRequest(random_hash(), EASY)), 30
            )
            assert len(work) == 16
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


# -- failover chain wiring --------------------------------------------------


def test_failover_trips_breaker_on_devices_exhausted():
    """FailoverBackend escalates the zero-healthy-devices signal
    immediately: the fallback serves the same request, the cause counter
    distinguishes devices_exhausted from hang, and the dead engine's
    breaker is OPEN at once (the next request never touches it)."""

    class Dead(WorkBackend):
        calls = 0

        async def setup(self):
            pass

        async def generate(self, request):
            Dead.calls += 1
            raise DevicesExhausted("all 8 device(s) quarantined")

        async def cancel(self, block_hash):
            pass

    class Brute(WorkBackend):
        async def setup(self):
            pass

        async def generate(self, request):
            h = bytes.fromhex(request.block_hash)
            w = 0
            while val(h, w) < request.difficulty:
                w += 1
            return f"{w:016x}"

        async def cancel(self, block_hash):
            pass

    async def run():
        clock = FakeClock()
        before = _metric(
            "dpow_client_backend_failover_total", "dead", "devices_exhausted"
        )
        chain = FailoverBackend(
            [("dead", Dead()), ("steady", Brute())],
            failure_threshold=3, reset_timeout=60.0, hang_timeout=30.0,
            clock=clock,
        )
        await chain.setup()
        h = random_hash()
        work = await chain.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        assert Dead.calls == 1
        assert chain.breakers["dead"].state == "open", (
            "devices_exhausted must trip the breaker outright"
        )
        # second request skips the dead engine without probing it (and
        # without counting another failover — it never touched the engine)
        await chain.generate(WorkRequest(random_hash(), EASY))
        assert Dead.calls == 1
        assert (
            _metric(
                "dpow_client_backend_failover_total",
                "dead", "devices_exhausted",
            ) - before
        ) == 1
        assert _metric(
            "dpow_client_backend_failover_total", "dead", "hang"
        ) == 0

    asyncio.run(asyncio.wait_for(run(), 30))


# -- bounded close against a wedged launch thread ---------------------------


def test_close_returns_within_bound_and_counts_leaked_thread():
    """close() with a truly wedged launch thread: the Clock-driven join
    bound expires, the slot is kill-fenced, the thread is detached and
    counted in dpow_backend_launch_threads_leaked_total — shutdown is
    never blocked forever."""

    async def run():
        clock = FakeClock()
        b = JaxWorkBackend(
            kernel="xla", sublanes=8, iters=8, run_mode="persistent",
            persistent_steps=4, control_poll_steps=1, pipeline=1,
            clock=clock, device_suspect_after=1000.0, close_join_timeout=5.0,
        )
        await b.setup()
        before = _metric("dpow_backend_launch_threads_leaked_total")
        with FaultyDevice() as fd:
            fd.hang_at_poll(0, 1)
            h = random_hash()
            t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
            await _spin_until(
                lambda: any(("poll", 0, k) in fd.events for k in (1, 2)),
                msg="device hang",
            )
            rec = next(r for r in b._inflight if r.control is not None)
            closer = asyncio.ensure_future(b.close())
            with pytest.raises(WorkCancelled):
                await t
            # the join bound elapses on the fake clock; close() returns
            for _ in range(30):
                if closer.done():
                    break
                await clock.advance(1.0)
            await asyncio.wait_for(closer, 5)
            assert (
                _metric("dpow_backend_launch_threads_leaked_total") - before
            ) == 1
            assert not rec.thread_done.is_set(), (
                "thread is wedged, yet close returned — the bound worked"
            )
            # zombie wake-up: the launch can no longer be applied or
            # steered; the thread drains and is gone
            fd.release(0)
            await _spin_until(
                lambda: rec.thread_done.is_set(), msg="zombie drain"
            )

    asyncio.run(asyncio.wait_for(run(), 60))


# -- chunked whole-launch backstop ------------------------------------------


def test_chunked_backstop_evacuates_hung_launch():
    """run_mode=chunked with --device_suspect_after set: a launch that
    outlives its run_steps-scaled deadline is ejected and its rows
    re-covered (reason=launch_hang) WITHOUT quarantining (chunked
    launches carry no per-device evidence); after the fault lifts the
    re-dispatched launch serves."""

    async def run():
        clock = FakeClock()
        b = JaxWorkBackend(
            kernel="xla", sublanes=8, iters=8, run_mode="chunked",
            pipeline=1, clock=clock, device_suspect_after=5.0,
        )
        await b.setup()
        before = _metric("dpow_backend_evacuations_total", "launch_hang")
        with FaultyDevice() as fd:
            fd.hang_at_poll(0, 0)  # blocks the launch-thread boundary too
            h = random_hash()
            t = asyncio.ensure_future(b.generate(WorkRequest(h, EASY)))
            await _spin_until(
                lambda: ("launch", 0, -1) in fd.events, msg="launch hang"
            )
            # no window-time EMA yet → the backstop doubles the deadline
            # (cold-compile grace), so the trip point is 2 × suspect_after
            await clock.advance(6.5)
            assert (
                _metric("dpow_backend_evacuations_total", "launch_hang")
                - before
            ) == 0, "backstop fired inside the cold-compile grace"
            await clock.advance(5.5)
            assert (
                _metric("dpow_backend_evacuations_total", "launch_hang")
                - before
            ) == 1
            assert b._dfd.state(0) == HEALTHY, (
                "chunked backstop must not quarantine"
            )
            fd.release(0)
            work = await asyncio.wait_for(t, 60)
            nc.validate_work(h, work, EASY)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))


# -- the operator-facing demo ----------------------------------------------


def test_chaos_demo_device_scenario_completes():
    """scripts/chaos_demo.py's device walkthrough (hang -> evacuate ->
    solve -> probe re-admission) must complete with its invariants, like
    the resilience and fleet scenarios before it."""
    from tpu_dpow.scripts.chaos_demo import device_scenario

    result = asyncio.run(asyncio.wait_for(device_scenario(), 180))
    assert result["readmitted"]
    assert result["evacuations"] == 1
    assert "dpow_backend_device_health" in result["metrics"]


# -- evacuation frontier vs delivered rebase (review regression) ------------


def test_dead_remainder_subtracts_rebase_boundary():
    """A device that ADOPTED a mid-launch rebase at window k_a and then
    wedged scanned the NEW base only for its post-adoption windows: the
    evacuation frontier must advance by (confirmed - k_a) windows, not by
    every window since launch start — over-advancing would leave a
    never-scanned gap the kill-fenced launch can no longer cover."""

    async def run():
        clock = FakeClock()
        b = JaxWorkBackend(
            kernel="xla", sublanes=8, iters=8, run_mode="persistent",
            persistent_steps=16, control_poll_steps=1, clock=clock,
        )
        from tpu_dpow.backend.jax_backend import _Job, _Launch

        job = _Job(
            block_hash="00" * 32, difficulty=UNREACH, params=None,
            future=asyncio.get_running_loop().create_future(), base=0,
        )
        job.part_start, job.part_len = 0, 1 << 30
        c = ctl.LaunchControl(1, clock=clock, n_dev=1)
        new_base = 1 << 20
        c.rebase(0, new_base, epoch=1)
        c.poll(0, 2, np.array([False]))  # adopts the rebase at k_a = 2
        c.poll(0, 5, np.array([False]))  # last live poll: 5 windows dry
        rec = _Launch(
            fut=asyncio.get_running_loop().create_future(), jobs=[job],
            launched_difficulty=[UNREACH], bases=[0], span=16 * b.chunk,
            shape=(1, 16), miss_factors=[1.0], control=c, slot=0,
        )
        start, _length = b._dead_remainder(rec, 0, job, 0)
        # windows provably dry ON THE NEW BASE: 5 - 2 = 3, not 5
        assert start == new_base + 3 * b.chunk_per_shard, hex(start)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 30))
