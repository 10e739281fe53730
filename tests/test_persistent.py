"""Persistent on-device search: mid-launch control (ISSUE 10).

The chunked engine applies cancel/raise/cover_range at relaunch
boundaries; run_mode=persistent applies them MID-LAUNCH through the
ops/control.py channel polled by the device-resident while_loop. These
tests pin the contract at both altitudes:

  * runloop level — the controlled loop reacts to commands issued from
    within the poll callback itself, which makes delivery timing fully
    deterministic (effect within one poll interval, by construction
    observable in ``last_k`` / ``done_at_k``);
  * engine level — JaxWorkBackend's persistent mode delivers cancel /
    raise_difficulty / cover_range to a RUNNING launch, fences stale
    epochs, and exports the dpow_backend_persistent_* family. Fan and
    plain paths run the same assertions (the PR-6 twin idiom); the
    shard_map mesh variant stays capability-gated.

Planted-difficulty technique: a difficulty equal to some nonce's own work
value is met by ~half of all nonces (values are uniform u64), so tests
that must NOT hit outside a region first compute the max value over every
nonce the loop could scan before the interesting moment, then plant a
target the pre-moment span cannot satisfy.
"""

import asyncio
import hashlib
import itertools

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_dpow import obs
from tpu_dpow.backend import WorkCancelled, WorkError
from tpu_dpow.backend.jax_backend import JaxWorkBackend
from tpu_dpow.models import WorkRequest
from tpu_dpow.ops import control as ctl
from tpu_dpow.ops import runloop, search
from tpu_dpow.resilience.clock import FakeClock
from tpu_dpow.utils import nanocrypto as nc


RNG = np.random.default_rng(10)
EASY = 0xFFF0000000000000
UNREACH = (1 << 64) - 2  # unreachable target that is still a valid raise
UNSOLVED = (1 << 64) - 1
W = 8 * 128 * 2  # the runloop tests' window: sublanes=8, iters=2


def val(h: bytes, nonce: int) -> int:
    return int.from_bytes(
        hashlib.blake2b(
            nonce.to_bytes(8, "little") + h, digest_size=8
        ).digest(),
        "little",
    )


def plant_above(h: bytes, start: int, floor: int) -> int:
    """First nonce >= start whose value exceeds ``floor`` — the planted
    solution of a difficulty the floor'd span cannot satisfy."""
    return next(n for n in itertools.count(start) if val(h, n) > floor)


def random_hash() -> str:
    return RNG.bytes(32).hex().upper()


class TickClock:
    """Monotonic stamps for runloop-level tests (the engine-level tests
    ride the real FakeClock through the backend's injectable seam)."""

    def __init__(self):
        self.t = 0.0

    def time(self) -> float:
        self.t += 0.125
        return self.t


def controlled_run(rows, control, *, max_steps, poll_steps, **kw):
    slot = ctl.register(control)
    try:
        lo, hi = runloop.search_run_batch_controlled(
            jnp.asarray(rows), None, jnp.uint32(slot),
            max_steps=max_steps, poll_steps=poll_steps,
            kernel=kw.pop("kernel", "xla"), sublanes=8, iters=2, **kw,
        )
        # jax dispatch is async: FORCE the result before the slot dies, or
        # the still-running loop polls dead zeros (the engine forces via
        # np.asarray in _launch_persistent for exactly this reason).
        lo, hi = np.asarray(lo), np.asarray(hi)
    finally:
        ctl.release(slot)
    return (int(hi[0]) << 32) | int(lo[0])


# -- runloop level ---------------------------------------------------------


def test_controlled_loop_without_commands_matches_plain_run():
    """Dead control (no commands) must not change the search result."""
    h = bytes(range(32))
    base = 1 << 40
    m = max(val(h, base + j) for j in range(2 * W))
    planted = plant_above(h, base + 2 * W, m)
    diff = val(h, planted)
    rows = np.stack([search.pack_params(h, diff, base)])
    c = ctl.LaunchControl(1, clock=TickClock())
    nonce = controlled_run(rows, c, max_steps=4096, poll_steps=4)
    lo_p, hi_p = runloop.search_run_batch(
        jnp.asarray(rows), jnp.array([True]), max_steps=4096, kernel="xla",
        sublanes=8, iters=2,
    )
    plain = (int(hi_p[0]) << 32) | int(lo_p[0])
    assert nonce == plain == planted
    assert c.polls >= 1 and not c.delivered


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_mid_launch_cancel_exits_within_one_poll_interval(kernel):
    """A cancel issued at poll k must stop the row before window
    k + poll_steps — the loop exits instead of grinding to max_steps.
    Runs on both the jnp scanner and the interpret-mode Pallas kernel
    (the TPU kernel's control path, minus the hardware)."""

    class CancelAt(ctl.LaunchControl):
        def poll(self, dev, k, done):
            if k >= 8 and not self.delivered:
                self.cancel(0)
            return super().poll(dev, k, done)

    # (done_at_k / windows_run are keyed (row, dev): delivery is tracked
    # per device — the plain path is device 0.)

    h = bytes(range(32))
    rows = np.stack([search.pack_params(h, UNREACH, 0)])
    c = CancelAt(1, clock=TickClock())
    kw = {"kernel": kernel, "interpret": True} if kernel == "pallas" else {}
    nonce = controlled_run(rows, c, max_steps=4096, poll_steps=4, **kw)
    assert nonce == UNSOLVED  # cancelled, not solved
    assert c.delivered and c.delivered[0][1] == "cancel"
    assert c.last_k <= 12, f"loop ran past the poll interval ({c.last_k})"
    assert c.done_at_k[(0, 0)] <= 12
    assert c.windows_run(0, 4096) <= 12


def test_mid_launch_rebase_moves_the_frontier():
    """A rebase delivered mid-launch re-aims the scan: the winner comes
    from the NEW region, and the host-side effective_base/epoch mirror
    what the device ran."""
    h = bytes(range(1, 33))
    m = max(val(h, j) for j in range(8 * W))  # pre-rebase span floor
    target = 9 << 40
    planted = plant_above(h, target, m)
    diff = val(h, planted)

    class RebaseAt(ctl.LaunchControl):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.sent = False

        def poll(self, dev, k, done):
            if k >= 1 and not self.sent:
                self.sent = True
                self.rebase(0, target, epoch=7)
            return super().poll(dev, k, done)

    rows = np.stack([search.pack_params(h, diff, 0)])
    c = RebaseAt(1, clock=TickClock())
    nonce = controlled_run(rows, c, max_steps=1 << 14, poll_steps=1)
    assert nonce != UNSOLVED and nonce >= target
    assert val(h, nonce) >= diff
    assert c.effective_base(0) == target
    assert c.effective_epoch(0, default=0) == 7


def test_mid_launch_raise_retargets_in_place():
    """A raise delivered before the first window forces the row past every
    nonce that only met the original target."""
    h = bytes(range(2, 34))
    m = max(val(h, j) for j in range(W))  # first window's best value
    planted = plant_above(h, W, m)

    class RaiseAt(ctl.LaunchControl):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.sent = False

        def poll(self, dev, k, done):
            if not self.sent:
                self.sent = True
                self.raise_difficulty(0, val(h, planted), epoch=1)
            return super().poll(dev, k, done)

    rows = np.stack([search.pack_params(h, EASY, 0)])
    c = RaiseAt(1, clock=TickClock())
    nonce = controlled_run(rows, c, max_steps=4096, poll_steps=1)
    assert nonce != UNSOLVED and val(h, nonce) >= val(h, planted)
    assert nonce >= W, "hit inside the pre-raise window: raise not applied"
    assert c.effective_difficulty(0) == val(h, planted)


def test_killed_row_control_word_is_dead():
    """The epoch fence: kill() stops the stale row (bare CANCEL — it must
    not grind the abandoned region) and refuses every later write, so a
    stale launch cannot be steered."""
    c = ctl.LaunchControl(2, clock=TickClock())
    c.kill(0)
    assert not c.cancel(0)
    assert not c.rebase(0, 123, epoch=2)
    assert not c.raise_difficulty(0, UNREACH, epoch=2)
    assert c.cancel(1)  # sibling rows stay live
    snap = c.poll(0, 0, np.array([False, False]))
    assert snap[0, ctl.IDX_FLAGS] == int(ctl.FLAG_CANCEL)
    assert snap[0, ctl.IDX_SEQ :].sum() == 0  # nothing steerable survives
    assert snap[1, ctl.IDX_FLAGS] == int(ctl.FLAG_CANCEL)
    # the stop is recorded: the device will exit the row at this poll
    assert c.done_at_k[(0, 0)] == 0
    assert c.windows_run(0, 4096) == 0


def test_released_slot_polls_dead_zeros():
    out = ctl.poll_slot(10**9, 0, 0, np.zeros(3, dtype=bool))
    assert out.shape == (3, ctl.CTRL_WORDS) and out.sum() == 0


def test_poll_to_effect_latency_rides_injectable_clock():
    """Issue→delivery latency is measured on the injected clock — the
    DPOW101 contract that lets FakeClock tests pin it exactly."""
    clock = FakeClock()
    c = ctl.LaunchControl(1, clock=clock)
    c.cancel(0)
    clock._now += 2.5  # no waiters: advance the fake time directly
    c.poll(0, 4, np.array([False]))
    assert c.delivered == [(0, "cancel", 2.5, 0)]


# -- engine level (fan and plain twins) ------------------------------------

#: Engine flavors under test: the plain single-device path and the pmap
#: fan. Mesh (shard_map) persistent launches share the fan's control
#: threading and stay capability-gated with the rest of the mesh suite.
ENGINE_IMPLS = [
    pytest.param("plain", id="plain"),
    pytest.param("fan", id="fan"),
]


def make_persistent(impl, **kw):
    if impl == "fan":
        kw.setdefault("devices", 4)
    return JaxWorkBackend(
        kernel="xla", sublanes=8, iters=8, run_mode="persistent", **kw
    )


async def _inflight_control(b, h):
    """Wait until a live persistent launch carries the job; (rec, row)."""
    deadline = asyncio.get_running_loop().time() + 10.0
    while True:
        job = b._jobs.get(h)
        if job is not None:
            recs = b._live_controls(job)
            if recs:
                return recs[-1]
        assert asyncio.get_running_loop().time() < deadline, (
            "no persistent launch picked up the job"
        )
        await asyncio.sleep(0.005)


def _metric(name, label=None):
    series = obs.snapshot().get(name, {}).get("series", {})
    if label is None:
        return series
    v = series.get(label, 0)
    return v.get("count", 0) if isinstance(v, dict) else v


@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_persistent_generate_and_validate(impl):
    async def run():
        b = make_persistent(impl)
        assert b.persistent_steps >= 10 * b.run_steps  # the 10x A/B floor
        await b.setup()
        h = random_hash()
        work = await b.generate(WorkRequest(h, EASY))
        nc.validate_work(h, work, EASY)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_persistent_cancel_lands_mid_launch(impl):
    """cancel() against a RUNNING persistent launch must stop the device
    rows through the control channel (delivered counter moves, the launch
    drains long before its span) — not wait for the span to run out."""

    async def run():
        b = make_persistent(impl)
        await b.setup()
        before = _metric("dpow_backend_persistent_control_total", "cancel")
        h = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
        rec, row = await _inflight_control(b, h)
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        # the launch itself must return (rows freed), not grind the span
        deadline = asyncio.get_running_loop().time() + 20.0
        while b._inflight:
            assert asyncio.get_running_loop().time() < deadline, (
                "cancelled persistent launch never drained"
            )
            await asyncio.sleep(0.005)
        assert rec.control.delivered, "cancel was never delivered on device"
        acts = {a for _r, a, _l, _t in rec.control.delivered}
        assert "cancel" in acts
        assert _metric(
            "dpow_backend_persistent_control_total", "cancel"
        ) > before
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_persistent_raise_difficulty_lands_mid_launch(impl):
    """raise_difficulty() retargets the running launch in place: the raise
    is DELIVERED (not queued for the next pack), and the job stays covered
    — no duplicate launch storm for the raised target."""

    async def run():
        b = make_persistent(impl)
        await b.setup()
        h = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH - 1)))
        rec, row = await _inflight_control(b, h)
        assert await b.raise_difficulty(h, UNREACH)
        deadline = asyncio.get_running_loop().time() + 10.0
        while not any(
            a == "raise" for _r, a, _l, _t in rec.control.delivered
        ):
            assert asyncio.get_running_loop().time() < deadline, (
                "raise never delivered to the running launch"
            )
            await asyncio.sleep(0.005)
        # delivery is per device: the raise is applied on whichever
        # device(s) polled it — at least one has by now
        n = len(b.fan) if b.fan is not None else 1
        assert UNREACH in [
            rec.control.effective_difficulty(row, d) for d in range(n)
        ]
        job = b._jobs[h]
        assert job.difficulty == UNREACH
        assert job.inflight_miss < 1.0, "raised job lost its coverage"
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_persistent_cover_range_rebases_mid_launch(impl):
    """cover_range() re-aims the RUNNING launch at the orphaned range: the
    rebase is delivered with the job's new epoch token, per-device bases
    on the fan, and the winner comes from the new region."""

    async def run():
        b = make_persistent(impl)
        await b.setup()
        hx = random_hash()
        h = bytes.fromhex(hx)
        n = len(b.fan) if b.fan is not None else 1
        # Unreachable-by-accident floor over everything the launch can
        # scan pre-rebase: the span is persistent_steps windows per device
        # from the initial range start.
        start_a = 1 << 30
        length = n << 22
        span = b.chunk * b.persistent_steps
        floor = max(val(h, start_a + j) for j in range(min(span * 2, 1 << 19)))
        start_b = 5 << 45
        planted = plant_above(h, start_b, floor)
        diff = val(h, planted)
        t = asyncio.ensure_future(
            b.generate(WorkRequest(hx, diff, nonce_range=(start_a, length)))
        )
        rec, row = await _inflight_control(b, hx)
        epoch_before = rec.dev_epochs[row]
        assert await b.cover_range(hx, (start_b, length))
        job = b._jobs[hx]
        assert job.dev_epoch == epoch_before + 1
        work = await asyncio.wait_for(t, 30)
        nonce = int(work, 16)
        assert nonce >= start_b, (
            f"winner {work} is not from the re-covered range"
        )
        nc.validate_work(hx, work, diff)
        delivered = [a for _r, a, _l, _t in rec.control.delivered]
        assert "rebase" in delivered
        if b.fan is not None:
            # Delivery is PER DEVICE: every device that observed the
            # rebase got ITS OWN sub-range base (a device that exited
            # first legitimately reads None — dispatch snapshot stands).
            applied = {
                d: base
                for d in range(n)
                if (base := rec.control.effective_base(row, d)) is not None
            }
            assert applied, "no device applied the rebase"
            assert len(set(applied.values())) == len(applied), applied
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 90))


def test_persistent_stale_epoch_launch_is_cancelled_not_rebased():
    """Two live launches carrying the job (pipeline): cover_range rebases
    the NEWEST and cancels the job's row in the older one — a stale
    launch's control word is dead for steering, its lanes free."""

    async def run():
        b = make_persistent("plain")
        await b.setup()
        h = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
        job_ready = asyncio.get_running_loop().time() + 15.0
        while True:
            job = b._jobs.get(h)
            recs = b._live_controls(job) if job is not None else []
            if len(recs) >= 2:
                break
            assert asyncio.get_running_loop().time() < job_ready, (
                f"pipeline never filled with 2 launches (have {len(recs)})"
            )
            await asyncio.sleep(0.005)
        (old_rec, old_row), (new_rec, new_row) = recs[0], recs[-1]
        assert await b.cover_range(h, (7 << 40, 1 << 24))
        # newest launch: rebase staged; older launch: cancel staged
        deadline = asyncio.get_running_loop().time() + 15.0
        while True:
            old_acts = {a for _r, a, _l, _t in old_rec.control.delivered}
            new_acts = {a for _r, a, _l, _t in new_rec.control.delivered}
            if "cancel" in old_acts and "rebase" in new_acts:
                break
            assert asyncio.get_running_loop().time() < deadline, (
                old_acts, new_acts,
            )
            await asyncio.sleep(0.005)
        assert "rebase" not in old_acts, "stale launch was steered"
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_persistent_metrics_exported():
    """The dpow_backend_persistent_* family moves: polls counted, launch
    windows observed, delivered commands and their poll-to-effect latency
    recorded (catalogued in docs/observability.md)."""

    async def run():
        polls0 = _metric("dpow_backend_persistent_polls_total").get("", 0)
        b = make_persistent("plain")
        await b.setup()
        h = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
        await _inflight_control(b, h)
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        deadline = asyncio.get_running_loop().time() + 20.0
        while b._inflight:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.005)
        await b.close()
        snap = obs.snapshot()
        assert snap["dpow_backend_persistent_polls_total"]["series"][""] > polls0
        wins = snap["dpow_backend_persistent_launch_windows"]["series"][""]
        assert wins["count"] >= 1
        eff = snap["dpow_backend_persistent_effect_seconds"]["series"][""]
        assert eff["count"] >= 1

    asyncio.run(asyncio.wait_for(run(), 60))


def test_persistent_effect_latency_deterministic_under_fake_clock():
    """FakeClock drives the poll-to-effect histogram: with time frozen the
    delivered latency is exactly 0.0 — the DPOW101 payoff that the poll
    timers are testable without real sleeps."""

    async def run():
        clock = FakeClock()
        b = make_persistent("plain", clock=clock)
        await b.setup()
        h = random_hash()
        t = asyncio.ensure_future(b.generate(WorkRequest(h, UNREACH)))
        rec, row = await _inflight_control(b, h)
        await b.cancel(h)
        with pytest.raises(WorkCancelled):
            await t
        deadline = asyncio.get_running_loop().time() + 20.0
        while not rec.control.delivered:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.005)
        assert all(lat == 0.0 for _r, _a, lat, _t in rec.control.delivered)
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 60))


def test_persistent_rejects_bad_options():
    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", run_mode="sideways")
    with pytest.raises(WorkError):
        JaxWorkBackend(kernel="xla", run_mode="persistent", control_poll_steps=-1)


def test_persistent_refuses_the_shard_map_mesh():
    """Mesh + persistent is refused AT CONSTRUCTION with the SPMD story:
    independent per-device control polls inside one collective program can
    diverge the replicated while_loop into a deadlock. The fan is the
    supported persistent multi-chip path (mesh_search.py docstring has the
    broadcast follow-up)."""
    with pytest.raises(WorkError, match="persistent"):
        JaxWorkBackend(kernel="xla", run_mode="persistent", mesh_devices=1)


def test_persistent_dedup_and_concurrent_batch():
    """The engine contract (dedup, concurrent batching) holds unchanged in
    persistent mode — the control channel is additive."""

    async def run():
        b = make_persistent("plain", max_batch=8)
        await b.setup()
        hashes = [random_hash() for _ in range(6)]
        works = await asyncio.gather(
            *(b.generate(WorkRequest(h, EASY)) for h in hashes)
        )
        for h, w in zip(hashes, works):
            nc.validate_work(h, w, EASY)
        h = random_hash()
        a, bb = await asyncio.gather(
            b.generate(WorkRequest(h, EASY)), b.generate(WorkRequest(h, EASY))
        )
        assert a == bb
        await b.close()

    asyncio.run(asyncio.wait_for(run(), 120))
