"""In-process JAX/TPU work engine: batched, cancellable nonce search.

Replaces the reference's external ``nano-work-server`` process (reference
client/bin; HTTP contract at client/work_handler.py:104-108) with an
in-process engine built on the chunk scanners in ops/:

  * Every active request gets a decorrelating random 64-bit start base —
    the same swarm decorrelation the reference gets from each worker's
    random starting nonce (SURVEY.md §2.5) — then advances deterministically
    chunk by chunk.
  * All active requests are packed into ONE fixed-shape batched launch per
    engine step (padded with difficulty-0 dummies that hit at offset 0 and
    early-exit, so arrival and completion never change the compiled shape —
    no recompiles, SURVEY.md §7 hard part #4). Concurrent hashes share a
    single device dispatch, replacing the reference's one-POST-per-item
    worker dialogue.
  * Cancels are lane masking: a cancelled job is dropped from the next
    pack; the chunk already in flight finishes and its result is discarded
    — the same cancel/completion race resolution the reference implements
    with its ``work_ongoing`` set (reference client/work_handler.py:109-114).
  * Chunked launches bound cancel latency and let the host check for
    cancels between steps (a SIMD machine cannot break mid-launch; SURVEY.md
    §7 hard part #2).
  * Run mode (``run_steps`` > 1, the TPU default) widens a launch to up to
    ``run_steps`` consecutive windows inside ONE persistent-kernel grid
    dispatch (ops/pallas_kernel.py ``_kernel_blocks``): the grid's found
    flag skips every window after a hit, so an easy request costs one
    window while a hard one gets its whole median solve covered without
    paying the dispatch + transfer round trip per window. Jobs are grouped
    into difficulty rungs served round-robin, each launch as wide as its
    own rung wants. (A ``lax.while_loop`` over dispatches — ops/runloop.py
    — covers the same span; the engine prefers one wide grid, whose
    windows need no host touch between them.)
  * Launch pipelining (``pipeline``, default 2) keeps a second launch in
    flight while the first's results travel back: jobs advance their scan
    base speculatively at dispatch, so consecutive launches cover disjoint
    spans and the device never idles through host readback/repack — the
    round-2 flood benchmark lost ~27% of the device solve ceiling to that
    bubble.

Every found nonce is re-validated on host against hashlib before being
returned (the belt to the device's suspenders, mirroring the reference's
final nanolib.validate_work at server/dpow_server.py:363-368).
"""

from __future__ import annotations

import asyncio
import math
import secrets
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import numpy as np

from .. import obs
from ..models import WorkRequest
from ..ops import control as ctl
from ..ops import pallas_kernel, runloop, search
from ..resilience.clock import Clock, SystemClock
from ..resilience.devfault import (
    DEADLINE_SLACK,
    HEALTHY,
    DeviceFaultDomains,
    launch_deadline,
)
from ..utils import nanocrypto as nc
from . import DevicesExhausted, WorkBackend, WorkCancelled, WorkError, await_shared_job

_MASK64 = (1 << 64) - 1


def _consume_abandoned(fut) -> None:
    """Done-callback tail for an abandoned launch future: consume its
    outcome so an exception never logs as never-retrieved (a cancelled
    wrapper has nothing to consume)."""
    if not fut.cancelled():
        fut.exception()


def _retire_on_done(fut, slot: int) -> None:
    """Attach the abandoned-launch retirement: when the future resolves,
    release the control slot (idempotent — the launch thread's own
    release normally got there first) and consume the outcome."""

    def _retire(f, s=slot):
        # dpowlint: disable=DPOW1004 — retirement backstop for ABANDONED launches only: their control rows are kill-fenced/cancelled before this callback is attached, the thread's own finally-release normally got here first, and release() is idempotent
        ctl.release(s)
        _consume_abandoned(f)

    fut.add_done_callback(_retire)

# Coverage-aware dispatch (see _dispatch_next): a job is worth another span
# while P(no in-flight span solves it) is at least this. Below it the job is
# only dispatched speculatively, and only when NO uncovered demand exists —
# round 3's on-chip batch benchmark measured 1.8x device overscan (123 M
# hashes/solve vs ~67 M expected) from unconditionally re-dispatching the
# same covered jobs while queued jobs waited.
SPEC_MISS_THRESHOLD = 0.5
# Even idle-device speculation stops once a job is this likely already
# solved in flight; deeper speculation is almost pure waste.
SPEC_MISS_FLOOR = 0.02
# A purely speculative launch (every included job already covered) may carry
# at most this many EXPECTED-WASTED rows (sum of per-job solve probability):
# ~2 rows of median scan ≈ one host round trip of device time, so the
# speculation never costs more device time than the readback bubble it
# hides. Without the cap, a batch-wide launch whose whole batch is covered
# re-dispatches every row — round 3's on-chip batch-64 run burned a full
# 3.8 s speculative launch (64 rows) to hide a 0.12 s readback and queued
# the survivors' real launch behind it, halving solves/s.
SPEC_WASTE_ROWS = 2.0
# The engine task exits after this many seconds with no job; the wait is
# traced in slices of IDLE_SPAN_S (see _idle_wait).
IDLE_EXIT_S = 5
IDLE_SPAN_S = 1

# The jax.monitoring duration events of one jit specialization's way to an
# executable, by the phase label of dpow_engine_jit_seconds_total.
JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_jit_listener_lock = threading.Lock()
_jit_listener_registered = False


def _register_jit_listener() -> None:
    """Count this process's host seconds in each jit phase, once per
    process however many engines it builds: jax.monitoring keeps every
    listener it is given, so a second one would count each event twice.

    A jit traced inside another's trace (a jitted jnp function in a kernel
    body) reports its own duration, already inside the outer one's; only
    the outermost event of a phase on a thread is counted. JAX marks each
    event's start with a scalar of the same name."""
    global _jit_listener_registered
    with _jit_listener_lock:
        if _jit_listener_registered:
            return
        seconds = obs.get_registry().counter(
            "dpow_engine_jit_seconds_total",
            "Host seconds spent making jit executables, by phase", ("phase",))
        open_events = threading.local()

        def started(event: str, _value: float, **_kw) -> None:
            if event in JIT_PHASES:
                setattr(open_events, event, getattr(open_events, event, 0) + 1)

        def ended(event: str, duration: float, **_kw) -> None:
            phase = JIT_PHASES.get(event)
            if phase is None:
                return
            depth = max(getattr(open_events, event, 0) - 1, 0)
            setattr(open_events, event, depth)
            if depth == 0:
                seconds.inc(duration, phase)

        jax.monitoring.register_scalar_listener(started)
        jax.monitoring.register_event_duration_secs_listener(ended)
        _jit_listener_registered = True


@dataclass
class _Job:
    block_hash: str
    difficulty: int  # current target; can only be raised by a later request
    params: np.ndarray  # cached uint32[12] row; base/diff words updated in place
    future: asyncio.Future
    base: int
    cancelled: bool = False
    waiters: int = 0  # refcount: last cancelled waiter drops the job
    # Device fan (engine ``devices`` mode): per-device shard state. The
    # engine sub-partitions the job's nonce range into disjoint per-device
    # sub-ranges (the fleet partition idiom one level down); each device
    # keeps its own frontier, scan counter and scan-clock stamp so a win
    # can be attributed to the device whose sub-range produced it.
    dev_bases: "Optional[list]" = None  # split policy: per-device next base
    dev_scanned: "Optional[list]" = None  # nonces scanned per device (this job)
    dev_t0: "Optional[list]" = None  # per-device scan-clock first-dispatch stamps
    # Bumped on every re-aim of the scan — fan re-partitions AND plain
    # cover_range rebases — so results of launches dispatched against the
    # OLD region cannot feed the new partition's scan counters/clocks, and
    # a stale launch's weak hit cannot rewind the frontier back into the
    # region a re-cover just left (the same inflation/undo the fleet's
    # per-shard scan stamps guard against).
    dev_epoch: int = 0
    # The partition's recorded range (fan mode): evacuation computes a dead
    # device's uncovered remainder against this end (length 0 = full span).
    part_start: int = 0
    part_len: int = 0
    # P(no launch currently in flight solves this job); 1.0 = uncovered.
    inflight_miss: float = 1.0
    # Timeline stamps (record_timeline only): submission and first dispatch.
    t_submit: float = 0.0
    t_first_dispatch: float = 0.0
    # Launches including this job whose results have been APPLIED — the
    # solving launch's position in the job's readback sequence. Counted at
    # apply (not dispatch) so an in-flight speculative successor does not
    # inflate it: the solve record reports the number of wire round trips
    # the solve actually consumed (the one-round-trip design ⇒ p50 of 1 at
    # a rung's native difficulty).
    applied_launches: int = 0

    def set_base(self, base: int) -> None:
        self.base = base & _MASK64
        self.params[search.BASE_LO] = self.base & 0xFFFFFFFF
        self.params[search.BASE_HI] = self.base >> 32

    def set_difficulty(self, difficulty: int) -> None:
        self.difficulty = difficulty
        self.params[search.DIFF_LO] = difficulty & 0xFFFFFFFF
        self.params[search.DIFF_HI] = difficulty >> 32
        # In-flight spans were dispatched at the OLD (easier) target and are
        # now far less likely to solve this job; treating it as still
        # covered would stall the raised request behind stale launches.
        # Resetting to uncovered makes it immediately eligible again (the
        # per-launch divide-back then clamps at 1.0 — see _apply_results).
        self.inflight_miss = 1.0


@dataclass
class _Launch:
    """One in-flight device launch and the per-job state it was packed with."""

    fut: asyncio.Future  # executor future → (lo, hi) result arrays
    jobs: list  # the _Jobs occupying the first len(jobs) batch rows
    launched_difficulty: list  # per-job target snapshot at dispatch
    bases: list  # per-job scan base at dispatch (pre-speculation)
    span: int  # nonces scanned per row this launch
    shape: tuple  # (batch, steps) — warmed on success
    miss_factors: list  # per-job P(this span misses), undone when applied
    # Fan mode: per-job per-device base snapshot [len(jobs)][n_devices] and
    # the partition epoch each job was packed under — the attribution keys.
    dev_bases: "Optional[list]" = None
    dev_epochs: "Optional[list]" = None
    timing: "Optional[dict]" = None  # stage stamps when record_timeline is on
    # Readback-await task, created when this launch reaches the head of the
    # FIFO; persists across wakeup-interrupted waits (engine loop).
    waiter: "Optional[asyncio.Task]" = None
    # Persistent mode (run_mode=persistent): the launch's live control
    # block + its slot id in ops/control.py's table. None on chunked
    # launches — they cannot be steered mid-flight.
    control: "Optional[ctl.LaunchControl]" = None
    slot: int = 0
    # Fan mode: launch slice index -> PHYSICAL device index. A launch
    # dispatched at degraded width (quarantined devices excluded) runs on
    # a subset of the fan; every apply/attribution path maps through this.
    fan_map: "Optional[list]" = None
    # Dispatch stamp on the engine's injectable clock — the watchdog's
    # progress-deadline anchor for a launch that has not polled yet.
    t_clock: float = 0.0
    # Set by the launch THREAD when it actually returns. ``fut`` cannot
    # stand in for this: cancelling its waiter marks the asyncio wrapper
    # done while the executor thread may still be wedged — and the close
    # bound exists precisely to tell those two apart.
    thread_done: "Optional[threading.Event]" = None
    # Set when the watchdog ejects the launch from the pipeline (a suspect
    # device pins it): its results are discarded, its control rows are
    # kill-fenced, and the engine loop must not apply it.
    abandoned: bool = False


class JaxWorkBackend(WorkBackend):
    """Batched chunked nonce search on this host's jax.local_devices().

    Two multi-chip flavors gang local devices onto every hash — the
    flagship latency configuration: the <50 ms p50 target at difficulty
    fffffff800000000 needs all 8 chips of a v5e-8 on one request
    (SURVEY.md §7 hard part #3). The per-dispatch window covers
    N_devices * chunk nonces either way:

    * ``devices`` >= 1 — the pmap FAN (parallel/fan_search.py,
      docs/device_sharding.md). Each job's nonce shard is sub-partitioned
      into disjoint per-device ranges (``device_shard`` policy: 'split' macro-ranges /
      'interleave' round-robin windows); the host elects the winner and
      attributes it to the device whose sub-range produced it, feeding
      per-device scan clocks + EMA (the fleet registry idiom one level
      down). Cancel/raise/cover_range apply to every device shard.
    * ``mesh_devices`` >= 1 — the shard_map (batch, nonce) mesh of
      parallel/mesh_search.py with an ICI pmin election.
    """

    def __init__(
        self,
        *,
        kernel: Optional[str] = None,  # 'pallas' | 'xla' | None = auto
        sublanes: int = 32,
        iters: int = 1024,
        nblocks: int = 8,
        group: int = 8,
        max_batch: int = 16,
        interpret: bool = False,
        device: Optional[jax.Device] = None,
        mesh_devices: int = 0,  # >=1: gang this many devices per hash (shard_map)
        devices: int = 0,  # >=1: fan this many local devices per hash (pmap)
        device_shard: str = "split",  # fan partition policy: 'split' | 'interleave'
        run_steps: Optional[int] = None,  # cap on windows per device launch
        run_mode: str = "chunked",  # 'chunked' | 'persistent' (mid-launch control)
        control_poll_steps: int = 0,  # persistent: windows between control polls (0 = auto)
        persistent_steps: Optional[int] = None,  # persistent: windows per launch (None = auto)
        warm_shapes: Optional[bool] = None,  # background-compile launch shapes
        launch_timeout: Optional[float] = None,  # s; None = auto (300 on TPU)
        pipeline: int = 2,  # launches in flight at once (1 = no overlap)
        step_ladder: str = "x4",  # run-length quantization: 'x4' | 'x2'
        shared_steps_cap: Optional[int] = None,  # windows/launch under contention
        clock: Optional[Clock] = None,  # fan scan clocks / busy-fraction wall
        device_suspect_after: float = 0.0,  # s without device progress (0 = auto)
        device_probe_interval: float = 30.0,  # s between re-admission probes
        close_join_timeout: float = 5.0,  # s close() waits for launch threads
    ):
        # Injectable time for the fan's per-device scan clocks and the
        # busy-fraction wall anchor (resilience/clock.py): chaos/FakeClock
        # tests drive EMA attribution without sleeping through real seconds.
        self._clock = clock or SystemClock()
        if devices and mesh_devices >= 1:
            raise WorkError(
                "devices (pmap fan) and mesh_devices (shard_map gang) are "
                "mutually exclusive — pick one multi-device path"
            )
        if device_shard not in ("split", "interleave"):
            raise WorkError(
                f"device_shard must be 'split' or 'interleave', not {device_shard!r}"
            )
        self.device_shard = device_shard
        self.fan = None
        if mesh_devices >= 1:
            # 0 (default) = plain single-device dispatch. >= 1 builds the
            # shard_map gang — INCLUDING 1: a one-device mesh runs the
            # exact gang code with zero ICI traffic, the A/B configuration
            # that prices the gang machinery on real hardware (r4 first
            # measured it via benchmarks/gang_ab.py at raw-launch level:
            # -1.0 ms, i.e. free; mesh_devices=1 prices it engine-level).
            # An earlier `> 1` guard silently downgraded that A/B to the
            # plain path, so its bench measured plain-vs-plain drift.
            # local_devices: under a jax.distributed multi-host slice the
            # per-worker gang must only claim this host's chips (ICI
            # domain); cross-host scale is the broker swarm's job, or an
            # SPMD deployment over parallel/multihost.py's mesh.
            local = jax.local_devices()
            if len(local) < mesh_devices:
                raise WorkError(
                    f"mesh_devices={mesh_devices} but only {len(local)} "
                    "local devices visible"
                )
            from ..parallel import make_mesh

            self.mesh = make_mesh(local[:mesh_devices])
            self.device = local[0]
        elif devices:
            # The pmap multi-device path (parallel/fan_search.py): one
            # WorkRequest's nonce shard is sub-partitioned into disjoint
            # per-device ranges and searched on `devices` local chips.
            # -1 = all local devices; 1 builds the real fan on one device
            # (the A/B that prices the fan machinery, same idiom as
            # mesh_devices=1).
            from ..parallel import fan_devices

            try:
                self.fan = fan_devices(devices)
            except ValueError as e:
                raise WorkError(str(e))
            self.mesh = None
            self.device = self.fan[0]
        else:
            self.mesh = None
            self.device = device or jax.local_devices()[0]
        on_tpu = self.device.platform == "tpu"
        self.kernel = kernel or ("pallas" if on_tpu else "xla")
        # Defaults follow the v5e geometry sweep (benchmarks/throughput.py):
        # (32 sublanes, 1024 iters, group 8) sustains >1 GH/s; nblocks sets
        # the per-dispatch window — 8 windows ≈ 33.5 M nonces ≈ 30 ms of
        # scan per launch, the cancel-latency/throughput tradeoff point.
        self.sublanes = sublanes
        self.iters = iters
        self.nblocks = nblocks
        self.group = group
        if self.kernel == "xla" and not on_tpu:
            # CPU fallback/test path: small chunks keep latency sane.
            self.sublanes = min(sublanes, 8)
            self.iters = min(iters, 8)
            self.nblocks = 1
            self.group = 1
        self.chunk_per_shard = self.sublanes * 128 * self.iters * self.nblocks
        # Global per-step window: every gang flavor (shard_map mesh, pmap
        # fan) multiplies the per-device chunk by its width; the host loop
        # advances one logical frontier by the global chunk either way.
        gang_width = mesh_devices if self.mesh else (len(self.fan) if self.fan else 1)
        self.chunk = self.chunk_per_shard * gang_width
        # Run mode: one launch may widen to run_steps consecutive windows in
        # a single persistent-kernel grid dispatch with cross-window early
        # exit. The cap bounds cancel latency: a launch cannot be
        # interrupted, so worst case a cancel waits run_steps windows
        # (16 * ~30 ms ≈ 0.5 s at the TPU default geometry). The window
        # ladder also may not cross the kernel's 2^31-offset limit.
        if run_steps is None:
            run_steps = 16 if on_tpu else 1
        if self.chunk >= 1 << 31:
            # Fail at construction with the actual constraint, not from deep
            # inside the first launch's kernel-geometry check.
            raise WorkError(
                f"per-dispatch window {self.chunk} nonces (sublanes*128*iters"
                f"*nblocks*mesh_devices) must stay below 2^31"
            )
        if self.kernel == "pallas" and not on_tpu and not interpret:
            # The Mosaic kernel runs on a TPU or in the interpreter, nowhere
            # else; an explicit pallas request must not quietly become
            # something else on the wrong device.
            raise WorkError(
                f"kernel='pallas' needs a TPU (device is {self.device.platform}"
                f"); pass interpret=True, or kernel='xla'"
            )
        max_by_window = ((1 << 31) - 1) // self.chunk
        self.run_steps = max(1, min(run_steps, max_by_window))
        # Persistent run mode: launches are a device-resident while_loop
        # (ops/runloop.py) polling a host control channel every
        # control_poll_steps windows, so cancel/raise/cover_range land
        # MID-LAUNCH and the launch length no longer caps cancel latency.
        # That lifts the windows-per-launch cap from run_steps (the chunked
        # cancel-latency bound) to persistent_steps — span-sized: one host
        # round trip per REQUEST instead of per run_steps windows. The
        # 2^31 ceiling applies per WINDOW (the device advances the 64-bit
        # base between windows), not per launch, so the span is unbounded.
        if run_mode not in ("chunked", "persistent"):
            raise WorkError(
                f"run_mode must be 'chunked' or 'persistent', not {run_mode!r}"
            )
        if run_mode == "persistent" and self.mesh is not None:
            # The mesh gang is one SPMD program with collectives; each
            # device would invoke the control poll independently while the
            # host mutates the block, so two devices can observe a command
            # at different poll blocks, diverge in while_loop trip count,
            # and deadlock the next collective. Until the poll is pinned
            # to one device and broadcast (io_callback sharding=),
            # persistent mode pairs with the fan — whose per-device loops
            # share no collective.
            raise WorkError(
                "run_mode=persistent cannot drive the shard_map mesh: the "
                "replicated control poll can diverge across devices inside "
                "one SPMD program (collective deadlock); use devices=N "
                "(the pmap fan) for persistent multi-chip search"
            )
        self.run_mode = run_mode
        if control_poll_steps < 0:
            raise WorkError("control_poll_steps must be >= 0 (0 = auto)")
        # Poll cadence tradeoff: each poll is an io_callback (a host touch)
        # and one poll interval is the worst-case cancel/raise/rebase
        # latency. The TPU default (8 windows ≈ 240 ms of scan at the
        # default geometry) amortizes the polls; the CPU default polls
        # every window (test windows are tiny).
        self.control_poll_steps = control_poll_steps or (8 if on_tpu else 1)
        if persistent_steps is None:
            # >= 10x the chunked window cap (the A/B floor the benchmarks
            # hold persistent mode to), default 16x: at the TPU default
            # geometry that is one ~8 s launch per request at 16x the
            # chunked span, cancel still bounded by one poll interval.
            persistent_steps = self.run_steps * 16
        self.persistent_steps = max(persistent_steps, 1)
        self.max_batch = max_batch
        self.interpret = interpret
        # Every launch program (see _program) is a separate XLA compile
        # (seconds each for the Pallas kernel at the default geometry on a
        # v5e host; the persistent compile cache saves restarts). With shape
        # warming on — the TPU default — the engine only ever launches
        # programs from _warm, and a background task grows that set after
        # setup, so no request stalls behind a compile wall. Off (the CPU
        # default, where compiles are cheap), everything counts as warm.
        self.warm_shapes = on_tpu if warm_shapes is None else warm_shapes
        # A wedged device can hold a dispatch or compile indefinitely; the
        # reference's analog is its worker-unreachable startup probe
        # (client/work_handler.py:50-55).
        # A bounded launch turns a silent worker hang into a WorkError the
        # server can time out and the operator can see. The stuck thread
        # itself cannot be killed, but the engine restarts on next demand.
        if launch_timeout is None:
            launch_timeout = 300.0 if on_tpu else None
        self.launch_timeout = launch_timeout
        # Launch pipelining: the engine keeps up to ``pipeline`` launches in
        # flight, overlapping host readback + repacking of launch N with
        # device execution of launch N+1 — without it the device idles for a
        # full host round trip between launches and every queued request
        # eats that bubble. Jobs included in a successor launch advance
        # their base SPECULATIVELY at dispatch (assuming the predecessor
        # misses); a predecessor hit just resolves the job and the
        # successor's now-useless lane result is discarded, identical to the
        # cancel-in-flight race. Successor launches prefer UNCOVERED demand
        # over re-scanning jobs already likely solved in flight
        # (_dispatch_next's coverage accounting). Worst-case wait behind
        # LIVE in-flight work is bounded by run_steps + (pipeline-1) *
        # shared_steps_cap windows: only the head-of-queue launch may run
        # full width (_dispatch_next's successor cap, which counts only
        # launches still serving an unresolved job — a transient corpse
        # launch can add up to run_steps more, bounded by its own already-
        # running scan).
        self.pipeline = max(1, pipeline)
        if step_ladder not in ("x4", "x2"):
            raise WorkError(f"step_ladder must be 'x4' or 'x2', not {step_ladder!r}")
        self.step_ladder = step_ladder
        # The device executes launches serially, so one steps=16 launch parks
        # ~16 windows of scan in front of everything behind it — the whole
        # cancel-latency / mixed-load fairness tax in one number. Under
        # CONTENTION (another difficulty rung has eligible demand) or for
        # purely SPECULATIVE launches (all demand already covered in flight),
        # cap the run length: round trips per solve rise a little (the
        # pipeline hides the readback either way), but nothing waits behind
        # more than `shared_steps_cap` windows of someone else's scan. A
        # lone uncovered hard job still gets the full run_steps width — that
        # single-round-trip launch IS the <50 ms design (SURVEY.md §7).
        if shared_steps_cap is None:
            shared_steps_cap = max(1, self.run_steps // 4)
        self.shared_steps_cap = max(1, min(shared_steps_cap, self.run_steps))
        self._warm: set = set()
        self._warm_task: Optional[asyncio.Task] = None
        # Dedicated launch executor (2 workers: one engine launch + one warm
        # compile may overlap). A timed-out launch leaks its blocked thread,
        # so the executor is REPLACED on timeout rather than poisoning
        # asyncio's shared to_thread pool until the pool starves.
        self._executor = None
        self._jobs: Dict[str, _Job] = {}
        # In-flight launch records, oldest first. Owned by the engine loop;
        # kept on the instance so the persistent control writers (cancel /
        # raise_difficulty / cover_range) can reach a RUNNING launch.
        self._inflight: deque = deque()
        self._last_rung = -1  # round-robin cursor over difficulty rungs
        self._engine_task: Optional[asyncio.Task] = None
        self._wakeup = asyncio.Event()
        self._closed = False
        self.total_hashes = 0
        self.total_solutions = 0
        # Per-stage latency decomposition (benchmarks/overhead.py): when on,
        # every launch appends {t_dispatch, t_thread, t_done, t_apply,
        # batch, steps} and every solve appends {queue_wait, total} to
        # ``timeline``. The perf_counter stamps themselves are ALWAYS taken
        # (a few ns each, nothing on the device path) because the metrics
        # below consume them; record_timeline only gates the deque.
        self.record_timeline = False
        self.timeline: "deque[tuple]" = deque(maxlen=1024)
        # Registry metrics (tpu_dpow.obs): batch occupancy, executor-queue
        # vs device time (from the launch stamps), nonces scanned.
        reg = obs.get_registry()
        self._tracer = obs.get_tracer()
        _register_jit_listener()
        self._m_hashes = reg.counter(
            "dpow_engine_hashes_total", "Nonces scanned on device", ("engine",))
        self._m_solutions = reg.counter(
            "dpow_engine_solutions_total", "Nonces found and host-validated",
            ("engine",))
        self._m_batch_rows = reg.histogram(
            "dpow_engine_batch_occupancy",
            "Live jobs packed per device launch (padding excluded)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        self._m_exec_queue = reg.histogram(
            "dpow_engine_executor_queue_seconds",
            "Launch wait between executor submit and the launch thread "
            "starting", ("engine",))
        self._m_device_seconds = reg.histogram(
            "dpow_engine_device_seconds",
            "Blocking device launch time (dispatch + scan + readback)",
            ("engine",))
        self._m_queue_wait = reg.histogram(
            "dpow_engine_queue_wait_seconds",
            "Job wait from submission to its first device dispatch",
            ("engine",))
        self._m_jobs = reg.gauge(
            "dpow_engine_jobs", "Jobs currently tracked by the engine",
            ("engine",))
        self._m_rungs = reg.gauge(
            "dpow_engine_rungs", "Distinct difficulty rungs with live demand")
        self._m_launches = reg.counter(
            "dpow_engine_launches_total",
            "Device launches submitted, by rows the launch's grid visits "
            "and run length (windows of nblocks)", ("batch", "steps"))
        self._m_rows_skipped = reg.counter(
            "dpow_engine_rows_skipped_total",
            "Padding rows a chunked Pallas launch's runtime row count kept "
            "out of its grid")
        self._m_programs = reg.gauge(
            "dpow_engine_launch_programs",
            "Compiled launch programs the engine holds (its warm set)")
        # Per-device families (fan mode; docs/observability.md catalogue).
        # Label cardinality is the local device count (<= 8 on every target
        # topology), never unbounded.
        self._m_dev_rate = reg.gauge(
            "dpow_backend_device_hash_rate_hs",
            "Per-device scan rate of the most recently applied fanned "
            "launch (H/s)", ("device",))
        self._m_dev_launches = reg.counter(
            "dpow_backend_device_launches_total",
            "Fanned launches applied, per device", ("device",))
        self._m_dev_hashes = reg.counter(
            "dpow_backend_device_hashes_total",
            "Nonces scanned per device across fanned launches", ("device",))
        self._m_dev_busy = reg.gauge(
            "dpow_backend_device_busy_fraction",
            "Fraction of wall time the device spent executing fanned "
            "launches (occupancy)", ("device",))
        self._m_dev_wins = reg.counter(
            "dpow_backend_device_wins_total",
            "Wins attributed to the device whose sub-range produced the "
            "nonce", ("device",))
        self._m_dev_ema = reg.gauge(
            "dpow_backend_device_ema_hs",
            "EMA of win-attributed scan rate on the device's own scan "
            "clock (H/s)", ("device",))
        # Persistent-mode families (run_mode=persistent): launch length,
        # control-channel traffic and poll-to-effect latency — the numbers
        # that prove mid-launch control works (docs/observability.md).
        self._m_p_windows = reg.histogram(
            "dpow_backend_persistent_launch_windows",
            "Windows a persistent launch actually ran before win/cancel/"
            "span end",
            buckets=(1, 4, 16, 64, 256, 1024, 4096))
        self._m_p_polls = reg.counter(
            "dpow_backend_persistent_polls_total",
            "Mid-launch control polls served to devices (io_callback reads)")
        self._m_p_control = reg.counter(
            "dpow_backend_persistent_control_total",
            "Mid-launch control commands delivered on device", ("action",))
        self._m_p_effect = reg.histogram(
            "dpow_backend_persistent_effect_seconds",
            "Control command issue -> device delivery latency on the "
            "engine's injectable clock")
        # Fan bookkeeping: per-device busy seconds + EMA folds, the wall
        # anchor for busy-fraction, and the last win's attribution record
        # (device index, hashes, scan-clock elapsed) — the engine-level
        # twin of the fleet registry's observe_result sample.
        n_fan = len(self.fan) if self.fan else 0
        self._fan_wall_t0 = self._clock.time()
        self._dev_busy = [0.0] * n_fan
        self.device_ema = [0.0] * n_fan
        self.fan_ema_alpha = 0.3  # same fold as fleet/registry.py
        self.last_win: Optional[dict] = None
        # -- device fault domains (docs/resilience.md) --------------------
        # Per-device healthy/suspect/quarantined state; the watchdog below
        # observes progress from the control channel's per-(row, device)
        # bookkeeping and evacuates a suspect device's uncovered range onto
        # the healthy rest. Auto policy: the watchdog runs wherever the
        # progress signal exists (run_mode=persistent, any width); chunked
        # launches have no mid-launch bookkeeping, so their whole-launch
        # deadline backstop only arms when the operator sets
        # --device_suspect_after explicitly.
        if device_suspect_after < 0:
            raise WorkError("device_suspect_after must be >= 0 (0 = auto)")
        self._watchdog_enabled = (
            run_mode == "persistent" or device_suspect_after > 0
        )
        self.device_suspect_after = device_suspect_after or 30.0
        self.device_probe_interval = device_probe_interval
        self.close_join_timeout = close_join_timeout
        self._dfd = DeviceFaultDomains(
            n_fan or 1,
            suspect_after=self.device_suspect_after,
            probe_interval=device_probe_interval,
            clock=self._clock,
        )
        self._watchdog_task: Optional[asyncio.Task] = None
        self._probe_tasks: Dict[int, asyncio.Task] = {}
        self._devices_exhausted = False
        # EMA of wall seconds per launch window (from applied launches):
        # the poll-cadence → seconds conversion the progress deadlines use.
        self._window_seconds = 0.0
        # EMA of dispatch → first-control-poll latency (XLA compile +
        # dispatch): a launch that has not polled AT ALL yet gets this
        # much extra deadline — a cold compile (~20 s per shape) must not
        # read as a dead device.
        self._first_poll_seconds = 0.0
        self._m_threads_leaked = obs.get_registry().counter(
            "dpow_backend_launch_threads_leaked_total",
            "Launch threads abandoned still running (watchdog ejection, "
            "launch timeout, or wedged past the close() join bound), "
            "detached and counted instead of awaited forever")

    # -- WorkBackend interface -------------------------------------------

    async def setup(self) -> None:
        self._closed = False  # setup() after close() reopens the engine
        from ..utils.logging import get_logger

        n_dev = len(self.fan) if self.fan else (self.mesh.size if self.mesh else 1)
        get_logger("tpu_dpow.backend").info(
            "jax engine on %s (%s) x%d; kernel=%s",
            self.device.platform, self.device.device_kind, n_dev, self.kernel,
        )
        # Self-test: the engine must find a planted easy solution. Also pays
        # the one-time jit compile cost off the event loop.
        probe = search.pack_params(bytes(32), 1, base=0)
        lo, hi = await self._timed_launch(np.stack([probe]), 1)
        # Fan mode returns per-device arrays; flat[0] is device 0 / row 0
        # either way, and device 0's sub-range starts at the probe base.
        if int(lo.flat[0]) != 0 or int(hi.flat[0]) != 0:
            raise WorkError(
                f"backend self-test failed "
                f"(nonce {int(hi.flat[0]):08x}{int(lo.flat[0]):08x})"
            )
        self._warm.add(self._program(1, 1))
        if not self.warm_shapes and len(self._step_counts()) > 1:
            # Warming off (CPU: compiles are cheap): pay the run-mode
            # ladder compiles inline so behavior is fully deterministic.
            # (_step_counts, not run_steps: persistent mode's mega-shape
            # rung exists even at run_steps=1 and the first request must
            # not eat its compile.)
            for steps in self._step_counts()[1:]:
                if self._program(1, steps) == self._program(1, 1):
                    continue  # the self-test's program runs this length
                await self._timed_launch(np.stack([probe]), steps)
                self._warm.add(self._program(1, steps))
        self._m_programs.set(len(self._warm))
        if self.warm_shapes and self._warm_task is None and (
            self.max_batch > 1 or self.run_steps > 1
        ):
            # With warming ON (TPU), setup() returns after the single
            # self-test compile; the rest of the ladder — max_batch, and
            # where programs are keyed by run length (_program) the
            # (1, steps) rungs — compiles in the background (on the chunked
            # Pallas path the self-test ran the one program). Each costs
            # seconds, and a client blocked in setup() serves nothing; a
            # request arriving before its rung is warm just runs at the
            # largest warmed step count (more round trips, still correct —
            # see _pick_shape).
            self._warm_task = asyncio.ensure_future(self._warmup_loop())

    async def generate(self, request: WorkRequest) -> str:
        if self._closed:
            raise WorkError("backend closed")
        if self._devices_exhausted:
            # The fault domains already declared every device quarantined:
            # fail fast so the failover chain serves NOW (it trips this
            # engine's breaker on sight) instead of queueing work behind
            # re-admission probes.
            raise DevicesExhausted(
                f"all {self._dfd.n} device(s) quarantined; awaiting a "
                "successful re-admission probe"
            )
        key = request.block_hash
        existing = self._jobs.get(key)
        if existing is not None and not existing.cancelled and not existing.future.done():
            # Dedup concurrent generates for the same hash (the reference
            # dedups on enqueue, client/work_handler.py:84-89). A stronger
            # difficulty raises the shared job's target: the eventual nonce
            # then satisfies every waiter; a weaker/equal one just shares.
            if request.difficulty > existing.difficulty:
                self._raise_job_target(existing, request.difficulty)
            return await self._await_job(existing)
        job = _Job(
            block_hash=key,
            difficulty=request.difficulty,
            params=search.pack_params(request.hash_bytes, request.difficulty, 0),
            future=asyncio.get_running_loop().create_future(),
            base=0,
            t_submit=time.perf_counter(),
        )
        # Sharded dispatch (tpu_dpow.fleet): an assigned nonce range pins
        # the scan base to the shard start — fleet-level decorrelation by
        # construction. Without one, a random base decorrelates this worker
        # from the racing swarm (SURVEY.md §2.5). The range end is soft:
        # the scan advances past it rather than stranding a dispatch whose
        # shard holds no solution (the server re-covers dead shards; a live
        # worker overrunning into a neighbor's shard is just redundancy).
        if request.nonce_range is not None:
            start, length = request.nonce_range
        else:
            start, length = secrets.randbits(64), 0
        if self.fan is not None:
            self._fan_partition(job, start, length)
        else:
            job.set_base(start)
        self._jobs[key] = job
        self._ensure_engine()
        self._wakeup.set()
        return await self._await_job(job)

    async def _await_job(self, job: _Job) -> str:
        def abort():  # engine drops cancelled jobs from the next pack
            job.cancelled = True
            # ...and a persistent launch frees the rows within one poll.
            self._control_cancel_job(job)

        return await await_shared_job(job, abort)

    async def cancel(self, block_hash: str) -> None:
        job = self._jobs.get(nc.validate_block_hash(block_hash))
        if job is not None and not job.future.done():
            job.cancelled = True
            job.future.set_exception(WorkCancelled(job.block_hash))
            # Persistent launches are steerable: the device frees the
            # cancelled rows within one poll interval instead of grinding
            # them to span end (the whole point of run_mode=persistent).
            self._control_cancel_job(job)

    async def raise_difficulty(self, block_hash: str, difficulty: int) -> bool:
        """Retarget a running job in place; the engine loop's per-launch
        difficulty snapshot keeps an in-flight chunk's weaker hit searching
        on past it at the new target. Persistent launches are retargeted
        MID-FLIGHT through the control channel — the running while_loop
        swaps its difficulty words at the next poll."""
        job = self._jobs.get(nc.validate_block_hash(block_hash))
        if job is None or job.cancelled or job.future.done():
            return False
        if difficulty > job.difficulty:
            self._raise_job_target(job, difficulty)
        return True

    def _raise_job_target(self, job: _Job, difficulty: int) -> None:
        """Raise a job's target AND steer any running persistent launch
        (shared by raise_difficulty and the dedup-upgrade path)."""
        prev_miss = job.inflight_miss
        job.set_difficulty(difficulty)
        covered, span = self._control_raise_job(job, difficulty)
        if covered:
            # A live launch carries the raised target in place: the job
            # stays covered (set_difficulty reset it to uncovered for
            # the chunked case, where in-flight spans scan the OLD
            # target). The covering launch's divide-back clamps at 1.0.
            job.inflight_miss = min(
                prev_miss, self._miss_factor(difficulty, span)
            )

    # -- persistent mid-launch control ------------------------------------

    def _live_controls(self, job: _Job) -> list:
        """(rec, row) for each in-flight persistent launch carrying ``job``,
        oldest first."""
        out = []
        for rec in self._inflight:
            if rec.control is None:
                continue
            for i, j in enumerate(rec.jobs):
                if j is job:
                    out.append((rec, i))
        return out

    def _control_cancel_job(self, job: _Job) -> None:
        """Free the job's device rows: deliver CANCEL to every in-flight
        persistent launch still scanning it. Cancel needs no epoch check —
        stopping a row is valid whatever partition it was aimed at."""
        for rec, row in self._live_controls(job):
            rec.control.cancel(row)

    def _control_raise_job(self, job: _Job, difficulty: int) -> tuple:
        """Deliver a raised target to running launches; (covered, span)
        where covered means at least one CURRENT-epoch launch now scans
        the job at the new difficulty. Stale-epoch launches are skipped —
        their control word is dead (the PR-6 fence: a launch aimed at a
        region the job has left must not be steered as if it were live)."""
        covered, span = False, 0
        for rec, row in self._live_controls(job):
            if rec.dev_epochs[row] != job.dev_epoch:
                continue
            if rec.control.raise_difficulty(row, difficulty, epoch=job.dev_epoch):
                covered, span = True, max(span, rec.span)
        return covered, span

    def _control_rebase_job(self, job: _Job) -> tuple:
        """Re-aim the NEWEST in-flight persistent launch at the job's new
        partition (cover_range already rewrote the job-side frontier and
        bumped ``dev_epoch``); the job's rows in OLDER launches are stale
        under the new epoch, so they are KILLED — the row stops at its
        next poll AND the control word goes dead, refusing any later
        write (the PR-6 fence for running launches).
        Returns (covered, span) of the rebased launch."""
        covered, span = False, 0
        for rec, row in reversed(self._live_controls(job)):
            if not covered:
                span_dev = self.chunk_per_shard * rec.shape[1]
                if self.fan is not None:
                    bases = self._rebase_bases_for(rec, job, span_dev)
                else:
                    bases = [job.base]
                if rec.control.rebase(row, bases, epoch=job.dev_epoch):
                    covered, span = True, rec.span
                    continue
            rec.control.kill(row)
        return covered, span

    async def cover_range(self, block_hash: str, nonce_range: tuple) -> bool:
        """Fleet re-cover: jump a running job's scan to an orphaned shard.

        The next pack dispatches from the new base; chunks already in
        flight finish their old span and apply normally (a hit there is
        still a valid nonce). Coverage accounting resets — the in-flight
        spans no longer predict the new region.
        """
        job = self._jobs.get(nc.validate_block_hash(block_hash))
        if job is None or job.cancelled or job.future.done():
            return False
        self._re_cover(job, nonce_range[0], nonce_range[1])
        return True

    def _re_cover(self, job: _Job, start: int, length: int) -> None:
        """Re-aim a running job at ``[start, start+length)`` — the shared
        core of the fleet cover_range path and the watchdog's device
        evacuation (both epoch-fenced the same way)."""
        if self.fan is not None:
            # EVERY active device shard rebases into the new range (the
            # epoch bump inside _fan_partition keeps old-partition launches
            # still on the wire from feeding the new shards'
            # counters/clocks).
            self._fan_partition(job, start, length)
        else:
            job.set_base(start)
            # Same staleness fence as the fan: a launch already on the wire
            # was aimed at the OLD region — its weak hit (raised-target
            # race, _apply_plain_rows) must not rewind the frontier out of
            # the range this re-cover just claimed.
            job.dev_epoch += 1
        job.inflight_miss = 1.0
        covered, span = self._control_rebase_job(job)
        if covered:
            # A running persistent launch was re-aimed at the new range
            # mid-flight (no relaunch): treat it as the covering launch.
            # Its divide-back at apply restores miss to ~1.0, so any tail
            # of the range it did not reach re-dispatches from the new
            # frontier — bounded overlap, never a gap.
            job.inflight_miss = self._miss_factor(job.difficulty, span)
        self._wakeup.set()

    async def close(self) -> None:
        self._closed = True
        # Detach-then-await (dpowlint DPOW801): a concurrent close() must
        # find the slots already empty, not await the same task twice.
        warm_task, self._warm_task = self._warm_task, None
        if warm_task is not None:
            warm_task.cancel()
            try:
                await warm_task
            except asyncio.CancelledError:
                pass
        watchdog_task, self._watchdog_task = self._watchdog_task, None
        if watchdog_task is not None:
            watchdog_task.cancel()
            await asyncio.gather(watchdog_task, return_exceptions=True)
        probe_tasks, self._probe_tasks = list(self._probe_tasks.values()), {}
        for t in probe_tasks:
            t.cancel()
        if probe_tasks:
            await asyncio.gather(*probe_tasks, return_exceptions=True)
        for job in list(self._jobs.values()):
            if not job.future.done():
                job.future.set_exception(WorkCancelled("backend closed"))
        self._jobs.clear()
        # Persistent launches would otherwise grind their span out in the
        # executor after close: cancel every row so the device threads
        # return within one poll interval.
        for rec in self._inflight:
            if rec.control is not None:
                for i in range(len(rec.jobs)):
                    rec.control.cancel(i)
        self._wakeup.set()
        engine_task, self._engine_task = self._engine_task, None
        if engine_task is not None:
            try:
                await engine_task
            except Exception:
                # The engine already failed its waiters before dying; its
                # exception must not break teardown too.
                pass
        # Bounded join (Clock-driven): give the persistent launch threads
        # one close_join_timeout to come back (their rows are cancelled, so
        # a HEALTHY thread returns within a poll interval). A thread still
        # out past the bound is truly wedged — kill-fence its control rows
        # (a zombie wake-up then stops at its first poll and can steer
        # nothing), DETACH it (the slot retires via the engine-teardown
        # done-callback if it ever returns, and its executor threads are
        # waived from the interpreter-exit join) and COUNT it, instead of
        # blocking shutdown forever.
        joinable = [
            rec for rec in list(self._inflight)
            if rec.control is not None and not self._launch_returned(rec)
        ]
        if joinable:
            step = max(self.close_join_timeout / 20.0, 0.005)
            deadline = self._clock.time() + self.close_join_timeout
            while (
                any(not self._launch_returned(rec) for rec in joinable)
                and self._clock.time() < deadline
            ):
                # Real-thread rendezvous: thread_done is set from executor
                # threads in REAL time, so a frozen FakeClock must not
                # stop close() from observing a healthy return — the
                # real-time poll provides liveness while the BOUND itself
                # rides the injectable clock (the wedged-thread tests
                # advance it to trip the leak path).
                timer = asyncio.ensure_future(self._clock.sleep(step))
                # dpowlint: disable=DPOW101 — liveness poll for real executor threads; the deadline above is what rides the Clock
                poll = asyncio.ensure_future(asyncio.sleep(0.01))
                await asyncio.wait(
                    {timer, poll}, return_when=asyncio.FIRST_COMPLETED
                )
                timer.cancel()
                poll.cancel()
            for rec in joinable:
                if self._launch_returned(rec):
                    continue
                rec.control.kill_all()
                self._m_threads_leaked.inc(1)
                from ..utils.logging import get_logger

                get_logger("tpu_dpow.backend").error(
                    "launch thread (batch=%d, steps=%d) wedged past the "
                    "%.1fs close bound; detached and counted",
                    rec.shape[0], rec.shape[1], self.close_join_timeout,
                )
        self._inflight.clear()
        if self._executor is not None:
            self._detach_executor(self._executor)
            self._executor = None

    # -- device fault domains (docs/resilience.md) ------------------------

    def _ensure_watchdog(self) -> None:
        if not self._watchdog_enabled or self._closed:
            return
        if self._watchdog_task is None or self._watchdog_task.done():
            self._watchdog_task = asyncio.ensure_future(self._watchdog_loop())

    async def _watchdog_loop(self) -> None:
        """Periodic health sweep on the injectable clock: declare devices
        that missed their progress deadline suspect (→ evacuate →
        quarantine) and launch re-admission probes when due."""
        interval = max(self.device_suspect_after / 4.0, 0.01)
        while not self._closed:
            await self._clock.sleep(interval)
            if self._closed:
                return
            try:
                self._watchdog_pass()
            except Exception:
                from ..utils.logging import get_logger

                # A watchdog bug must degrade to "no fault handling", not
                # take the engine down with it.
                get_logger("tpu_dpow.backend").warning(
                    "device watchdog pass failed", exc_info=True)
            self._spawn_due_probes()
            if (
                (self._engine_task is None or self._engine_task.done())
                and not self._inflight
                and len(self._dfd.healthy_devices()) == self._dfd.n
            ):
                return  # idle and fully healthy; _ensure_engine revives us

    def _expected_poll_seconds(self) -> float:
        """Expected wall seconds between a device's control polls, from
        the window-time EMA of applied launches (0.0 until one applies —
        the deadline then floors at device_suspect_after)."""
        return self._window_seconds * max(1, self.control_poll_steps)

    @staticmethod
    def _launch_returned(rec: "_Launch") -> bool:
        """Has the launch THREAD actually come back? Judged by the
        thread_done Event (set in the thread's own finally), because the
        asyncio wrapper lies: a launch-timeout cancels ``rec.fut`` while
        the executor thread may still be wedged on the device — exactly
        the launches the close bound and the watchdog exist to catch
        (dpowlint DPOW1004). ``fut`` stands in only for pre-Event
        launches (tests installing bare records)."""
        if rec.thread_done is not None:
            return rec.thread_done.is_set()
        return rec.fut.done()

    def _watchdog_pass(self) -> None:
        """One sweep over the in-flight launches: progress is read from
        the control channel's per-(row, device) poll/done bookkeeping —
        a device is EXPECTED to poll every control_poll_steps windows
        until all its rows are done or it clears its final poll block."""
        now = self._clock.time()
        suspects: list = []
        hung_chunked: list = []
        for rec in list(self._inflight):
            # thread_done, not fut: a timeout-cancelled wrapper must not
            # hide a still-wedged launch from the sweep (DPOW1004).
            if self._launch_returned(rec) or rec.abandoned:
                continue
            if rec.control is not None:
                deadline = launch_deadline(
                    self._expected_poll_seconds(), self.device_suspect_after
                )
                if rec.control.first_poll_t is None:
                    # Compile + dispatch still in front of the program's
                    # first poll: grant a grace window (at least double,
                    # plus the measured first-poll EMA scaled) so a cold
                    # XLA compile does not read as a dead device.
                    deadline += max(
                        deadline, self._first_poll_seconds * DEADLINE_SLACK
                    )
                for s, d in enumerate(rec.fan_map or [0]):
                    if self._dfd.state(d) != HEALTHY or d in suspects:
                        continue
                    if rec.control.device_accounted(
                        s, rec.shape[1], self.control_poll_steps
                    ):
                        continue
                    t, _k = rec.control.last_poll(s)
                    last = t if t is not None else rec.t_clock
                    if now - last > deadline:
                        suspects.append(d)
            else:
                # Chunked launches have no mid-launch bookkeeping: the
                # whole launch is the unit, its deadline run_steps-scaled.
                # No per-device evidence → evacuate without quarantining.
                deadline = launch_deadline(
                    self._window_seconds * rec.shape[1],
                    self.device_suspect_after,
                )
                if self._window_seconds <= 0.0:
                    # No timing history yet: the first launch may be
                    # paying an XLA compile — the chunked twin of the
                    # persistent branch's no-first-poll grace.
                    deadline *= 2.0
                if now - rec.t_clock > deadline:
                    hung_chunked.append(rec)
        for d in suspects:
            self._declare_suspect(d)
        for rec in hung_chunked:
            if rec in self._inflight:
                self._evacuate_launch(rec, reason="launch_hang")

    def _declare_suspect(self, d: int) -> None:
        """healthy → suspect → (evacuate) → quarantined, exactly once.

        Every launch pinned by the suspect device is ejected (a pmap
        launch cannot return while one member hangs) with its control rows
        kill-fenced, then each affected job's uncovered remainder — the
        suspect device's effective base plus its provably-dry windows — is
        re-covered onto the remaining healthy devices through the
        epoch-fenced cover_range path. Subsequent launches run at degraded
        fan width until a probe re-admits the device."""
        if not self._dfd.mark_suspect(d):
            return
        wrecked = [
            rec for rec in list(self._inflight)
            if not self._launch_returned(rec) and not rec.abandoned
            and d in (rec.fan_map or [0])
        ]
        evacuations: Dict[int, tuple] = {}
        for rec in wrecked:
            for i, job in enumerate(rec.jobs):
                if job.cancelled or job.future.done():
                    continue
                start, length = self._dead_remainder(rec, i, job, d)
                prev = evacuations.get(id(job))
                # Several wrecked launches: keep the least-advanced
                # remainder (re-covering a superset is overlap, not a gap).
                if prev is None or ((start - job.part_start) & _MASK64) < (
                    (prev[1] - job.part_start) & _MASK64
                ):
                    evacuations[id(job)] = (job, start, length)
            self._eject_launch(rec)
        for job, start, length in evacuations.values():
            self._re_cover(job, start, length)
        if evacuations:
            # The counter means "a range was re-covered": a suspect device
            # whose launches carried only done/cancelled jobs evacuates
            # nothing (same guard as _evacuate_launch).
            self._dfd.record_evacuation("stalled_poll")
        self._dfd.quarantine(d)
        if self._dfd.exhausted():
            self._fail_devices_exhausted()
        self._wakeup.set()

    def _dead_remainder(self, rec: "_Launch", i: int, job: _Job, d: int) -> tuple:
        """The suspect device's uncovered remainder of row ``i``: its
        effective base (a delivered mid-launch rebase counts) advanced by
        the windows its own polls PROVED dry, out to the end of the job's
        recorded partition range (length 0 = soft / full span)."""
        fan_map = rec.fan_map or [0]
        s = fan_map.index(d)
        if rec.dev_bases is not None:
            base = rec.dev_bases[i][s]
        else:
            base = rec.bases[i]
        windows = 0
        if rec.control is not None:
            eb = rec.control.effective_base(i, s)
            windows = rec.control.confirmed_no_hit_windows(
                i, s, self.control_poll_steps
            )
            if eb is not None:
                # A delivered rebase re-aimed the device at eb AT window
                # applied_at_k: only the windows after that boundary were
                # scanned from the new base — counting the pre-rebase ones
                # would advance the evacuation frontier past nonces the
                # device never visited (a gap, not an overlap; the apply
                # path subtracts the same boundary for scan credit).
                base = eb
                windows = max(0, windows - rec.control.applied_at_k(i, s))
        start = (base + windows * self.chunk_per_shard) & _MASK64
        if job.part_len:
            end = (job.part_start + job.part_len) & _MASK64
            length = (end - start) & _MASK64
            if length > job.part_len:
                length = 0  # frontier already past the range end: soft
            return start, length
        return start, 0

    def _eject_launch(self, rec: "_Launch") -> None:
        """Pull a wrecked launch out of the pipeline: its results are
        discarded (never applied), its control rows are kill-fenced so the
        zombie thread stops at its first wake-up poll and cannot be
        steered, and the executor is replaced so the wedged worker cannot
        starve later launches (the launch-timeout idiom)."""
        rec.abandoned = True
        try:
            self._inflight.remove(rec)
        except ValueError:
            pass
        if rec.waiter is not None:
            rec.waiter.cancel()
        for job, f in zip(rec.jobs, rec.miss_factors):
            if not job.future.done() and not job.cancelled:
                # Its span will never be applied: undo the coverage factor.
                job.inflight_miss = min(1.0, job.inflight_miss / f)
        if rec.control is not None:
            rec.control.kill_all()
            _retire_on_done(rec.fut, rec.slot)
        else:
            rec.fut.add_done_callback(_consume_abandoned)
        if rec.thread_done is not None and not rec.thread_done.is_set():
            # The ejection abandons a thread that is still out — count it
            # (most drain when the zombie device wakes; the counter
            # measures abandonment events, matching the close() bound).
            self._m_threads_leaked.inc(1)
        if self._executor is not None:
            self._detach_executor(self._executor)
            self._executor = None
        self._wakeup.set()

    def _evacuate_launch(self, rec: "_Launch", reason: str) -> None:
        """Whole-launch evacuation (chunked backstop): eject the launch
        and re-cover each live job from the launch's own dispatch frontier
        (fan: the whole recorded partition range — chunked launches carry
        no per-device progress evidence to narrow it)."""
        jobs = [
            (i, j) for i, j in enumerate(rec.jobs)
            if not j.cancelled and not j.future.done()
        ]
        self._eject_launch(rec)
        for i, job in jobs:
            if self.fan is not None:
                self._re_cover(job, job.part_start, job.part_len)
            else:
                self._re_cover(job, rec.bases[i], 0)
        if jobs:
            self._dfd.record_evacuation(reason)

    def _fail_devices_exhausted(self) -> None:
        """Zero healthy devices: the engine declares ITSELF dead — every
        live waiter fails NOW with DevicesExhausted (the failover chain
        trips this engine's breaker on sight instead of waiting out its
        hang budget) and new generates refuse until a probe re-admits a
        device."""
        self._devices_exhausted = True
        err_msg = (
            f"all {self._dfd.n} device(s) quarantined; awaiting a "
            "successful re-admission probe"
        )
        for job in list(self._jobs.values()):
            if not job.future.done():
                job.cancelled = True
                self._control_cancel_job(job)
                job.future.set_exception(DevicesExhausted(err_msg))
        self._wakeup.set()

    def _spawn_due_probes(self) -> None:
        for d in range(self._dfd.n):
            if not self._dfd.probe_due(d):
                continue
            task = self._probe_tasks.get(d)
            if task is not None and not task.done():
                continue
            self._probe_tasks[d] = asyncio.ensure_future(self._probe_device(d))

    async def _probe_device(self, d: int) -> None:
        """The single re-admission launch for quarantined device ``d``: a
        difficulty-1 probe row must come back (hitting at offset 0, the
        setup self-test contract) within the probe bound on the injectable
        clock. Success re-admits the device and re-balances live jobs over
        the restored fan; failure re-opens the probe interval."""
        probe = search.pack_params(bytes(32), 1, base=0)
        devs = (self.fan[d],) if self.fan is not None else None
        ok = False
        fut = None
        try:
            fut = self._submit_launch(np.stack([probe]), 1, devices=devs)
            timer = asyncio.ensure_future(
                self._clock.sleep(self.device_suspect_after)
            )
            await asyncio.wait(
                {fut, timer}, return_when=asyncio.FIRST_COMPLETED
            )
            if fut.done():
                timer.cancel()
                lo, hi = fut.result()
                ok = int(lo.flat[0]) == 0 and int(hi.flat[0]) == 0
            else:
                # The probe itself hung: abandon its thread (counted) and
                # hand later launches a fresh executor.
                fut.add_done_callback(_consume_abandoned)
                self._m_threads_leaked.inc(1)
                if self._executor is not None:
                    self._detach_executor(self._executor)
                    self._executor = None
        except asyncio.CancelledError:
            if fut is not None and not fut.done():
                fut.add_done_callback(_consume_abandoned)
            raise
        except Exception:
            ok = False  # a crashing probe is a failed probe
        prev_active = self._fan_active if self.fan is not None else None
        self._dfd.probe_result(d, ok)
        if not ok:
            return
        self._devices_exhausted = False
        if self.fan is not None:
            # Re-balance live jobs over the restored fan: re-partition each
            # from its least-advanced healthy frontier — overlap over gaps
            # (soft ranges), and the epoch bump fences degraded launches
            # still on the wire.
            for job in list(self._jobs.values()):
                if job.cancelled or job.future.done():
                    continue
                if job.dev_bases is not None and prev_active:
                    # Least-advanced frontier RELATIVE to the partition
                    # start (wrap-aware): a range wrapping 2^64 makes the
                    # numerically smallest base the MOST advanced shard.
                    start = min(
                        (job.dev_bases[dd] for dd in prev_active),
                        key=lambda bs: (bs - job.part_start) & _MASK64,
                    )
                else:
                    start = job.base
                length = 0
                if job.part_len:
                    length = (job.part_start + job.part_len - start) & _MASK64
                    if length > job.part_len:
                        length = 0
                self._re_cover(job, start, length)
        self._wakeup.set()

    @staticmethod
    def _detach_executor(executor) -> None:
        """shutdown(wait=False) AND waive the pool's threads from the
        interpreter-exit join: concurrent.futures registers every worker
        in a module-global table that Python joins at shutdown, so one
        wedged launch thread would otherwise hang process exit forever —
        the exact failure the close bound exists for. Running healthy
        threads still complete and resolve their futures; only the
        exit-join is waived (private API, stable since 3.9)."""
        import concurrent.futures.thread as cft

        executor.shutdown(wait=False)
        for t in list(getattr(executor, "_threads", ()) or ()):
            cft._threads_queues.pop(t, None)

    # -- engine -----------------------------------------------------------

    def _ensure_engine(self) -> None:
        if self._engine_task is None or self._engine_task.done():
            self._engine_task = asyncio.ensure_future(self._engine_loop())
        self._ensure_watchdog()

    def _batch_sizes(self) -> list:
        """The padded batch sizes the engine may emit (ascending). On the
        chunked Pallas path every size runs one program (_program).

        With shape warming on (TPU) there are exactly TWO: singleton and
        max_batch. Difficulty-0 padding rows are free on the Pallas path
        (measured: an all-pads batch-16 launch costs the bare round-trip
        floor), so intermediate sizes would only multiply the programs
        (_program) — each one is seconds of warmup during which the engine
        would fall back to singleton launches and batching throughput would
        sit at 1/launch-time.

        With warming off (CPU/xla path: no early exit, pads scan their full
        window) the ladder is the classic powers of two, compiled on demand.
        """
        if self.warm_shapes:
            return [1, self.max_batch] if self.max_batch > 1 else [1]
        sizes, b = [], 1
        while b < self.max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(self.max_batch)
        return sizes

    async def _warmup_loop(self) -> None:
        """Background-compile the remaining launch programs (_program).

        Probe rows solve at offset 0, so device time is negligible — each
        iteration's cost is the compile itself, after which the shape
        becomes eligible for real launches.
        """
        probe = search.pack_params(bytes(32), 1, base=0)
        try:
            # Priority order: the flood shape (max_batch, 1) first — batched
            # base-difficulty traffic is the dominant cold-start load — then
            # the singleton run-mode rungs (solo-request latency), then the
            # batched run-mode rungs.
            shapes = [(b, 1) for b in self._batch_sizes()[1:]]
            shapes += [(1, s) for s in self._step_counts()[1:]]
            shapes += [
                (b, s)
                for b in self._batch_sizes()[1:]
                for s in self._step_counts()[1:]
            ]
            for b, steps in shapes:
                if self._closed:
                    return
                if self._program(b, steps) in self._warm:
                    continue
                await self._timed_launch(np.stack([probe] * b), steps)
                # dpowlint: disable=DPOW801 — one warm task exists per backend (close() joins it before a successor could start) and set.add is idempotent; a racing inline warm costs one duplicate compile, never corrupts state
                self._mark_warm(b, steps)
        except asyncio.CancelledError:
            raise
        except Exception:
            # A failed warm compile must neither kill close() nor go
            # unnoticed: the engine keeps running on the shapes already
            # warmed, just without the bigger ones.
            from ..utils.logging import get_logger

            get_logger("tpu_dpow.backend").warning(
                "launch-shape warmup failed; engine stays on %d warmed shapes",
                len(self._warm),
                exc_info=True,
            )

    def _rows_at_run_time(self) -> bool:
        """A chunked Pallas launch — plain, fan or mesh — takes its row and
        window counts at run time: one program, at max_batch rows, serves
        every launch (_launch pads to it)."""
        return self.kernel == "pallas" and self.run_mode == "chunked"

    def _program(self, b: int, steps: int) -> tuple:
        """The compiled launch program a (batch, steps) launch runs, as the
        warm set keys it: (max_batch, None) for every chunked Pallas launch
        (_rows_at_run_time). Persistent mode's while_loop and the XLA
        scanner compile each batch and run length: (b, steps)."""
        if self._rows_at_run_time():
            return self.max_batch, None
        return b, steps

    def _mark_warm(self, b: int, steps: int) -> None:
        self._warm.add(self._program(b, steps))
        self._m_programs.set(len(self._warm))

    def cold_shapes(self) -> list:
        """The warm ladder's launch programs (_program) not compiled yet."""
        ladder = {
            self._program(b, s)
            for b in self._batch_sizes()
            for s in self._step_counts()
        }
        return sorted(ladder - self._warm)

    def _pick_shape(self, njobs: int, steps_want: int) -> tuple:
        """Largest warmed launch shape covering the demand.

        Falls back to fewer steps (more round trips) or a smaller batch
        (jobs beyond it wait one engine pass) rather than stalling every
        active request behind a cold compile. On the chunked Pallas path
        the shape is the demand itself: its one program runs any rows and
        run length.
        """
        want = min(max(njobs, 1), self.max_batch)
        if self._rows_at_run_time():
            return want, steps_want  # one program serves every shape
        b_want = next(b for b in self._batch_sizes() if b >= want)
        if not self.warm_shapes or not self._warm:
            # Warming off (CPU default) or nothing warmed yet (generate()
            # without setup()): launch the wanted shape, compiling inline.
            return b_want, steps_want
        warmed_bs = sorted({b for b, _ in self._warm})
        fitting = [b for b in warmed_bs if b >= b_want]
        b = fitting[0] if fitting else warmed_bs[-1]
        if self._program(b, steps_want) in self._warm:
            return b, steps_want
        cands = [s for bb, s in self._warm if bb == b and s <= steps_want]
        steps = max(cands) if cands else steps_want  # compile inline if cold
        return b, steps

    def _step_counts(self) -> list:
        """The quantized run lengths the engine may emit (ascending).

        The default ladder is powers of four: granular enough that easy
        difficulties return to the host (and thus to fresh arrivals and
        cancels) after one or two windows. On the chunked Pallas path the
        count is a runtime operand and costs no compile; on the XLA path
        each count is a separate compile (_program), few enough to warm at
        setup. The ``step_ladder="x2"`` option halves the quantization step
        (base difficulty then launches 2 windows instead of 4 — less span
        to drain past the hit), at the cost of ~2x the XLA warm compiles;
        which wins is an on-chip measurement (benchmarks/latency.py A/B).
        """
        if self.run_mode == "persistent":
            # One steerable mega-shape (plus the singleton the setup probe
            # and cold fallbacks use): the while_loop's early exit makes
            # run-length quantization pointless — every launch compiles to
            # the same max_steps and returns on win/cancel/span end.
            if self.persistent_steps > 1:
                return [1, self.persistent_steps]
            return [1]
        factor = 2 if self.step_ladder == "x2" else 4
        counts, steps = [1], 1
        while steps < self.run_steps:
            steps = min(steps * factor, self.run_steps)
            counts.append(steps)
        return counts

    @staticmethod
    def _solve_p(difficulty: int) -> float:
        """Per-nonce solve probability, floored away from 0.0 (difficulty
        can be 2^64-1) — the one probability model shared by rung sizing
        (_steps_for) and coverage accounting (_miss_factor)."""
        return max((2**64 - difficulty) / 2**64, 1e-30)

    def _steps_for(self, difficulty: int) -> int:
        """Windows one launch should cover for this difficulty: enough that
        the median solve finishes in a single round trip (2x the median
        window count), clamped to the run_steps cancel-latency cap.

        Persistent mode has no cancel-latency cap to clamp to (the control
        channel bounds cancel at one poll interval), so every difficulty
        gets the span-sized launch — one host round trip per request, the
        in-loop early exit returns easy rows after their first window."""
        if self.run_mode == "persistent":
            return self.persistent_steps
        median = math.log(2) / self._solve_p(difficulty)
        windows = 2 * median / self.chunk
        for steps in self._step_counts():
            if steps >= windows:
                return steps
        return self.run_steps

    def _submit_launch(
        self,
        params_batch: np.ndarray,
        steps: int,
        timing: Optional[dict] = None,
        slot: int = 0,
        devices: Optional[tuple] = None,
        thread_done: Optional[threading.Event] = None,
    ) -> asyncio.Future:
        """Hand a launch to the executor; device work starts immediately.
        ``slot`` routes a persistent launch's control polls (0 = no control
        block registered: the launch reads dead zeros and just runs).
        ``devices`` pins the launch to a fan subset (degraded width /
        re-admission probes); None = the engine's full complement."""
        rows = params_batch.shape[-2]
        self._m_launches.inc(1, str(rows), str(steps))
        if self._rows_at_run_time():
            self._m_rows_skipped.inc(self.max_batch - rows)
        if self._executor is None:
            import concurrent.futures

            # pipeline launch threads + one for warm compiles.
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.pipeline + 1
            )
        loop = asyncio.get_running_loop()

        def call_launch():
            # Chunked full-width launches (slot 0) keep the two-arg call:
            # _launch wrappers installed by tests and tooling predate the
            # slot and the device-subset kwarg.
            try:
                if devices is not None:
                    return self._launch(
                        params_batch, steps, slot, devices=devices
                    )
                if slot:
                    return self._launch(params_batch, steps, slot)
                return self._launch(params_batch, steps)
            finally:
                # The control slot lives exactly as long as the thread:
                # releasing any earlier feeds a still-running loop dead
                # zeros and UNDOES its cancel/kill flags (the rows then
                # grind the whole span while pinning an execution thread
                # — observed starving the evacuation's recovery launch
                # when an ejected launch's cancelled future released the
                # slot early). release() is idempotent; the apply path's
                # and teardown's releases remain as belt-and-suspenders.
                if slot:
                    ctl.release(slot)
                # The thread-return flag the close()/watchdog bounds watch
                # — the asyncio future lies once its waiter is cancelled.
                if thread_done is not None:
                    thread_done.set()

        if timing is None:
            return loop.run_in_executor(self._executor, call_launch)

        def timed():  # stamps the executor-queue and device stages
            timing["t_thread"] = time.perf_counter()
            # Injectable-clock twin of the device-time stamps: the fan's
            # busy-fraction gauge divides busy by wall measured on the SAME
            # clock (SystemClock: identical to the perf stamps; FakeClock:
            # deterministic, advanced only by the test).
            timing["t_thread_clock"] = self._clock.time()
            out = call_launch()
            timing["t_done"] = time.perf_counter()
            timing["t_done_clock"] = self._clock.time()
            return out

        return loop.run_in_executor(self._executor, timed)

    async def _await_launch(self, fut: asyncio.Future, shape_note: str) -> tuple:
        if self.launch_timeout is None:
            return await fut
        try:
            return await asyncio.wait_for(fut, self.launch_timeout)
        except asyncio.TimeoutError:
            # The wedged thread cannot be killed; abandon the whole executor
            # so later launches get fresh workers instead of queueing behind
            # the stuck one. (Other in-flight launches on it are presumed
            # wedged on the same device and abandoned with it.) Detached
            # from the interpreter-exit join and counted, like every other
            # abandoned-thread site.
            self._detach_executor(self._executor)
            self._executor = None
            self._m_threads_leaked.inc(1)
            raise WorkError(
                f"device launch exceeded {self.launch_timeout:.0f}s "
                f"({shape_note}) — device hang"
            )

    async def _timed_launch(self, params_batch: np.ndarray, steps: int) -> tuple:
        """_launch off the event loop, bounded by launch_timeout."""
        return await self._await_launch(
            self._submit_launch(params_batch, steps),
            f"batch={params_batch.shape[0]}, steps={steps}",
        )

    def _launch(
        self,
        params_batch: np.ndarray,
        steps: int,
        slot: int = 0,
        devices: Optional[tuple] = None,
    ) -> tuple:
        """One blocking batched device launch (called via to_thread).

        Returns (lo, hi) uint32[B] — absolute winning nonces per row,
        all-ones where the scanned span held no solution. A chunked Pallas
        launch pads its B rows to max_batch for its one program and visits
        only the B (_pad_rows); elsewhere padding rows arrive packed and
        short-circuit via difficulty 0, their results discarded.
        ``steps`` > 1 widens the
        launch to ``steps`` consecutive windows in the same single dispatch
        (bigger ``nblocks`` grid / chunk), so the whole span costs one
        host↔device round trip and early-exits per request as soon as a
        window hits. In persistent mode the same span runs as a
        device-resident while_loop polling control slot ``slot`` between
        windows (one compile per shape; the slot id is a traced value).
        ``devices`` pins a fan launch to a subset of the fan (degraded
        width after quarantine, single-device re-admission probes).
        """
        ctl.launch_hook(self._launch_hook_indices(devices))
        if self.run_mode == "persistent":
            return self._launch_persistent(params_batch, steps, slot, devices)
        nblocks = self.nblocks * steps
        # The launch's program (_program): on Pallas one, the row and window
        # counts runtime operands under the static max_batch and run_steps;
        # the XLA scanner's batch and chunk are static, so there the bounds
        # are this launch.
        n_rows = params_batch.shape[-2]
        bound = self.nblocks * (self.run_steps if self.kernel == "pallas" else steps)
        windows = pallas_kernel.window_count(nblocks, bound)
        chunk_bound = pallas_kernel.chunk_size(self.sublanes, self.iters) * bound
        if self._rows_at_run_time():
            rows = pallas_kernel.row_count(n_rows, self.max_batch)
            params_batch = self._pad_rows(params_batch, self.max_batch)
        else:
            rows = pallas_kernel.row_count(n_rows, n_rows)
        if self.fan is not None:
            from ..parallel import fan_search_devices

            devs = tuple(devices) if devices is not None else tuple(self.fan)
            n = len(devs)
            span_dev = self.chunk_per_shard * steps
            if params_batch.ndim == 2:
                # Bare rows (setup self-test, warm probes): interleave from
                # each row's own base so the fan covers a contiguous window.
                params_batch = self._fan_stack_probe(params_batch, n, span_dev)
            with obs.span("dpow.launch.wait"):
                offs = fan_search_devices(
                    params_batch,
                    rows,
                    windows,
                    devices=devs,
                    chunk_per_shard=chunk_bound,
                    kernel=self.kernel,
                    sublanes=self.sublanes,
                    iters=self.iters,
                    nblocks=bound,
                    group=self.group,
                    interpret=self.interpret,
                )[:, :n_rows]
            params_batch = params_batch[:, :n_rows]
            flat_p = params_batch.reshape(-1, search.PARAMS_LEN)
            lo, hi = self._offsets_to_nonces(flat_p, offs.reshape(-1))
            # Per-device absolute nonces [n_dev, B] (all-ones where that
            # device's span was dry); the host elects the winner against
            # the launch's base snapshot and keeps the attribution.
            return lo.reshape(offs.shape), hi.reshape(offs.shape)
        if self.mesh is not None:
            from ..parallel import replicate_params, sharded_search_chunk_batch

            with obs.span("dpow.launch.wait"):
                offs = np.asarray(
                    sharded_search_chunk_batch(
                        replicate_params(params_batch, self.mesh),
                        rows,
                        windows,
                        mesh=self.mesh,
                        chunk_per_shard=chunk_bound,
                        kernel=self.kernel,
                        sublanes=self.sublanes,
                        iters=self.iters,
                        nblocks=bound,
                        group=self.group,
                        interpret=self.interpret,
                    )
                )[:n_rows]
            return self._offsets_to_nonces(params_batch[:n_rows], offs)
        # The plain path, phase by phase on the profiler's clock: the
        # (asynchronous) dispatch, the device run, the host readback.
        with obs.span("dpow.launch.dispatch"):
            pj = jax.device_put(params_batch, self.device)
            if self.kernel == "pallas":
                out = pallas_kernel.pallas_search_chunk_batch(
                    pj,
                    rows,
                    windows,
                    sublanes=self.sublanes,
                    iters=self.iters,
                    nblocks=bound,
                    group=self.group,
                    interpret=self.interpret,
                )
            else:
                out = search.search_chunk_batch(pj, chunk_size=self.chunk * steps)
        with obs.span("dpow.launch.wait"):
            out.block_until_ready()
        with obs.span("dpow.launch.readback"):
            offs = np.asarray(out)[:n_rows]
        return self._offsets_to_nonces(params_batch[:n_rows], offs)

    def _launch_hook_indices(self, devices: Optional[tuple]) -> tuple:
        """PHYSICAL fan indices this launch touches — the chaos seam's
        device identities (ops/control.py launch_hook)."""
        if self.fan is None:
            return (0,)
        if devices is None:
            return tuple(range(len(self.fan)))
        return tuple(self.fan.index(d) for d in devices)

    def _launch_persistent(
        self,
        params_batch: np.ndarray,
        steps: int,
        slot: int,
        devices: Optional[tuple] = None,
    ) -> tuple:
        """One blocking PERSISTENT launch: a device-resident while_loop of
        ``steps`` windows (ops/runloop.py) that polls control slot ``slot``
        every ``control_poll_steps`` windows and returns only on win,
        cancel or span end. Same (lo, hi) absolute-nonce contract as the
        chunked ``_launch`` on every gang flavor; the per-window geometry
        (``self.chunk``) is identical, so ``span = chunk * steps`` and the
        warm-shape ladder key (batch, steps) mean the same thing in both
        modes — only the dispatch structure differs (one round trip per
        REQUEST instead of per ``run_steps`` windows).
        """
        if self.fan is not None:
            from ..parallel import fan_search_run_controlled

            devs = tuple(devices) if devices is not None else tuple(self.fan)
            n = len(devs)
            if params_batch.ndim == 2:
                # Bare rows (setup self-test, warm probes): block-interleave
                # from each row's own base, as the controlled fan scans
                # contiguously per device.
                params_batch = self._fan_stack_probe(
                    params_batch, n, self.chunk_per_shard * steps
                )
            with obs.span("dpow.launch.wait"):
                lo, hi = fan_search_run_controlled(
                    params_batch,
                    slot,
                    devices=devs,
                    chunk_per_shard=self.chunk_per_shard,
                    max_steps=steps,
                    poll_steps=self.control_poll_steps,
                    kernel=self.kernel,
                    sublanes=self.sublanes,
                    iters=self.iters,
                    nblocks=self.nblocks,
                    group=self.group,
                    interpret=self.interpret,
                )
            return lo, hi
        # No mesh branch: persistent + shard_map mesh is refused at
        # construction (SPMD control-poll divergence — see __init__).
        with obs.span("dpow.launch.wait"):
            lo, hi = runloop.search_run_batch_controlled(
                jax.device_put(params_batch, self.device),
                None,
                np.uint32(slot),
                max_steps=steps,
                poll_steps=self.control_poll_steps,
                kernel=self.kernel,
                sublanes=self.sublanes,
                iters=self.iters,
                nblocks=self.nblocks,
                group=self.group,
                interpret=self.interpret,
            )
            return np.asarray(lo), np.asarray(hi)

    @staticmethod
    def _offsets_to_nonces(params_batch: np.ndarray, offs: np.ndarray) -> tuple:
        """Single-window offsets → the run-mode (lo, hi) nonce contract."""
        base_lo = params_batch[:, search.BASE_LO]
        win_lo = (base_lo + offs).astype(np.uint32)  # uint32 wrap
        carry = (win_lo < base_lo).astype(np.uint32)
        win_hi = (params_batch[:, search.BASE_HI] + carry).astype(np.uint32)
        unsolved = offs == search.SENTINEL
        ones = np.uint32(0xFFFFFFFF)
        return np.where(unsolved, ones, win_lo), np.where(unsolved, ones, win_hi)

    _PAD_ROW = None  # lazily built difficulty-0 padding row

    def _pack(self, jobs: list, b: int) -> np.ndarray:
        """Fixed-shape batch: active jobs + difficulty-0 padding (_pad_rows).
        Pad results are discarded by the engine (only the first len(jobs)
        rows are read back)."""
        rows = np.array([j.params for j in jobs], dtype=np.uint32)
        return self._pad_rows(rows.reshape(-1, search.PARAMS_LEN), b)

    @staticmethod
    def _pad_rows(rows: np.ndarray, b: int) -> np.ndarray:
        """uint32[..., n, 12] rows → uint32[..., b, 12], difficulty-0 rows
        appended.

        Difficulty 0 makes a padding row "hit" at offset 0, so wherever a
        grid does visit it (persistent mode, the XLA scanner) the per-row
        found flag skips all its windows and the in-window early exit fires
        after one tile group — an unreachable-difficulty pad would instead
        scan the launch's whole widened span every pass. A chunked Pallas
        launch's runtime row count keeps its pads out of the grid.
        """
        if JaxWorkBackend._PAD_ROW is None:
            JaxWorkBackend._PAD_ROW = search.pack_params(bytes(32), 0, 0)
        pad = np.broadcast_to(
            JaxWorkBackend._PAD_ROW,
            rows.shape[:-2] + (b - rows.shape[-2], search.PARAMS_LEN),
        )
        return np.concatenate([rows, pad], axis=-2)

    # -- device fan (devices >= 1) ----------------------------------------

    @property
    def _fan_active(self) -> list:
        """PHYSICAL indices of the devices currently in the fan — the
        healthy set of the fault domains (docs/resilience.md). Quarantined
        devices are excluded from partitions and launches until a probe
        re-admits them; the single source of truth is the state machine."""
        return self._dfd.healthy_devices()

    def _fan_partition(self, job: _Job, start: int, length: int) -> None:
        """Sub-partition ``[start, start+length)`` (length 0 = full 2^64
        span) across the HEALTHY fan — the fleet partition idiom one level
        down, at whatever width the fault domains currently allow.

        'split' gives each device a contiguous macro-range (its own shard:
        per-device frontier, scan counter and scan clock — EMA attribution
        mirrors the fleet's (nonces from shard start)/(elapsed) formula).
        'interleave' keeps ONE frontier and deals consecutive per-launch
        windows round-robin (device d takes the d-th window of every
        launch), which matches the mesh gang's coverage order exactly.
        Ends are soft either way, like fleet shards: a device may overrun
        into its neighbor's sub-range rather than strand a dispatch whose
        shard holds no solution.
        """
        n_total = len(self.fan)
        active = self._fan_active
        n = max(len(active), 1)
        job.set_base(start)
        job.part_start, job.part_len = start & _MASK64, length
        if self.device_shard == "split":
            stride = max((length or (1 << 64)) // n, 1)
            # Full-length table (stale entries for quarantined devices are
            # never packed); strides go to the healthy set in order.
            if job.dev_bases is None or len(job.dev_bases) != n_total:
                job.dev_bases = [start & _MASK64] * n_total
            for i, d in enumerate(active):
                job.dev_bases[d] = (start + i * stride) & _MASK64
        else:
            job.dev_bases = None  # derived from the frontier at pack time
        job.dev_scanned = [0] * n_total
        job.dev_t0 = None  # stamped at the first dispatch of this partition
        job.dev_epoch += 1

    def _fan_launch_bases(self, job: _Job, span_dev: int) -> list:
        """This launch's per-slice bases for one job (pre-advance),
        parallel to the current healthy set ``self._fan_active``."""
        active = self._fan_active
        if job.dev_bases is not None:  # split: each device's own frontier
            return [job.dev_bases[d] for d in active]
        # interleave: consecutive windows of the single frontier
        return [
            (job.base + i * span_dev) & _MASK64 for i in range(len(active))
        ]

    def _rebase_bases_for(self, rec: "_Launch", job: _Job, span_dev: int) -> list:
        """Per-slice rebase bases for a RUNNING launch — keyed by the
        launch's own fan_map, which may differ from the current healthy
        set (a pre-quarantine launch still live on the wire)."""
        fan_map = rec.fan_map or list(range(len(self.fan)))
        if job.dev_bases is not None:
            return [job.dev_bases[d] for d in fan_map]
        return [
            (job.base + s * span_dev) & _MASK64 for s in range(len(fan_map))
        ]

    def _fan_advance(self, job: _Job, span_dev: int) -> None:
        """Speculative frontier advance at dispatch (active device shards)."""
        active = self._fan_active
        if job.dev_bases is not None:
            for d in active:
                job.dev_bases[d] = (job.dev_bases[d] + span_dev) & _MASK64
        else:
            job.set_base(job.base + span_dev * max(len(active), 1))

    def _fan_stack(self, jobs: list, b: int, steps: int) -> tuple:
        """Fan batch: uint32[n_dev, b, 12] plus the per-job base snapshot.

        Row content matches _pack (active jobs + difficulty-0 padding);
        each device's slice carries that device's base words. Padding rows
        hit at offset 0 on every device and early-exit, exactly as on the
        single-device path. Width is the HEALTHY fan: quarantined devices
        get no slice (the launch runs at degraded width on the rest).
        """
        n = len(self._fan_active)
        span_dev = self.chunk_per_shard * steps
        rows = self._pack(jobs, b)
        stacked = np.repeat(rows[None], n, axis=0)
        snap = []
        for i, job in enumerate(jobs):
            bases = self._fan_launch_bases(job, span_dev)
            snap.append(bases)
            for d, base in enumerate(bases):
                stacked[d, i, search.BASE_LO] = base & 0xFFFFFFFF
                stacked[d, i, search.BASE_HI] = base >> 32
        return stacked, snap

    @staticmethod
    def _fan_stack_probe(params_batch: np.ndarray, n: int, span_dev: int) -> np.ndarray:
        """Stack bare rows (setup/warm probes) with interleaved bases."""
        stacked = np.repeat(params_batch[None], n, axis=0)
        base_lo = params_batch[:, search.BASE_LO].astype(np.uint64)
        base_hi = params_batch[:, search.BASE_HI].astype(np.uint64)
        bases = (base_hi << np.uint64(32)) | base_lo
        for d in range(n):
            nb = (bases + np.uint64(d) * np.uint64(span_dev)) & np.uint64(_MASK64)
            stacked[d, :, search.BASE_LO] = (nb & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            stacked[d, :, search.BASE_HI] = (nb >> np.uint64(32)).astype(np.uint32)
        return stacked

    def _next_rung(self, rungs: Dict[int, list]) -> int:
        """Next difficulty rung to serve, round-robin by run length.

        Cycles through the present rung keys in ascending order starting
        after the last one served, so mixed traffic alternates fairly
        between e.g. steps-1 precache work and a steps-16 hard request.
        """
        keys = sorted(rungs)
        for k in keys:
            if k > self._last_rung:
                self._last_rung = k
                return k
        self._last_rung = keys[0]
        return keys[0]

    async def _engine_loop(self) -> None:
        try:
            await self._engine_loop_inner()
        except Exception as e:
            # A dead engine must never strand waiters on unresolved futures.
            for job in self._jobs.values():
                if not job.future.done():
                    job.future.set_exception(WorkError(f"engine failed: {e!r}"))
            self._jobs.clear()
            raise

    @classmethod
    def _miss_factor(cls, difficulty: int, span: int) -> float:
        """P(a span of ``span`` nonces holds no solution at ``difficulty``).

        Floored away from 0.0 so the divide-back in _apply_results can
        never divide by an underflowed exp() (easy difficulties make
        span*p large enough to underflow).
        """
        return max(math.exp(-span * cls._solve_p(difficulty)), 1e-12)

    def _dispatch_next(
        self, inflight: int = 0, physical_inflight: Optional[int] = None
    ) -> "Optional[_Launch]":
        """Pack and submit one launch for the next difficulty rung, or None
        when nothing is worth dispatching.

        Difficulty-adaptive run length, decoupled across difficulty
        classes: jobs are grouped into rungs by the run length their
        difficulty wants, and each launch serves ONE rung (round-robin), so
        a hard request's wide launch never stretches every easy request's
        pass — and easy floods can't starve the hard rung either. Batch and
        steps then clamp to warmed shapes.

        Selection within the demand is COVERAGE-AWARE: jobs whose in-flight
        spans are already likely to solve them (inflight_miss below
        SPEC_MISS_THRESHOLD) yield to uncovered jobs — under load a
        pipelined successor launch serves the QUEUE, not a re-scan of the
        batch already on the device. Only when every alive job is covered
        does the engine speculate past the threshold (down to
        SPEC_MISS_FLOOR): for a lone request that speculation hides the
        readback round trip from the unlucky tail, and there is no queued
        demand it could starve.

        Each included job's base advances SPECULATIVELY here, so a
        successor launch dispatched while this one is still in flight scans
        the NEXT span instead of re-scanning this one.
        """
        self._gc_jobs()
        if self._devices_exhausted or (
            self.fan is not None and not self._fan_active
        ):
            return None  # zero healthy devices: nothing can be dispatched
        alive = [j for j in self._jobs.values() if not j.cancelled]
        if not alive:
            return None
        rungs: Dict[int, list] = {}
        for j in alive:
            rungs.setdefault(self._steps_for(j.difficulty), []).append(j)
        for cutoff in (SPEC_MISS_THRESHOLD, SPEC_MISS_FLOOR):
            cands = {
                k: eligible
                for k, js in rungs.items()
                if (eligible := [j for j in js if j.inflight_miss >= cutoff])
            }
            if cands:
                break
        else:
            return None  # everything in flight is near-certain to solve
        # Reaching the floor pass means all demand is covered: anything
        # dispatched now is pure speculation.
        speculative = cutoff == SPEC_MISS_FLOOR
        rung_key = self._next_rung(cands)
        steps_want = rung_key
        # Full width is only ever needed at the HEAD of the device queue:
        # that launch's width is what makes a fresh hard request solve in a
        # single round trip. Everything dispatched behind it — a pipelined
        # successor (``inflight`` > 0), a speculative re-scan, or any launch
        # while another rung has live jobs — executes after queued device
        # time anyway, so its width buys no latency; it only parks more scan
        # in front of fresh arrivals, cancels, and the other rung's next
        # pass. Cap those at shared_steps_cap windows: the pipeline hides
        # the extra per-launch dispatch overhead, so sustained throughput is
        # unchanged, while worst-case wait-behind drops from
        # pipeline*run_steps windows to ~run_steps + shared_steps_cap. The
        # rung's identity (cursor slot, job pool) keeps the UNCAPPED key.
        if (
            (speculative or inflight > 0 or len(rungs) > 1)
            and steps_want > self.shared_steps_cap
            and self.run_mode != "persistent"
        ):
            # Persistent launches are exempt: the width demotion exists to
            # bound how long queued work and cancels wait behind one launch,
            # and the control channel bounds that at one poll interval —
            # every persistent launch may run span-sized.
            steps_want = max(
                s for s in self._step_counts() if s <= self.shared_steps_cap
            )
        # Least-covered first (ties keep insertion order: oldest job wins).
        pool = sorted(cands[rung_key], key=lambda j: -j.inflight_miss)
        if speculative:
            # Bound the expected wasted device time (see SPEC_WASTE_ROWS).
            active, waste = [], 0.0
            for j in pool:
                waste += 1.0 - j.inflight_miss
                if active and waste > SPEC_WASTE_ROWS:
                    break
                active.append(j)
                if len(active) == self.max_batch:
                    break
        else:
            active = pool[: self.max_batch]
        b, steps = self._pick_shape(len(active), steps_want)
        active = active[:b]
        dev_snap, fan_map, launch_devs = None, None, None
        if self.fan is not None:
            # Snapshot the healthy set: the launch runs on exactly these
            # devices, and every apply/attribution path maps its slices
            # through this list — the watchdog may shrink the fan while
            # this launch is still on the wire.
            fan_map = list(self._fan_active)
            params, dev_snap = self._fan_stack(active, b, steps)
            if fan_map != list(range(len(self.fan))):
                launch_devs = tuple(self.fan[d] for d in fan_map)
            span = self.chunk_per_shard * steps * len(fan_map)
        else:
            params = self._pack(active, b)
            span = self.chunk * steps  # global: every sub-span summed
        factors = [self._miss_factor(j.difficulty, span) for j in active]
        # Timing stamps the PHYSICAL queue depth: the overhead
        # decomposition buckets head-vs-successor device time by
        # "nothing in front of it on the device", which a corpse launch
        # still is — only the WIDTH policy treats corpses as absent.
        timing = {
            "t_dispatch": time.perf_counter(),
            "inflight": (
                inflight if physical_inflight is None else physical_inflight
            ),
        }
        self._m_batch_rows.observe(len(active))
        self._m_jobs.set(len(self._jobs), "jax")
        self._m_rungs.set(len(rungs))
        for j in active:
            if not j.t_first_dispatch:
                j.t_first_dispatch = timing["t_dispatch"]
                self._tracer.mark_hash(j.block_hash, "pack")
            if self.fan is not None and j.dev_t0 is None:
                # Per-device scan clocks start at the partition's first
                # dispatch (all devices launch together in one fan pack).
                j.dev_t0 = [self._clock.time()] * len(self.fan)
        slot, launch_control = 0, None
        if self.run_mode == "persistent":
            # One control block per launch, slot-registered so the compiled
            # program can route its polls by traced value; released when the
            # launch's results are applied (a late straggler poll then reads
            # dead zeros — the same fence as a killed row).
            launch_control = ctl.LaunchControl(
                b,
                clock=self._clock,
                n_dev=len(fan_map) if fan_map else 1,
                fan_map=fan_map,
            )
            slot = ctl.register(launch_control)
        thread_done = threading.Event()
        rec = _Launch(
            fut=self._submit_launch(
                params, steps, timing, slot, devices=launch_devs,
                thread_done=thread_done,
            ),
            jobs=active,
            # Snapshot targets and bases at launch: a concurrent dedup may
            # raise job.difficulty, and a pipelined successor dispatch will
            # advance job.base, while this chunk is in flight.
            launched_difficulty=[j.difficulty for j in active],
            bases=[j.base for j in active],
            span=span,
            shape=(b, steps),
            miss_factors=factors,
            timing=timing,
            dev_bases=dev_snap,
            # Both paths snapshot the re-aim epoch: the apply paths use it
            # to fence stale launches out of frontier rewinds (plain) and
            # shard counters/clocks (fan).
            dev_epochs=[j.dev_epoch for j in active],
            control=launch_control,
            slot=slot,
            fan_map=fan_map,
            t_clock=self._clock.time(),
            thread_done=thread_done,
        )
        span_dev = self.chunk_per_shard * steps
        for job, f in zip(active, factors):
            if self.fan is not None:
                self._fan_advance(job, span_dev)
            else:
                job.set_base(job.base + span)
            job.inflight_miss *= f
        return rec

    def _apply_results(self, rec: "_Launch", lo_arr, hi_arr) -> None:
        self._mark_warm(*rec.shape)  # organic warming
        timing = rec.timing
        if timing is not None:
            timing["t_apply"] = time.perf_counter()
            timing["batch"], timing["steps"] = rec.shape
            if "t_thread" in timing and "t_done" in timing:
                self._m_exec_queue.observe(
                    max(0.0, timing["t_thread"] - timing["t_dispatch"]), "jax"
                )
                self._m_device_seconds.observe(
                    max(0.0, timing["t_done"] - timing["t_thread"]), "jax"
                )
            if self.record_timeline:
                self.timeline.append(("launch", timing))
        windows_ran = rec.shape[1]
        if rec.control is not None:
            # The launch is off the device: retire its control slot (a
            # straggler poll now reads dead zeros) and export what the
            # channel saw — launch length, polls, commands delivered and
            # their issue→delivery latency on the injectable clock.
            # dpowlint: disable=DPOW1004 — apply path: the thread already returned its arrays (we hold them), so its finally-release landed first; this is the idempotent belt-and-suspenders release
            ctl.release(rec.slot)
            c = rec.control
            windows_ran = min(c.last_k + self.control_poll_steps, rec.shape[1])
            self._m_p_polls.inc(c.polls)
            self._m_p_windows.observe(windows_ran)
            for _row, action, latency, _token in c.delivered:
                self._m_p_control.inc(1, action)
                self._m_p_effect.observe(latency)
        if timing is not None and "t_done" in timing and "t_thread" in timing:
            # Wall seconds per launch window (EMA): the poll-cadence →
            # seconds conversion behind the watchdog's progress deadlines.
            dev_s = timing["t_done"] - timing["t_thread"]
            if dev_s > 0.0 and windows_ran > 0:
                w = dev_s / windows_ran
                self._window_seconds = (
                    w if self._window_seconds <= 0.0
                    else 0.3 * w + 0.7 * self._window_seconds
                )
        if rec.control is not None and rec.control.first_poll_t is not None:
            # Dispatch → first-poll latency (compile + dispatch) on the
            # engine clock: the never-polled-yet grace window's scale.
            fp = max(0.0, rec.control.first_poll_t - rec.t_clock)
            self._first_poll_seconds = (
                fp if self._first_poll_seconds <= 0.0
                else 0.3 * fp + 0.7 * self._first_poll_seconds
            )
        for job, f in zip(rec.jobs, rec.miss_factors):
            # This launch is no longer in flight: undo its coverage factor
            # (clamped — repeated multiply/divide may drift past 1.0).
            job.inflight_miss = min(1.0, job.inflight_miss / f)
            job.applied_launches += 1
        if rec.dev_bases is not None:
            applied_hashes = self._apply_fan_rows(rec, lo_arr, hi_arr)
        else:
            applied_hashes = self._apply_plain_rows(rec, lo_arr, hi_arr)
        self._m_hashes.inc(applied_hashes, "jax")

    def _record_solve(self, job: _Job, work: str) -> None:
        """Shared per-solve bookkeeping (plain and fan apply paths)."""
        # Persistent successors still scanning the solved job exit within
        # one poll interval instead of grinding their span out.
        self._control_cancel_job(job)
        self.total_solutions += 1
        self._m_solutions.inc(1, "jax")
        self._tracer.mark_hash(job.block_hash, "device")
        if job.t_submit:
            self._m_queue_wait.observe(
                max(0.0, job.t_first_dispatch - job.t_submit), "jax"
            )
        job.future.set_result(work)
        if self.record_timeline and job.t_submit:
            now = time.perf_counter()
            self.timeline.append((
                "solve",
                {
                    "queue_wait": job.t_first_dispatch - job.t_submit,
                    "total": now - job.t_submit,
                    "launches": job.applied_launches,
                },
            ))

    def _apply_plain_rows(self, rec: "_Launch", lo_arr, hi_arr) -> int:
        applied_hashes = 0
        for i, (job, launched, base, epoch, lo, hi) in enumerate(zip(
            rec.jobs, rec.launched_difficulty, rec.bases, rec.dev_epochs,
            lo_arr[: len(rec.jobs)], hi_arr[: len(rec.jobs)],
        )):
            if rec.control is not None:
                # Mid-launch control re-aimed what the dispatch snapshot
                # says: a DELIVERED rebase moved the row's base (and epoch)
                # and a delivered raise moved the judged target — results
                # must be read against what the device actually ran.
                eb = rec.control.effective_base(i)
                if eb is not None:
                    base = eb
                ed = rec.control.effective_difficulty(i)
                if ed is not None:
                    launched = ed
                epoch = rec.control.effective_epoch(i, epoch)
            nonce = (int(hi) << 32) | int(lo)
            if nonce == _MASK64:  # span exhausted, cancelled, or dry
                span_i = rec.span
                if rec.control is not None:
                    # A cancelled row exited early: count the windows the
                    # device actually ran, not the full span.
                    span_i = min(
                        rec.span,
                        rec.control.windows_run(i, rec.shape[1]) * self.chunk,
                    )
                self.total_hashes += span_i
                applied_hashes += span_i
                # base already advanced at dispatch — exactly the miss case
                # the speculation assumed.
                continue
            scanned = ((nonce - base) & _MASK64) + 1
            self.total_hashes += scanned
            applied_hashes += scanned
            if job.future.done():
                continue  # cancelled/solved while the launch was in flight: drop
            work = search.work_hex_from_nonce(nonce)
            value = nc.work_value(job.block_hash, work)
            if value >= job.difficulty:
                self._record_solve(job, work)
            elif value >= launched:
                # Valid for the difficulty this chunk was launched at,
                # but the target was raised mid-flight: keep searching
                # past this nonce at the new difficulty. (An in-flight
                # successor still scans its speculative span at the old
                # target; a weaker hit there just lands back in this
                # branch.) Skipped when the job was re-aimed (cover_range)
                # while this launch was on the wire — the rewind would
                # drag the frontier back out of the re-covered range.
                if epoch == job.dev_epoch:
                    job.set_base(nonce + 1)
            else:  # device/host disagreement: a real bug, surface it
                job.future.set_exception(
                    WorkError(
                        f"device produced invalid work {work} for "
                        f"{job.block_hash} (value {value:016x} < {launched:016x})"
                    )
                )
        return applied_hashes

    def _apply_fan_rows(self, rec: "_Launch", lo_arr, hi_arr) -> int:
        """Apply one fanned launch: winner election + device attribution.

        ``lo_arr``/``hi_arr`` are per-device absolute nonces [n_dev, B].
        Per row, the hit scanned in the fewest nonces from its device's
        launch base wins (the fan's "first" hit under equal scan rates —
        deterministic, matching the mesh gang's pmin election); the win is
        attributed to that device: its scan counter and scan clock produce
        the EMA sample exactly the way the fleet registry attributes a
        sharded win to the worker whose range contains the nonce.
        """
        fan_map = rec.fan_map or list(range(len(self.fan)))
        n = len(fan_map)  # launch slices; fan_map[s] is the physical device
        span_dev = rec.span // n
        applied_hashes = 0
        per_slice_scanned = [0] * n
        for i, (job, launched, bases, epoch) in enumerate(zip(
            rec.jobs, rec.launched_difficulty, rec.dev_bases, rec.dev_epochs
        )):
            # Mid-launch control is applied PER DEVICE: each fan device
            # polls (and exits) independently, so a command counts only on
            # the devices that actually observed it — a device that exited
            # early keeps its dispatch base/target/epoch, or its results
            # would be misread (garbage scanned counts against a base it
            # never adopted, an old-target hit misjudged as a device bug,
            # a stale weak hit rewinding a re-covered frontier).
            launched_dev = [launched] * n
            epoch_dev = [epoch] * n
            dry_scan = [span_dev] * n
            if rec.control is not None:
                bases = list(bases)
                for s in range(n):
                    eb = rec.control.effective_base(i, s)
                    if eb is not None:
                        bases[s] = eb
                    ed = rec.control.effective_difficulty(i, s)
                    if ed is not None:
                        launched_dev[s] = ed
                    epoch_dev[s] = rec.control.effective_epoch(i, epoch, s)
                    dry_scan[s] = min(
                        span_dev,
                        rec.control.windows_run(i, rec.shape[1], s)
                        * self.chunk_per_shard,
                    )
            # Per-slice results for this row: (local offset, slice, nonce).
            cands = []
            row_scanned = list(dry_scan)
            for s in range(n):
                nonce = (int(hi_arr[s, i]) << 32) | int(lo_arr[s, i])
                if nonce == _MASK64:
                    continue  # this device's sub-span was dry
                local = (nonce - bases[s]) & _MASK64
                row_scanned[s] = local + 1
                cands.append((local, s, nonce))
            hit_slices = {s for _l, s, _n in cands}
            for s in range(n):
                d = fan_map[s]
                per_slice_scanned[s] += row_scanned[s]
                applied_hashes += row_scanned[s]
                self.total_hashes += row_scanned[s]
                if job.dev_scanned is not None and epoch_dev[s] == job.dev_epoch:
                    # Same-partition results only: a cover_range rebase
                    # while this launch was on the wire reset the shard
                    # counters, and the old span must not inflate them.
                    # For a device that ADOPTED the rebase mid-launch and
                    # then ran dry, subtract the windows it scanned in the
                    # OLD partition before applying (a hit's row_scanned
                    # is already relative to the rebased base).
                    credit = row_scanned[s]
                    if rec.control is not None and s not in hit_slices:
                        credit = max(
                            0,
                            credit
                            - rec.control.applied_at_k(i, s)
                            * self.chunk_per_shard,
                        )
                    job.dev_scanned[d] += credit
            if job.future.done() or not cands:
                continue
            cands.sort()  # fewest-nonces-scanned first, slice as tiebreak
            for local, s, nonce in cands:
                d = fan_map[s]
                work = search.work_hex_from_nonce(nonce)
                value = nc.work_value(job.block_hash, work)
                if value >= job.difficulty:
                    self._record_solve(job, work)
                    self._attribute_win(job, d, epoch_dev[s])
                    break
                elif value >= launched_dev[s]:
                    # Valid at the target device d was actually holding the
                    # row to, but raised past it meanwhile: ONLY the device
                    # that produced the weak hit resumes past it — its
                    # siblings' shards are untouched. Both policies skip
                    # the rewind when the job was re-partitioned while this
                    # launch was on the wire (epoch mismatch): rewinding
                    # would drag the frontier back into the OLD region and
                    # undo a cover_range re-cover.
                    if epoch_dev[s] == job.dev_epoch:
                        if job.dev_bases is not None:
                            job.dev_bases[d] = (nonce + 1) & _MASK64
                        else:
                            job.set_base(nonce + 1)
                else:  # device/host disagreement: a real bug, surface it
                    job.future.set_exception(
                        WorkError(
                            f"device produced invalid work {work} for "
                            f"{job.block_hash} "
                            f"(value {value:016x} < {launched_dev[s]:016x})"
                        )
                    )
                    break
        self._fan_update_device_metrics(rec, per_slice_scanned)
        return applied_hashes

    def _attribute_win(self, job: _Job, d: int, epoch: int) -> None:
        """Fold one win into device d's EMA on ITS scan clock — the
        engine-level twin of fleet/registry.py observe_result."""
        if (
            job.dev_scanned is None
            or job.dev_t0 is None
            or epoch != job.dev_epoch
        ):
            return
        self._m_dev_wins.inc(1, str(d))
        elapsed = self._clock.time() - job.dev_t0[d]
        hashes = job.dev_scanned[d]
        if elapsed <= 0.0 or hashes <= 0:
            return
        sample = hashes / elapsed
        if self.device_ema[d] <= 0.0:
            self.device_ema[d] = sample
        else:
            a = self.fan_ema_alpha
            self.device_ema[d] = a * sample + (1.0 - a) * self.device_ema[d]
        self._m_dev_ema.set(self.device_ema[d], str(d))
        self.last_win = {
            "device": d,
            "hashes": hashes,
            "elapsed": elapsed,
            "sample_hs": sample,
            "ema_hs": self.device_ema[d],
        }

    def _fan_update_device_metrics(
        self, rec: "_Launch", per_slice_scanned: list
    ) -> None:
        fan_map = rec.fan_map or list(range(len(self.fan)))
        timing = rec.timing or {}
        # Physical device time (perf_counter) feeds the H/s rate — a
        # hardware measure; busy-vs-wall rides the INJECTABLE clock on
        # both sides, so the occupancy gauge is deterministic under
        # FakeClock and honest under SystemClock.
        dev_seconds = max(
            0.0, timing.get("t_done", 0.0) - timing.get("t_thread", 0.0)
        )
        busy_clock = max(
            0.0,
            timing.get("t_done_clock", 0.0) - timing.get("t_thread_clock", 0.0),
        )
        wall = self._clock.time() - self._fan_wall_t0
        for d, scanned in zip(fan_map, per_slice_scanned):
            label = str(d)
            self._m_dev_launches.inc(1, label)
            self._m_dev_hashes.inc(scanned, label)
            if dev_seconds > 0.0:
                self._m_dev_rate.set(scanned / dev_seconds, label)
            self._dev_busy[d] += busy_clock
            if wall > 0.0:
                self._m_dev_busy.set(
                    min(1.0, self._dev_busy[d] / wall), label
                )

    async def _engine_loop_inner(self) -> None:
        # Instance-held so the persistent control writers can reach running
        # launches; cleared on (re)start — a crashed predecessor's records
        # are abandoned with their jobs.
        inflight = self._inflight
        inflight.clear()
        try:
            await self._engine_loop_body(inflight)
        finally:
            # The interruptible wait leaves the oldest launch's waiter task
            # alive across iterations; on any exit (close, crash) cancel
            # them or the event loop logs destroyed-pending-task warnings
            # and the executor futures leak their results.
            for r in inflight:
                if r.waiter is not None:
                    r.waiter.cancel()
                if r.control is not None:
                    # A launch abandoned mid-flight (close, crash, timeout)
                    # never reaches _apply_results. Cancel every row so the
                    # orphan thread exits at its next poll instead of
                    # grinding the span out, then retire the slot once the
                    # thread actually returns (releasing before it polls
                    # would feed it dead zeros and UNDO the cancel; release
                    # is idempotent, so the happy path's release is safe).
                    for i in range(len(r.jobs)):
                        r.control.cancel(i)
                    _retire_on_done(r.fut, r.slot)

    async def _engine_loop_body(self, inflight: deque) -> None:
        while not self._closed:
            if not inflight:
                self._gc_jobs()
                for j in self._jobs.values():
                    # Pipe fully drained ⇒ nothing is in flight by
                    # definition; snap out any float drift from the
                    # multiply/divide coverage accounting.
                    j.inflight_miss = 1.0
                if not self._jobs:
                    if not await self._idle_wait():
                        return
                    continue
            # Clear BEFORE filling: a submit landing after the fill re-sets
            # the event and the wait below returns immediately; clearing
            # after the fill could eat that signal and park the new job
            # behind a full launch round trip.
            self._wakeup.clear()
            # Keep up to ``pipeline`` launches in flight: the device starts
            # on launch N+1 while launch N's results are still in transit.
            while len(inflight) < self.pipeline:
                # Width policy counts only LIVE in-flight launches (still
                # serving an unresolved, uncancelled job). A dying launch —
                # every covered job solved or cancelled while it was on the
                # wire — occupies a pipeline slot but must not demote the
                # next launch to successor width: that launch is the
                # effective head for the fresh demand it serves, and its
                # full width is what makes a sequential arrival solve in
                # one round trip instead of chaining capped passes behind
                # a corpse (measured on-chip r4: 83 ms p50 queue-wait tax).
                live = sum(
                    1
                    for r in inflight
                    if any(
                        not (j.cancelled or j.future.done()) for j in r.jobs
                    )
                )
                with obs.span("dpow.engine.dispatch"):
                    rec = self._dispatch_next(live, len(inflight))
                if rec is None:
                    break
                inflight.append(rec)
            if not inflight:
                await asyncio.sleep(0)  # cancelled stragglers gc'd next pass
                continue
            # Wait on the OLDEST launch's readback — interruptibly: a fresh
            # request arriving mid-await must be DISPATCHED into a free
            # pipeline slot now, not after the wire round trip completes.
            # (Second half of the r4 queue-wait finding: with the width
            # demotion fixed, the remaining sequential-arrival tax was this
            # loop sitting blocked in await while a slot stood free — up to
            # a full host round trip before the fresh head even started.)
            # Results still apply strictly in FIFO order.
            rec = inflight[0]
            if rec.waiter is None:
                rec.waiter = asyncio.ensure_future(
                    self._await_launch(
                        rec.fut, f"batch={rec.shape[0]}, steps={rec.shape[1]}"
                    )
                )
            wake = asyncio.ensure_future(self._wakeup.wait())
            try:
                with obs.span("dpow.engine.wait"):
                    await asyncio.wait(
                        {rec.waiter, wake}, return_when=asyncio.FIRST_COMPLETED
                    )
            finally:
                wake.cancel()
            if rec.abandoned:
                # The watchdog ejected the head launch mid-wait (suspect
                # device): it is already out of the deque, its rows are
                # kill-fenced and its results must never be applied.
                continue
            if not rec.waiter.done():
                continue  # new demand: refill free slots, then keep waiting
            lo_arr, hi_arr = rec.waiter.result()
            inflight.popleft()
            with obs.span("dpow.engine.apply"):
                self._apply_results(rec, lo_arr, hi_arr)

    async def _idle_wait(self) -> bool:
        """Wait for demand with no job held: True once a job is there,
        False after ``IDLE_EXIT_S`` without one (the engine task exits).

        Each slice of the wait is its own ``dpow.engine.idle`` span, at
        most ``IDLE_SPAN_S`` long: a trace reader labels a device gap only
        with host spans that began shortly before it, so a span open for
        the whole idle stretch would leave its later gaps unlabelled."""
        self._wakeup.clear()
        for _ in range(IDLE_EXIT_S // IDLE_SPAN_S):
            with obs.span("dpow.engine.idle"):
                try:
                    await asyncio.wait_for(self._wakeup.wait(), timeout=IDLE_SPAN_S)
                    return True
                except asyncio.TimeoutError:
                    # A job may have landed exactly at the deadline (set()
                    # and the timeout can race).
                    if self._jobs:
                        return True
        return False

    def _gc_jobs(self) -> None:
        for key in [k for k, j in self._jobs.items() if j.future.done()]:
            del self._jobs[key]
        # A drained engine must read 0, not the last batch's values — the
        # pack path only runs while there is demand to pack.
        self._m_jobs.set(len(self._jobs), "jax")
        if not self._jobs:
            self._m_rungs.set(0)
