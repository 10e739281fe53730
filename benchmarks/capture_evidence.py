"""One-shot TPU evidence capture, priority-ordered.

Evidence lands in priority order — headline first, diagnostics last. This
runs every measurement, each in a bounded child process, and appends
results to the artifact (TPU_DPOW_BENCH_OUT, default BENCH_latency.json)
after EACH step, so a chip lost halfway still leaves the top-priority
numbers on disk. ROADMAP C1 replaces this apparatus with the cell runner.

Order:
  1. bench.py            — the headline H/s artifact (the driver's metric)
  2. tests_tpu           — on-chip correctness suite
  3. latency (base, 8x)  — p50/p95 through the full backend
  4. flood               — e2e req/s through the HTTP->broker->engine stack
  5. fairness            — mixed-load scheduling tax
  6. overhead            — engine overhead decomposition

Usage: python benchmarks/capture_evidence.py \
           [--steps headline,flood,...]   (default: all, in priority order)
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import contextlib
import fcntl
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Env override exists for the test suite (and ad-hoc captures that must not
# touch the repo artifact).
OUT = os.environ.get("TPU_DPOW_BENCH_OUT") or os.path.join(REPO, "BENCH_latency.json")


class ArtifactBusy(Exception):
    """Another writer holds the artifact lock (a capture is mid-flight)."""


@contextlib.contextmanager
def artifact_lock(path: str, blocking: bool = True):
    """Advisory flock serializing writers of one evidence artifact.

    The capture holds it for its whole run; yield_drill.py takes the SAME
    lock around its read-modify-write of the shared file (and refuses to
    start while a capture is mid-flight), so a manually launched drill can
    no longer race a capture and silently lose one writer's update
    (ADVICE r5). The lock file lives next to the artifact (``<path>.lock``)
    so distinct artifacts — e.g. the drill's temp inner capture — never
    contend. Python opens the fd close-on-exec, so step children do not
    inherit (and thus cannot prolong) the capture's hold.
    """
    fh = open(path + ".lock", "w")
    try:
        try:
            fcntl.flock(fh, fcntl.LOCK_EX | (0 if blocking else fcntl.LOCK_NB))
        except OSError as e:
            raise ArtifactBusy(f"{path}.lock: {e}") from e
        yield
    finally:
        try:
            fcntl.flock(fh, fcntl.LOCK_UN)
        finally:
            fh.close()

STEPS = [
    ("headline", [sys.executable, "bench.py"], 900),
    ("tests_tpu", [sys.executable, "-m", "pytest", "tests_tpu", "-q",
                   "--no-header", "-p", "no:cacheprovider"], 1200),
    # CPU-only (env-wrapped like gang_e2e): derives ops/hash + VPU ceiling
    # and reads the headline just captured above, so the artifact carries
    # the MFU of the FRESH number, not a doc citation.
    ("roofline", ["env", "PYTHONPATH=", "JAX_PLATFORMS=cpu",
                  sys.executable, "benchmarks/roofline.py"], 300),
    ("latency_base", [sys.executable, "benchmarks/latency.py", "--n", "20"], 600),
    ("latency_8x", [sys.executable, "benchmarks/latency.py", "--n", "10",
                    "--multiplier", "8"], 900),
    ("latency_base_x2ladder", [sys.executable, "benchmarks/latency.py",
                               "--n", "20", "--step_ladder", "x2"], 900),
    ("flood", [sys.executable, "benchmarks/flood.py", "--n", "100",
               "--concurrency", "20"], 900),
    ("fairness", [sys.executable, "benchmarks/fairness.py", "--n", "10"], 900),
    ("precache", [sys.executable, "benchmarks/precache.py", "--n", "30"], 600),
    ("cancel", [sys.executable, "benchmarks/cancel_latency.py", "--n", "10"], 600),
    ("gang_ab", [sys.executable, "benchmarks/gang_ab.py", "--reps", "20"], 600),
    # Virtual-mesh step (never touches the TPU): graded e2e drive of the
    # ganged engine at the flagship gang size.
    ("gang_e2e", ["env", "JAX_PLATFORMS=cpu",
                  sys.executable, "benchmarks/gang_e2e.py"], 900),
    ("latency_mesh1", [sys.executable, "benchmarks/latency.py", "--n", "15",
                       "--mesh_devices", "1"], 900),
    ("overhead", [sys.executable, "benchmarks/overhead.py"], 900),
    ("batch", [sys.executable, "benchmarks/batch.py"], 600),
    ("soak", [sys.executable, "benchmarks/soak.py", "--waves", "10",
              "--width", "16"], 600),
    ("chaos_crossproc", [sys.executable, "benchmarks/chaos_crossproc.py",
                         "--n", "80", "--concurrency", "10"], 600),
    # Lowest priority: geometry re-sweep hunting a new champion shape —
    # only the LAST JSON line (the sweep prints one per shape) is recorded,
    # so the full stdout lands in the watch log, not BENCH_latency.json.
    ("throughput_sweep", [sys.executable, "benchmarks/throughput.py",
                          "--reps", "6"], 1200),
]


# Steps that pin themselves to CPU and never touch the chip: a failure here
# is a real failure, not chip weather — the dead-chip abort must not
# swallow it (it skips the attempts increment, so a genuine regression
# would re-run and re-abort every window, starving the steps below it).
CPU_ONLY_STEPS = {"gang_e2e", "roofline"}
# A resumed capture re-runs a previously failed step at most this many times
# before skipping past it (see the retry-cap comment in main()).
MAX_STEP_ATTEMPTS = 2


def foreign_bench_pid():
    """Pid of a live DRIVER-invoked chip user (bench.py or the
    __graft_entry__ compile check), or None.

    The chip is single-client and the watcher outlives the builder session,
    so the driver's official round-end runs can collide with a detached
    capture and fail with UNAVAILABLE — the exact artifact failure rounds
    1–3 recorded. Driver-invoked chip users announce themselves via a
    "pid start-time" flag (tpu_dpow.utils.announce_foreign_chip_user);
    a stale flag is removed.

    Staleness is identity-based: the driver's hard timeout SIGKILLs its
    children (no atexit), and a bare liveness check on a recycled pid
    pointing at some long-lived daemon would park the watcher for hours —
    the kernel start-time recorded in the flag identifies the announcing
    process exactly. A pid-only flag (non-Linux writer) degrades to a
    liveness check.
    """
    from tpu_dpow.utils import foreign_bench_flag_path, process_start_time

    path = foreign_bench_flag_path()
    try:
        with open(path) as f:
            parts = f.read().split()
        pid = int(parts[0])
        flag_start = parts[1] if len(parts) > 1 else None
    except (OSError, ValueError, IndexError):
        return None
    if flag_start is not None:
        alive = process_start_time(pid) == flag_start
    else:
        try:
            os.kill(pid, 0)
            alive = True
        except OSError:
            alive = False
    if not alive:
        _unlink_flag_if_still(path, pid)
        return None
    return pid


def _unlink_flag_if_still(path: str, pid: int) -> None:
    """Remove the flag only if it still names the pid we judged stale —
    a fresh driver bench may have atomically replaced it between our read
    and this unlink, and deleting ITS live flag would strip the driver of
    the very protection this mechanism exists to provide."""
    try:
        with open(path) as f:
            if int(f.read().split()[0]) == pid:
                os.unlink(path)
    except (OSError, ValueError, IndexError):
        pass


def _kill_step_group(proc) -> None:
    import signal as _signal

    try:
        os.killpg(proc.pid, _signal.SIGKILL)
    except OSError:
        try:
            proc.kill()
        except OSError:
            pass


def run_step(cmd, timeout: float, env: dict):
    """Run one step, watching for a driver bench announcement mid-step.

    The longest steps (1200 s) outlast the driver bench's entire retry
    budget (~675 s), so a between-step gate alone would still let a
    mid-step driver run fail every attempt with UNAVAILABLE. The step runs
    in its own process group (its own children hold the chip) and is
    killed the moment a foreign bench appears.

    Returns (rc, stdout, stderr) where rc is the child's returncode,
    "timeout", or "yielded".
    """
    proc = subprocess.Popen(
        cmd, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env, start_new_session=True,
    )
    deadline = time.time() + timeout
    while True:
        try:
            out, err = proc.communicate(timeout=min(5.0, max(0.1, deadline - time.time())))
            return proc.returncode, out, err
        except subprocess.TimeoutExpired:
            pass
        if foreign_bench_pid() is not None:
            _kill_step_group(proc)
            out, err = proc.communicate()
            return "yielded", out, err
        if time.time() >= deadline:
            _kill_step_group(proc)
            out, err = proc.communicate()
            return "timeout", out, err


def wait_for_foreign_bench() -> None:
    """Block (bounded) while a driver bench holds the chip.

    The driver's worst case is ~12 min of attempts; the 30 min cap keeps a
    wedged-but-alive foreign process from parking the capture forever.
    A flag still live when the cap expires is treated as wedged and
    force-cleared — otherwise the mid-step foreign check would kill the
    very next step ~5 s in and the abort/resume cycle would loop forever,
    defeating the cap. (A wedged bench is not measuring anything anyway.)
    """
    max_wait = float(os.environ.get("TPU_DPOW_FOREIGN_MAX_WAIT", 1800))
    poll = min(10.0, max(0.1, max_wait / 4))
    waited = 0.0
    while waited < max_wait:
        pid = foreign_bench_pid()
        if pid is None:
            return
        print(f"yielding chip to driver bench.py (pid {pid}); waiting",
              flush=True)
        time.sleep(poll)
        waited += poll
    pid = foreign_bench_pid()
    if pid is not None:
        from tpu_dpow.utils import foreign_bench_flag_path

        print(f"foreign bench.py (pid {pid}) exceeded the {max_wait:.0f}s "
              "wait cap; treating it as wedged and clearing its flag",
              flush=True)
        _unlink_flag_if_still(foreign_bench_flag_path(), pid)


def chip_alive(timeout: float | None = None) -> bool:
    """Bounded probe: is the TPU chip serving jits right now?

    Used to distinguish "this step failed" from "the chip went away under the
    whole capture" — observed live windows can be ~2 min, so once the
    chip is gone every remaining step would just burn its full timeout
    (hours of dead time that a resumed capture could use instead).

    Bounded by the PROBE_TIMEOUT env (default 75 s).
    """
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS") == "cpu":
        # Pinned to CPU (the test env): a TPU probe cannot succeed.
        return False
    pid = foreign_bench_pid()
    if pid is not None:
        # A driver bench holds the single-client chip: probing now would
        # contend with the round's official artifact. Report "not alive" so
        # the watcher sleeps and retries after the driver is done.
        print(f"yielding chip to driver bench.py (pid {pid}); probe deferred",
              flush=True)
        return False
    if timeout is None:
        timeout = float(env.get("PROBE_TIMEOUT", 75))
    probe = (
        "import jax\n"
        "jax.jit(lambda a: a + 1)(jax.numpy.ones((8,))).block_until_ready()\n"
        "raise SystemExit(0 if jax.devices()[0].platform != 'cpu' else 1)\n"
    )
    try:
        # Two layers, mirroring the watcher: the `timeout` binary bounds the
        # probe (KILL backstop — a half-up chip has been observed eating
        # a plain TERM), and subprocess.run's own timeout (which SIGKILLs)
        # covers a wedged `timeout` itself so a mid-capture liveness check
        # can never park the capture through a live window.
        proc = subprocess.run(
            ["timeout", "--kill-after=30", str(int(timeout)),
             sys.executable, "-c", probe], cwd=REPO,
            capture_output=True, timeout=timeout + 60, env=env,
        )
        return proc.returncode == 0
    except subprocess.TimeoutExpired:
        return False


def load() -> dict:
    try:
        with open(OUT) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def save(data: dict) -> None:
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2)
    os.replace(tmp, OUT)


def main() -> int:
    p = argparse.ArgumentParser("priority-ordered on-chip evidence capture")
    p.add_argument("--steps", default=None,
                   help="comma-separated subset of step names (priority order kept)")
    p.add_argument("--mark", default=None,
                   help="record this value under each step's 'mark' key "
                   "(lets a re-capture watcher distinguish fresh results "
                   "from a previous code revision's)")
    p.add_argument("--skip_fresh", action="store_true",
                   help="skip steps already recorded with rc==0 and this "
                   "--mark (resume a capture a lost chip cut short)")
    p.add_argument("--no_dead_chip_abort", action="store_true",
                   help="keep running remaining steps even after a failed "
                   "step coincides with a lost chip probe (default: "
                   "abort with rc 3 so the watcher can resume later)")
    p.add_argument("--steps_file", default=None,
                   help="JSON file of [name, argv, timeout_s] triples "
                   "replacing the built-in step list (tests / ad-hoc runs)")
    p.add_argument("--probe", action="store_true",
                   help="just probe the chip and exit 0 (live) / 1 (dead) "
                   "— the watcher shares this probe so the two can't "
                   "disagree about what alive means")
    p.add_argument("--validate", action="store_true",
                   help="check the step selection and exit without running "
                   "anything (the watcher validates BEFORE its probe loop: "
                   "a typo'd step name must fail at launch, not burn the "
                   "first live window)")
    args = p.parse_args()
    if args.probe:
        return 0 if chip_alive() else 1
    steps = STEPS
    if args.steps_file:
        with open(args.steps_file) as f:
            steps = [(n, cmd, t) for n, cmd, t in json.load(f)]
    if args.steps:
        want = {s.strip() for s in args.steps.split(",")}
        unknown = want - {n for n, _, _ in steps}
        if unknown:
            print(f"unknown steps: {sorted(unknown)}", file=sys.stderr)
            return 2
        steps = [s for s in steps if s[0] in want]
    if args.validate:
        print(f"steps ok: {[n for n, _, _ in steps]}")
        return 0
    if args.skip_fresh and args.mark is None:
        # Without a mark, "fresh" would match records from ANY prior code
        # revision and silently publish stale numbers as a clean finish.
        print("--skip_fresh requires --mark", file=sys.stderr)
        return 2
    # One writer per artifact: hold the lock for the whole capture so a
    # concurrently launched drill or second capture cannot interleave its
    # read-modify-write with this run's progressive saves.
    try:
        with artifact_lock(OUT, blocking=False):
            return _run_capture(args, steps)
    except ArtifactBusy as e:
        print(f"artifact busy ({e}): another capture/drill is mid-flight "
              "on the same file; refusing a concurrent run", file=sys.stderr)
        return 2


def _run_capture(args, steps) -> int:
    results = load()
    if args.skip_fresh and "capture_started_unix" in results:
        # Preserve the original start time across resumed windows; log the
        # resume so artifact provenance stays auditable.
        results.setdefault("capture_resumed_unix", []).append(round(time.time(), 1))
    else:
        results["capture_started_unix"] = round(time.time(), 1)
    if args.skip_fresh:
        # A step that keeps failing on a LIVE chip must not livelock the
        # resume loop (each window re-running it, starving everything
        # below). Deferring it to the END — rather than skipping it —
        # bounds the starvation without ever permanently dropping a step
        # (a dead-chip kill misattributed as a live failure by a flapping
        # chip would otherwise consume the cap and lose the step forever).
        def _capped(name):
            prior = results.get(name)
            return (isinstance(prior, dict) and prior.get("mark") == args.mark
                    and prior.get("rc") != 0
                    and int(prior.get("attempts", 1)) >= MAX_STEP_ATTEMPTS)

        deferred = [s for s in steps if _capped(s[0])]
        if deferred:
            steps = [s for s in steps if not _capped(s[0])] + deferred
            print(f"deferring to end (failed >={MAX_STEP_ATTEMPTS}x live): "
                  f"{[n for n, _, _ in deferred]}", flush=True)
    for name, cmd, timeout in steps:
        prior = results.get(name)
        prior_marked = (isinstance(prior, dict)
                        and (args.mark is None or prior.get("mark") == args.mark))
        if args.skip_fresh and prior_marked and prior.get("rc") == 0:
            print(f"== {name}: fresh (rc 0, mark {args.mark!r}), skipping",
                  flush=True)
            continue
        wait_for_foreign_bench()
        print(f"== {name}: {' '.join(cmd)}", flush=True)
        t0 = time.time()
        # The env marker tells bench.py children they are part of this
        # capture (no foreign-bench announcement) — a capture must not
        # yield to itself.
        child_env = dict(os.environ)
        child_env["TPU_DPOW_EVIDENCE_CAPTURE"] = "1"
        rc, out, err = run_step(cmd, timeout, child_env)
        record = {"rc": rc, "seconds": round(time.time() - t0, 1)}
        if rc not in ("timeout", "yielded"):
            tail = (out or "").strip().splitlines()
            # keep the last JSON line if any step prints one
            for line in reversed(tail):
                try:
                    record["result"] = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
            if "result" not in record and tail:
                record["tail"] = tail[-3:]
            if rc != 0:
                record["stderr_tail"] = (err or "").strip().splitlines()[-3:]
        if args.mark:
            # Namespaced under a fixed key: a free-form value must not be
            # able to collide with (and overwrite) the reserved record keys
            # rc/seconds/result/tail/stderr_tail.
            record["mark"] = args.mark
        failed = record["rc"] != 0
        yielded = record["rc"] == "yielded"
        chip_died = (failed and not yielded and name not in CPU_ONLY_STEPS
                       and not args.no_dead_chip_abort
                       and not chip_alive())
        if prior_marked:
            if chip_died or yielded:
                # A failure the probe attributes to the chip going away — or a
                # step killed to yield the chip to the driver — must not
                # consume the retry budget: with ~2-min live windows and
                # 900 s step timeouts, two such kills would otherwise
                # permanently skip the step via the retry cap.
                if "attempts" in prior:
                    record["attempts"] = prior["attempts"]
            else:
                record["attempts"] = int(prior.get("attempts", 1)) + 1
        results[name] = record
        save(results)  # progressive: a lost chip still leaves earlier steps
        print(f"   -> {json.dumps(record)[:240]}", flush=True)
        if yielded:
            results["capture_yielded_to_driver_unix"] = round(time.time(), 1)
            save(results)
            print(f"!! step {name} killed to yield the chip to a driver "
                  "bench.py; aborting so the watcher resumes after it",
                  flush=True)
            return 3
        if chip_died:
            results["capture_aborted_dead_chip_unix"] = round(time.time(), 1)
            save(results)
            print(f"!! chip lost after failed step {name}; aborting so "
                  "the watcher can resume (--skip_fresh) on the next "
                  "window", flush=True)
            return 3
    results.pop("capture_aborted_dead_chip_unix", None)
    results.pop("capture_yielded_to_driver_unix", None)
    results["capture_finished_unix"] = round(time.time(), 1)
    save(results)
    return 0


if __name__ == "__main__":
    sys.exit(main())
