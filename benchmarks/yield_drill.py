"""Chip-yield drill: prove the driver bench lands on TPU THROUGH a capture.

VERDICT r4 item 2: four rounds of BENCH_r0N.json CPU fallbacks, and the
chip-yield protocol (bench.py announces; the capture's probe + mid-step
gates defer) has never been exercised against a real driver-shaped run on a
live chip. This drill is that exercise, end to end, with the REAL
machinery on both sides:

  1. Spawn an inner ``capture_evidence.py`` (temp artifact file) whose one
     step is a long latency bench — a genuine capture holding the chip via
     the genuine run_step() foreign-bench watch.
  2. Once the holder is mid-step on the chip, fire the DRIVER'S EXACT
     command — ``bash -c 'if [ -f bench.py ]; then python bench.py; fi'`` —
     under its shortest timeout (120 s), from a cold process against the
     persistent compile cache.
  3. Verify: the inner capture yields (rc 3, "yield" in its output), the
     driver invocation exits rc 0 within the bound with platform "tpu" and
     value >= 1e9, and the announce flag is cleaned up afterward.

The verdict is recorded under "yield_drill" in BENCH_latency.json (with
--mark) so the committed artifact carries the drill evidence, and the
summarizer grades it. Exit codes: 0 drill ran and recorded (ok true or
false — the record says which); 3 the chip went away underneath the drill
(watcher: resume watching and re-run on the next window).

Run by watch_and_capture.sh after a completed capture (the chip is idle and
the cache is warm — the same state a driver-slot run would find).
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

import capture_evidence as ce

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER_CMD = "if [ -f bench.py ]; then python bench.py; fi"
DRIVER_TIMEOUT = 120  # the driver's SHORTEST attempt budget
# Test knobs (tests/test_yield_drill.py runs the real holder + yield path on
# CPU with a stubbed driver; production values otherwise).
HOLDER_N = os.environ.get("TPU_DPOW_DRILL_HOLDER_N", "500")
SETTLE_S = float(os.environ.get("TPU_DPOW_DRILL_SETTLE_S", "30"))


def _chip_alive() -> bool:
    """The drill's dead-chip veto, honoring the watcher's smoke knob.

    TPU_DPOW_WATCH_ASSUME_LIVE=1 (test-only) must bypass this veto too —
    otherwise a CPU smoke run's drill always exits rc 3 (genuinely dead
    chip) and the watcher's phased flow can never reach its terminal
    sequence in a bounded smoke.
    """
    if os.environ.get("TPU_DPOW_WATCH_ASSUME_LIVE") == "1":
        return True
    return ce.chip_alive()


def fresh_verdict(out_path: str, mark: str | None):
    """The recorded drill verdict under this mark: True, False, or None.

    None = no recorded run (crash or never ran). A recorded False is a
    terminal verdict for --skip_recorded callers (the window-head phase
    must not burn ~4 min re-litigating it every window) but the
    post-capture caller retries it — a false caused by a cold cache or a
    dying window can flip true on a healthier chip state.
    """
    try:
        with open(out_path) as f:
            rec = json.load(f).get("yield_drill") or {}
    except (OSError, json.JSONDecodeError):
        return None
    if rec.get("mark") != mark or rec.get("rc") != 0:
        return None
    return (rec.get("result") or {}).get("ok")


def fresh_ok(out_path: str, mark: str | None) -> bool:
    return fresh_verdict(out_path, mark) is True


def start_holder(tmpdir: str) -> subprocess.Popen:
    """A REAL capture (capture_evidence.py) holding the chip with one step.

    500 base-difficulty solves is minutes of chip time — the drill kills
    whatever remains after the driver phase; the point is that the holder
    is still mid-step when the driver lands.
    """
    steps = [["hold", [sys.executable, "benchmarks/latency.py",
                       "--n", HOLDER_N], 600]]
    steps_file = os.path.join(tmpdir, "steps.json")
    with open(steps_file, "w") as f:
        json.dump(steps, f)
    env = dict(os.environ)
    env["TPU_DPOW_BENCH_OUT"] = os.path.join(tmpdir, "inner_bench.json")
    env.pop("TPU_DPOW_EVIDENCE_CAPTURE", None)
    return subprocess.Popen(
        [sys.executable, "benchmarks/capture_evidence.py",
         "--steps_file", steps_file, "--steps", "hold"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True,
    )


def run_driver_sim() -> dict:
    """The driver's exact invocation, bounded at its shortest budget."""
    env = dict(os.environ)
    # A real driver run is NOT part of any capture: the env marker would
    # suppress bench.py's announcement and the drill would test nothing.
    env.pop("TPU_DPOW_EVIDENCE_CAPTURE", None)
    t0 = time.perf_counter()
    try:
        # --kill-after: a bench wedged in an uninterruptible chip call has
        # been observed shrugging off the plain TERM (the watcher's probe
        # comment); the outer subprocess timeout (which SIGKILLs) backstops
        # a wedged timeout(1) itself so the drill always regains control
        # and can record its negative verdict.
        proc = subprocess.run(
            ["timeout", "--kill-after=30", str(DRIVER_TIMEOUT),
             "bash", "-c", DRIVER_CMD],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=DRIVER_TIMEOUT + 90,
        )
    except subprocess.TimeoutExpired as e:
        return {"rc": "timeout", "seconds": round(time.perf_counter() - t0, 1),
                "result": {}, "note": str(e)[:120]}
    seconds = round(time.perf_counter() - t0, 1)
    result = {}
    for line in reversed((proc.stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and "value" in parsed:
            result = parsed
            break
    return {"rc": proc.returncode, "seconds": seconds, "result": result}


def main() -> int:
    p = argparse.ArgumentParser("chip-yield protocol drill")
    p.add_argument("--mark", default=None)
    p.add_argument("--out", default=None,
                   help="record destination (default: the repo artifact)")
    p.add_argument("--skip_recorded", action="store_true",
                   help="skip if ANY verdict (ok true or false) is recorded "
                   "under this mark — the watcher's window-head phase; the "
                   "default retries a recorded false")
    args = p.parse_args()
    # Same artifact resolution as capture_evidence.py: the env override
    # exists for tests/smokes that must not touch the repo artifact, and a
    # drill run inside such a session must read its skip-verdict from and
    # write its record to the same file the capture used.
    out_path = (args.out or os.environ.get("TPU_DPOW_BENCH_OUT")
                or os.path.join(REPO, "BENCH_latency.json"))
    verdict = fresh_verdict(out_path, args.mark)
    if verdict is True or (args.skip_recorded and verdict is not None):
        print(f"yield_drill verdict {verdict} already recorded under mark "
              f"{args.mark!r}; skipping")
        return 0
    # Refuse to run while a capture is mid-flight on the same artifact
    # (ADVICE r5): captures hold the artifact lock for their whole run, so
    # a probe-acquire tells us one is live. rc 3 = "try again later", the
    # same signal the watcher already handles for a lost chip.
    try:
        with ce.artifact_lock(out_path, blocking=False):
            pass
    except ce.ArtifactBusy as e:
        print(f"a capture is mid-flight on {out_path} ({e}); "
              "refusing to race its artifact writes (rc 3)")
        return 3

    tmpdir = tempfile.mkdtemp(prefix="yield_drill_")
    try:
        return _drill(args, out_path, tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _drill(args, out_path: str, tmpdir: str) -> int:
    holder = start_holder(tmpdir)
    holder_out: list[str] = []
    # Non-blocking reads on a text pipe are unsupported (TextIOWrapper
    # raises on a None raw read); drain via a thread instead.
    reader = threading.Thread(
        target=lambda: holder_out.extend(iter(holder.stdout.readline, "")),
        daemon=True)
    reader.start()
    # Wait for the holder's step launch line, then give its jax child time
    # to actually seize the chip (imports + cache-warm compile).
    step_seen = False
    deadline = time.time() + 120
    while time.time() < deadline and not step_seen:
        step_seen = any("== hold:" in line for line in holder_out)
        if step_seen or holder.poll() is not None:
            break
        time.sleep(1)
    if not step_seen:
        print("holder never reached its step; aborting drill")
        print("".join(holder_out)[-2000:])
        _kill(holder)
        return 3 if not _chip_alive() else 1
    time.sleep(SETTLE_S)

    t_drill = time.time()
    driver = run_driver_sim()

    # The holder should notice the announcement within ~5 s and exit rc 3.
    try:
        holder.wait(timeout=120)
    except subprocess.TimeoutExpired:
        pass
    _kill(holder)
    reader.join(timeout=10)
    holder_text = "".join(holder_out)
    holder_yielded = ("yield" in holder_text
                      and holder.returncode == 3)
    flag_clean = ce.foreign_bench_pid() is None

    r = driver["result"]
    on_tpu = r.get("platform") == "tpu"
    ok = bool(driver["rc"] == 0 and on_tpu
              and r.get("value", 0) >= 1e9
              and driver["seconds"] <= DRIVER_TIMEOUT
              and holder_yielded and flag_clean)
    record = {
        "rc": 0,
        "seconds": round(time.time() - t_drill, 1),
        "result": {
            "bench": "yield_drill",
            "ok": ok,
            "driver_rc": driver["rc"],
            "driver_seconds": driver["seconds"],
            "driver_timeout_s": DRIVER_TIMEOUT,
            "driver_platform": r.get("platform"),
            "driver_value": r.get("value"),
            "driver_attempts": r.get("attempts"),
            "holder_rc": holder.returncode,
            "holder_yielded": holder_yielded,
            "announce_flag_cleaned": flag_clean,
        },
    }
    if args.mark:
        record["mark"] = args.mark
    print(json.dumps(record["result"]))
    if not ok and not _chip_alive():
        # Dead chip explains any of the failures above; don't record a
        # false negative — let the watcher re-run on the next window.
        print("drill failed with a lost chip; not recording (rc 3)")
        return 3
    # Same lock capture_evidence holds for its runs: the read-modify-write
    # below must not interleave with a capture's progressive saves.
    try:
        with ce.artifact_lock(out_path, blocking=False):
            data = _load(out_path)
            data["yield_drill"] = record
            _save(out_path, data)
    except ce.ArtifactBusy as e:
        print(f"a capture started mid-drill on {out_path} ({e}); "
              "not recording (rc 3)")
        return 3
    return 0


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _save(path: str, data: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2)
    os.replace(tmp, path)


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            try:
                proc.kill()
            except OSError:
                pass
        proc.wait()


if __name__ == "__main__":
    sys.exit(main())
