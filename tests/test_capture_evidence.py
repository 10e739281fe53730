"""capture_evidence.py contract — the tool that turns a live chip window
into BENCH_latency.json. Observed live windows can be ~2 min (r4: live
01:00:58Z, probe dead 30 s later), so the capture must (a) resume across
windows instead of re-running landed steps, and (b) abort the moment a
failed step coincides with a lost chip rather than burning every
remaining step's full timeout. Both behaviors are pinned here with stub
steps in a subprocess, against a temp artifact (TPU_DPOW_BENCH_OUT)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "benchmarks", "capture_evidence.py")


def run_capture(tmp_path, steps, argv_extra, out_name="bench.json", prior=None,
                env_extra=None):
    out = tmp_path / out_name
    if prior is not None:
        out.write_text(json.dumps(prior))
    steps_file = tmp_path / "steps.json"
    steps_file.write_text(json.dumps(steps))
    env = dict(os.environ)
    env["TPU_DPOW_BENCH_OUT"] = str(out)
    env.update(env_extra or {})
    # The dead-chip probe must see a CPU-only jax quickly, not block on a
    # half-up accelerator plugin: strip any plugin dirs from PYTHONPATH and
    # force the CPU platform (same rationale as tests/conftest.py).
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--steps_file", str(steps_file)] + argv_extra,
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    data = json.loads(out.read_text()) if out.exists() else {}
    return proc, data


def ok_step(name):
    return [name, [sys.executable, "-c",
                   f"import json; print(json.dumps({{'step': '{name}'}}))"], 30]


def fail_step(name):
    return [name, [sys.executable, "-c", "raise SystemExit(1)"], 30]


def test_steps_record_result_and_mark(tmp_path):
    proc, data = run_capture(
        tmp_path, [ok_step("a"), ok_step("b")], ["--mark", "t1"])
    assert proc.returncode == 0, proc.stderr
    assert data["a"]["rc"] == 0 and data["a"]["result"] == {"step": "a"}
    assert data["b"]["mark"] == "t1"
    assert "capture_finished_unix" in data


def test_skip_fresh_skips_only_matching_mark_and_rc0(tmp_path):
    prior = {
        "a": {"rc": 0, "mark": "t1", "result": {"step": "stale-code"}},
        "b": {"rc": 1, "mark": "t1"},          # failed: must re-run
        "c": {"rc": 0, "mark": "OLDMARK"},     # old revision: must re-run
    }
    proc, data = run_capture(
        tmp_path, [ok_step("a"), ok_step("b"), ok_step("c")],
        ["--mark", "t1", "--skip_fresh"], prior=prior)
    assert proc.returncode == 0, proc.stderr
    assert data["a"]["result"] == {"step": "stale-code"}  # untouched
    assert data["b"]["rc"] == 0 and data["b"]["result"] == {"step": "b"}
    assert data["c"]["mark"] == "t1"
    assert "skipping" in proc.stdout


def test_failed_step_with_dead_chip_aborts_rc3(tmp_path):
    # JAX_PLATFORMS=cpu makes the liveness probe report "dead" (platform is
    # cpu), so the first failing step must abort the rest of the capture.
    proc, data = run_capture(
        tmp_path, [fail_step("a"), ok_step("never")], ["--mark", "t1"])
    assert proc.returncode == 3, (proc.stdout, proc.stderr)
    assert data["a"]["rc"] == 1
    assert "never" not in data
    assert "capture_aborted_dead_chip_unix" in data
    assert "capture_finished_unix" not in data


def test_cpu_only_step_failure_never_blamed_on_chip(tmp_path):
    # gang_e2e pins itself to CPU and cannot depend on the chip: its
    # failure is a real regression. The dead-chip abort must NOT swallow
    # it (that path skips the attempts increment, so the capture would
    # re-run and re-abort every window, starving the steps below it).
    steps = [fail_step("gang_e2e"), ok_step("after")]
    prior = {"gang_e2e": {"rc": 1, "mark": "t1", "attempts": 1}}
    proc, data = run_capture(
        tmp_path, steps, ["--mark", "t1", "--skip_fresh"], prior=prior)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert data["gang_e2e"]["rc"] == 1
    assert data["gang_e2e"]["attempts"] == 2   # a live failure, counted
    assert data["after"]["rc"] == 0            # capture continued past it
    assert "capture_finished_unix" in data
    assert "capture_aborted_dead_chip_unix" not in data


def test_retry_capped_step_deferred_to_end(tmp_path):
    # A step that keeps failing on a live chip must not livelock the
    # resume loop — but it must not be dropped forever either (a flapping
    # chip can misattribute outage kills as live failures). It runs LAST.
    prior = {"a": {"rc": 1, "mark": "t1", "attempts": 2}}
    proc, data = run_capture(
        tmp_path, [fail_step("a"), ok_step("b")],
        ["--mark", "t1", "--skip_fresh", "--no_dead_chip_abort"],
        prior=prior)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "deferring to end" in proc.stdout
    assert proc.stdout.index("== b:") < proc.stdout.index("== a:")
    assert data["b"]["rc"] == 0
    assert data["a"]["attempts"] == 3          # re-run (at the end), counted
    assert "capture_finished_unix" in data


def test_skip_fresh_requires_mark(tmp_path):
    proc, data = run_capture(tmp_path, [ok_step("a")], ["--skip_fresh"])
    assert proc.returncode == 2
    assert "requires --mark" in proc.stderr
    assert data == {}


def test_resume_preserves_original_start_time(tmp_path):
    prior = {"capture_started_unix": 111.5,
             "a": {"rc": 0, "mark": "t1"}}
    proc, data = run_capture(
        tmp_path, [ok_step("a"), ok_step("b")],
        ["--mark", "t1", "--skip_fresh"], prior=prior)
    assert proc.returncode == 0, proc.stderr
    assert data["capture_started_unix"] == 111.5
    assert len(data["capture_resumed_unix"]) == 1


def test_failed_step_attempts_counted_across_resumes(tmp_path):
    prior = {"a": {"rc": 1, "mark": "t1"},
             "capture_aborted_dead_chip_unix": 123.0}
    proc, data = run_capture(
        tmp_path, [fail_step("a"), ok_step("b")],
        ["--mark", "t1", "--skip_fresh", "--no_dead_chip_abort"],
        prior=prior)
    assert proc.returncode == 0, proc.stderr
    assert data["a"]["attempts"] == 2
    # a completed capture clears the stale abort marker
    assert "capture_aborted_dead_chip_unix" not in data
    assert "capture_finished_unix" in data


def test_probe_mode_reports_dead_when_pinned_to_cpu(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, SCRIPT, "--probe"],
                          capture_output=True, text=True, timeout=60,
                          env=env, cwd=REPO)
    assert proc.returncode == 1


def test_dead_chip_failure_does_not_consume_retry_budget(tmp_path):
    # A step killed by the chip going away must be retryable forever: only
    # live-chip failures count toward MAX_STEP_ATTEMPTS, else two outage
    # windows would permanently skip the top-priority step.
    prior = {"a": {"rc": 1, "mark": "t1", "attempts": 1}}
    proc, data = run_capture(
        tmp_path, [fail_step("a")], ["--mark", "t1", "--skip_fresh"],
        prior=prior)
    assert proc.returncode == 3
    assert data["a"]["attempts"] == 1   # unchanged: this failure was "dead chip"


def test_validate_catches_typod_step_name(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    ok = subprocess.run(
        [sys.executable, SCRIPT, "--steps", "headline,flood", "--validate"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    assert ok.returncode == 0 and "steps ok" in ok.stdout
    bad = subprocess.run(
        [sys.executable, SCRIPT, "--steps", "headlne", "--validate"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    assert bad.returncode == 2 and "headlne" in bad.stderr


import contextlib

sys.path.insert(0, REPO)
from tpu_dpow.utils import process_start_time  # noqa: E402


@contextlib.contextmanager
def standin_bench():
    """A live stand-in for a driver-invoked chip user. Yields the flag
    CONTENT alongside the process — "pid start-time" where the kernel
    exposes start times, a bare pid elsewhere (mirroring
    announce_foreign_chip_user, so the tests exercise whichever identity
    form this host would really produce)."""
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    start = process_start_time(proc.pid)
    try:
        yield proc, f"{proc.pid} {start}" if start is not None else str(proc.pid)
    finally:
        proc.kill()
        proc.wait()


def test_capture_yields_to_live_foreign_bench_then_proceeds(tmp_path):
    # The driver's official bench.py announces itself via a pid flag; the
    # capture must wait (bounded) rather than contend for the
    # single-client chip. Tiny max-wait: the capture logs the yield, times
    # the wait out, and still completes.
    flag = tmp_path / "foreign.pid"
    with standin_bench() as (_, identity):
        flag.write_text(identity)
        env_extra = {"TPU_DPOW_FOREIGN_BENCH_FLAG": str(flag),
                     "TPU_DPOW_FOREIGN_MAX_WAIT": "1"}
        proc, data = run_capture(
            tmp_path, [ok_step("a")], ["--mark", "t1"], env_extra=env_extra)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "yielding chip to driver bench.py" in proc.stdout
    assert data["a"]["rc"] == 0


def test_midstep_foreign_bench_kills_step_and_aborts_for_resume(tmp_path):
    # The driver's whole retry budget (~675 s) is SHORTER than the longest
    # step timeouts, so a between-step gate is not enough: a step must die
    # the moment a driver bench appears mid-run, without consuming the
    # step's retry budget.
    flag = tmp_path / "foreign.pid"
    started = tmp_path / "step_started"
    # The step announces itself via a sentinel file so the test can write
    # the foreign flag strictly AFTER the step is in flight — a fixed sleep
    # here proved flaky under load (capture startup outran the sleep and
    # the flag was treated as a pre-step foreign user, parking the capture
    # in the wait path instead of the mid-step kill this test pins).
    slow = ["slow", [sys.executable, "-c",
                     "import pathlib, time; "
                     f"pathlib.Path({str(started)!r}).write_text('x'); "
                     "time.sleep(120)"], 150]
    out = tmp_path / "bench.json"
    steps_file = tmp_path / "steps.json"
    steps_file.write_text(json.dumps([slow]))
    env = dict(os.environ)
    env.update({"TPU_DPOW_BENCH_OUT": str(out), "PYTHONPATH": REPO,
                "JAX_PLATFORMS": "cpu",
                "TPU_DPOW_FOREIGN_BENCH_FLAG": str(flag)})
    with standin_bench() as (_, identity):
        proc = subprocess.Popen(
            [sys.executable, SCRIPT, "--steps_file", str(steps_file),
             "--mark", "t1"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO)
        import time as _time

        try:
            deadline = _time.monotonic() + 60
            while not started.exists():
                assert _time.monotonic() < deadline, "slow step never started"
                assert proc.poll() is None, proc.communicate()
                _time.sleep(0.2)
            flag.write_text(identity)
            stdout, stderr = proc.communicate(timeout=120)
        except BaseException:
            proc.kill()
            proc.wait()
            raise
    data = json.loads(out.read_text())
    assert proc.returncode == 3, (stdout, stderr)
    assert "killed to yield" in stdout
    assert data["slow"]["rc"] == "yielded"
    assert data["slow"]["seconds"] < 60  # killed, not run to completion
    assert "attempts" not in data["slow"]  # yield never consumes the budget
    assert "capture_yielded_to_driver_unix" in data


def test_wedged_foreign_bench_flag_force_cleared_after_wait_cap(tmp_path):
    # A wedged-but-alive foreign bench must not park the capture forever:
    # once the wait cap expires its flag is force-cleared, so the mid-step
    # foreign check cannot kill the very next step and loop the abort
    # cycle (a real driver bench finishes well inside the cap).
    flag = tmp_path / "foreign.pid"
    with standin_bench() as (_, identity):
        flag.write_text(identity)
        env_extra = {"TPU_DPOW_FOREIGN_BENCH_FLAG": str(flag),
                     "TPU_DPOW_FOREIGN_MAX_WAIT": "1"}
        proc, data = run_capture(
            tmp_path, [ok_step("a")], ["--mark", "t1"], env_extra=env_extra)
        assert not flag.exists()  # cleared while the wedged process lives
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "treating it as wedged" in proc.stdout
    assert data["a"]["rc"] == 0


def test_zombie_chip_user_reads_as_gone():
    # A SIGKILLed-but-unreaped (zombie) chip user holds nothing; its
    # /proc stat line still exists, so the identity helper must report it
    # gone by state, not alive by start-time.
    import time

    proc = subprocess.Popen([sys.executable, "-c", "pass"],
                            stdout=subprocess.DEVNULL)
    try:
        deadline = time.time() + 10
        while process_start_time(proc.pid) is not None and time.time() < deadline:
            time.sleep(0.05)
        assert process_start_time(proc.pid) is None
    finally:
        proc.wait()


def test_stale_foreign_bench_flag_is_removed_and_ignored(tmp_path):
    # A flag left by a SIGKILLed chip user whose pid was RECYCLED must not
    # stall anything: the pid below is alive, but its kernel start-time
    # cannot match the (fabricated) one in the flag.
    flag = tmp_path / "foreign.pid"
    with standin_bench() as (proc_alive, _):
        flag.write_text(f"{proc_alive.pid} 1")
        env_extra = {"TPU_DPOW_FOREIGN_BENCH_FLAG": str(flag)}
        proc, data = run_capture(
            tmp_path, [ok_step("a")], ["--mark", "t1"], env_extra=env_extra)
    assert proc.returncode == 0, proc.stderr
    assert "yielding" not in proc.stdout
    assert data["a"]["rc"] == 0
    assert not flag.exists()


def test_bench_announces_and_clears_foreign_flag(tmp_path, monkeypatch):
    import bench
    from tpu_dpow.utils import process_start_time

    flag = tmp_path / "foreign.pid"
    monkeypatch.setenv("TPU_DPOW_FOREIGN_BENCH_FLAG", str(flag))
    monkeypatch.delenv("TPU_DPOW_EVIDENCE_CAPTURE", raising=False)
    bench._announce_foreign_bench()
    pid, start = flag.read_text().split()
    assert pid == str(os.getpid())
    assert start == process_start_time(os.getpid())  # exact identity
    bench._clear_foreign_bench()
    assert not flag.exists()

    # Capture-spawned bench runs must NOT announce: they are the capture.
    monkeypatch.setenv("TPU_DPOW_EVIDENCE_CAPTURE", "1")
    bench._announce_foreign_bench()
    assert not flag.exists()


def test_no_dead_chip_abort_flag_keeps_going(tmp_path):
    proc, data = run_capture(
        tmp_path, [fail_step("a"), ok_step("b")],
        ["--mark", "t1", "--no_dead_chip_abort"])
    assert proc.returncode == 0, proc.stderr
    assert data["a"]["rc"] == 1 and data["b"]["rc"] == 0
    assert "capture_finished_unix" in data
