"""Chip-yield drill contract (benchmarks/yield_drill.py).

The drill is the round's answer to four straight CPU-fallback driver
artifacts, so its machinery must be provably correct BEFORE a live window:
this runs the REAL holder path (a genuine capture_evidence.py subprocess
holding the engine via a latency step on CPU) against a STUBBED driver that
announces through the real tpu_dpow.utils flag — exercising startup
detection, the mid-step yield kill, rc-3 propagation, flag cleanup, and the
record write, with only the chip itself faked.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import yield_drill  # noqa: E402


def test_fresh_ok_matches_mark_and_ok(tmp_path):
    out = tmp_path / "bench.json"
    rec = {"yield_drill": {"rc": 0, "mark": "r5", "result": {"ok": True}}}
    out.write_text(json.dumps(rec))
    assert yield_drill.fresh_ok(str(out), "r5")
    assert not yield_drill.fresh_ok(str(out), "r6")  # different mark
    rec["yield_drill"]["result"]["ok"] = False
    out.write_text(json.dumps(rec))
    assert not yield_drill.fresh_ok(str(out), "r5")  # failed drill re-runs
    assert not yield_drill.fresh_ok(str(tmp_path / "absent.json"), "r5")


def test_failed_drill_on_dead_chip_returns_3_without_recording(
        tmp_path, monkeypatch):
    """A drill failure a lost chip explains must NOT record a false
    negative: rc 3 tells the watcher to resume and retry next window."""
    import subprocess

    monkeypatch.setattr(yield_drill, "SETTLE_S", 0.5)

    # The rc-3 decision is pure logic over the driver result + the chip
    # veto; the real holder mechanics are covered by the yield test below.
    # A stub holder (prints the step line, exits 3 on its own) keeps this
    # test at seconds, not a second full capture subprocess.
    def stub_holder(tmpdir):
        return subprocess.Popen(
            [sys.executable, "-c",
             "import time; print('== hold: stub', flush=True); "
             "time.sleep(3); raise SystemExit(3)"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True)

    monkeypatch.setattr(yield_drill, "start_holder", stub_holder)

    def stub_driver():
        # The driver's 120 s budget expired with a CPU fallback number —
        # the smoke-observed shape of a drill run during an outage.
        return {"rc": 124, "seconds": 120.0,
                "result": {"platform": "cpu", "value": 9e5}}

    monkeypatch.setattr(yield_drill, "run_driver_sim", stub_driver)
    monkeypatch.setattr(yield_drill.ce, "chip_alive", lambda *a, **k: False)
    out = tmp_path / "bench.json"
    monkeypatch.setattr(
        sys, "argv", ["yield_drill.py", "--mark", "t", "--out", str(out)])
    assert yield_drill.main() == 3
    assert not out.exists() or "yield_drill" not in json.loads(out.read_text())


def test_drill_yields_real_holder_to_announced_driver(tmp_path, monkeypatch):
    """Full drill mechanics on CPU: real holder capture, stubbed driver."""
    from tpu_dpow.utils import (announce_foreign_chip_user,
                                clear_foreign_chip_user)

    # Fast knobs: small settle, a holder long enough to still be mid-step
    # when the stub announces (~15 s of CPU solves).
    monkeypatch.setattr(yield_drill, "SETTLE_S", 2.0)
    monkeypatch.setattr(yield_drill, "HOLDER_N", "3000")

    def stub_driver():
        # The driver's observable behavior, minus the chip: announce via the
        # REAL flag (the holder's run_step must kill its step within ~5 s),
        # hold it a beat, clean up, report a TPU-shaped success.
        announce_foreign_chip_user()
        try:
            time.sleep(8)
        finally:
            clear_foreign_chip_user()
        return {"rc": 0, "seconds": 41.0,
                "result": {"platform": "tpu", "value": 1.2e9}}

    monkeypatch.setattr(yield_drill, "run_driver_sim", stub_driver)
    # A dead chip must not veto recording in the stubbed environment.
    monkeypatch.setattr(yield_drill.ce, "chip_alive", lambda *a, **k: True)

    out = tmp_path / "bench.json"
    monkeypatch.setattr(
        sys, "argv",
        ["yield_drill.py", "--mark", "test", "--out", str(out)])
    rc = yield_drill.main()
    assert rc == 0
    rec = json.loads(out.read_text())["yield_drill"]
    assert rec["mark"] == "test"
    r = rec["result"]
    assert r["holder_rc"] == 3, r  # the capture aborted BECAUSE it yielded
    assert r["holder_yielded"] is True, r
    assert r["announce_flag_cleaned"] is True, r
    assert r["ok"] is True, r
    # And a second invocation self-skips on the fresh ok record.
    assert yield_drill.fresh_ok(str(out), "test")


def test_drill_refuses_while_capture_holds_artifact_lock(tmp_path, monkeypatch):
    """ADVICE r5: a manually launched drill must not race a mid-flight
    capture's read-modify-write of the shared artifact — with the capture's
    lock held, the drill exits rc 3 (try again later) without writing."""
    out = tmp_path / "bench.json"
    monkeypatch.setattr(
        sys, "argv", ["yield_drill.py", "--mark", "t", "--out", str(out)])
    with yield_drill.ce.artifact_lock(str(out)):  # a "capture" mid-flight
        assert yield_drill.main() == 3
    assert not out.exists()


def test_concurrent_captures_on_one_artifact_refused(tmp_path):
    """Second capture on the SAME artifact is refused (rc 2) while the
    first holds the lock; a different artifact is unaffected."""
    import subprocess

    ce = yield_drill.ce
    env = dict(os.environ)
    env["TPU_DPOW_BENCH_OUT"] = str(tmp_path / "bench.json")
    env["PYTHONPATH"] = REPO
    with ce.artifact_lock(str(tmp_path / "bench.json")):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks",
                                          "capture_evidence.py"),
             "--steps", "headline"],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 2, (proc.stdout, proc.stderr)
        assert "busy" in proc.stderr
