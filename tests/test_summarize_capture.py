"""summarize_capture.py contract — the tool that turns a capture artifact
into the round's PASS/FAIL gap list. A bug here misreports the evidence
the whole round exists to produce (a false PASS hides a regression; a
false FAIL sends the next session chasing a ghost), so the criteria
arithmetic and the mark staleness filter are pinned against synthetic
artifacts."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "benchmarks", "summarize_capture.py")


def summarize(tmp_path, data, argv=()):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(data))
    argv = list(argv)
    if "--invalidated" not in argv:
        # Hermetic by default: a future entry in the repo's live
        # benchmarks/invalidated.json whose fingerprint happened to match a
        # synthetic record here would silently flip unrelated assertions.
        # Only test_repo_invalidation_list_covers_the_r4_mesh1_record reads
        # the real file (directly, not via this helper).
        empty = tmp_path / "_no_invalidations.json"
        empty.write_text("[]")
        argv += ["--invalidated", str(empty)]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--path", str(path), *argv],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    rows = {}
    for line in proc.stdout.splitlines():
        parts = line.split(None, 2)
        if len(parts) >= 2:
            rows[parts[0]] = (parts[1], parts[2] if len(parts) > 2 else "")
    return proc, rows


def test_headline_pass_requires_tpu_platform(tmp_path):
    data = {"headline": {"rc": 0, "result": {
        "platform": "cpu", "value": 2e9, "unit": "H/s"}}}
    _, rows = summarize(tmp_path, data)
    assert rows["headline"][0] == "FAIL"
    data["headline"]["result"]["platform"] = "tpu"
    _, rows = summarize(tmp_path, data)
    assert rows["headline"][0] == "PASS"


def test_batch_ratio_math_against_difficulty(tmp_path):
    # p(solve) = (2^64 - difficulty)/2^64 = 2^-26 at base difficulty, so a
    # batch of 64 expects 64 * 2^26 hashes; exactly that many = ratio 1.0.
    difficulty = "ffffffc000000000"
    p_solve = (2**64 - int(difficulty, 16)) / 2**64
    data = {"batch": {"rc": 0, "result": {
        "batch": 64, "difficulty": difficulty, "solves_per_sec": 10.0,
        "device_hashes": 64 / p_solve}}}
    _, rows = summarize(tmp_path, data)
    assert rows["batch"][0] == "PASS" and "1.0x" in rows["batch"][1]
    data["batch"]["result"]["device_hashes"] = 1.5 * 64 / p_solve
    _, rows = summarize(tmp_path, data)
    assert rows["batch"][0] == "FAIL" and "1.5x" in rows["batch"][1]


def test_mark_filter_rejects_stale_records(tmp_path):
    data = {"fairness": {"rc": 0, "mark": "r3",
                         "result": {"added_p50_ms": 5.0}}}
    _, rows = summarize(tmp_path, data, ["--mark", "r4"])
    assert rows["fairness"][0] == "absent"
    _, rows = summarize(tmp_path, data, ["--mark", "r3"])
    assert rows["fairness"][0] == "PASS"


def test_fairness_requires_nonnegative_tax(tmp_path):
    data = {"fairness": {"rc": 0, "result": {"added_p50_ms": -145.7}}}
    _, rows = summarize(tmp_path, data)
    assert rows["fairness"][0] == "FAIL"


def test_precache_gates_on_hit_latency_and_errors(tmp_path):
    rec = {"rc": 0, "result": {"hit_p50_ms": 1.8, "pipeline_p50_ms": 40.0,
                               "errors": 0}}
    _, rows = summarize(tmp_path, {"precache": rec})
    assert rows["precache"][0] == "PASS"
    rec["result"]["hit_p50_ms"] = 130.0  # a device wait, not a cache hit
    _, rows = summarize(tmp_path, {"precache": rec})
    assert rows["precache"][0] == "FAIL"
    rec["result"]["hit_p50_ms"] = 1.8
    rec["result"]["errors"] = 2
    _, rows = summarize(tmp_path, {"precache": rec})
    assert rows["precache"][0] == "FAIL"


def test_flood_gates_on_e2e_overscan_ratio_when_present(tmp_path):
    rec = {"rc": 0, "result": {"req_per_sec": 18.8, "p50_ms": 940.0,
                               "hashes_per_ok_vs_bound": 1.04}}
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "PASS" and "1.04x" in rows["flood"][1]
    rec["result"]["hashes_per_ok_vs_bound"] = 1.8  # r3's overscan regime
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "FAIL"
    del rec["result"]["hashes_per_ok_vs_bound"]  # old record: rate only
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "PASS"


def test_flood_gate_prefers_error_adjusted_ratio(tmp_path):
    # With zero errors the two ratios are equal and the error-adjusted one
    # wins when present; a nonzero error count fails outright — per-ok
    # inflates and per-req dilutes (a cheaply-aborted errored request is
    # credited a full 1/p budget), so neither ratio is gateable.
    rec = {"rc": 0, "result": {"req_per_sec": 18.0, "p50_ms": 950.0,
                               "errors": 0,
                               "hashes_per_ok_vs_bound": 1.05,
                               "hashes_per_req_vs_bound": 1.05}}
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "PASS" and "1.05x" in rows["flood"][1]
    rec["result"]["hashes_per_req_vs_bound"] = 1.4  # genuine overscan
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "FAIL"
    # Errors gate first: ratio dilution cannot mask overscan on a lossy run.
    rec["result"].update(errors=50, hashes_per_req_vs_bound=1.0,
                         hashes_per_ok_vs_bound=2.0)
    _, rows = summarize(tmp_path, {"flood": rec})
    assert rows["flood"][0] == "FAIL"


def test_cancel_gates_on_probe_first_readback_majority(tmp_path):
    rec = {"rc": 0, "result": {
        "added_p50_ms": 100.0, "bound_windows": 20,
        "probe_launches_per_solve": {"1": 8, "2": 2}}}
    _, rows = summarize(tmp_path, {"cancel": rec})
    assert rows["cancel"][0] == "PASS"
    # Probes mostly chaining extra readbacks = the corpse demotion is back.
    rec["result"]["probe_launches_per_solve"] = {"1": 2, "3": 8}
    _, rows = summarize(tmp_path, {"cancel": rec})
    assert rows["cancel"][0] == "FAIL"
    # Exactly half degraded is not a majority solving on readback #1.
    rec["result"]["probe_launches_per_solve"] = {"1": 5, "2": 5}
    _, rows = summarize(tmp_path, {"cancel": rec})
    assert rows["cancel"][0] == "FAIL"


def test_cancel_bound_prices_launch_floor_from_overhead_record(tmp_path):
    # The drain serializes ~2 launch round trips, so the bound must widen
    # with the SAME capture's measured padded-launch floor: 20*3.7 + 2*66
    # ≈ 206 ms. Without an overhead record it falls back to doubling.
    cancel = {"rc": 0, "result": {"added_p50_ms": 180.0, "bound_windows": 20}}
    overhead = {"rc": 0, "result": {"pad_batch16_8win_ms": 66.0}}
    _, rows = summarize(tmp_path, {"cancel": cancel, "overhead": overhead})
    assert rows["cancel"][0] == "PASS" and "~206 ms bound" in rows["cancel"][1]
    _, rows = summarize(tmp_path, {"cancel": cancel})  # fallback: 148 ms
    assert rows["cancel"][0] == "FAIL" and "~148 ms bound" in rows["cancel"][1]
    cancel["result"]["added_p50_ms"] = 361.8  # the pre-fix r4 on-chip value
    _, rows = summarize(tmp_path, {"cancel": cancel, "overhead": overhead})
    assert rows["cancel"][0] == "FAIL"


def test_invalidated_record_grades_stale_not_pass(tmp_path):
    # VERDICT r4 item 4: a record the docs disavow must be UN-GRADABLE even
    # though its rc is 0 and its mark matches — a PASS for a dead number
    # lets a future reader cite it.
    inv = tmp_path / "invalidated.json"
    inv.write_text(json.dumps([{
        "step": "latency_mesh1", "mark": "r4",
        "match": {"p50_ms": 183.6}, "reason": "guard bug: plain-vs-plain"}]))
    rec = {"rc": 0, "mark": "r4",
           "result": {"p50_ms": 183.6, "mesh_devices": 1}}
    proc, rows = summarize(tmp_path, {"latency_mesh1": rec},
                           ["--mark", "r4", "--invalidated", str(inv)])
    assert rows["latency_mesh1"][0] == "stale"
    assert "guard bug" in rows["latency_mesh1"][1]
    # A stale record is missing evidence, not a failure: exit code stays 0.
    assert proc.returncode == 0


def test_recapture_supersedes_invalidation_fingerprint(tmp_path):
    # Same step, same mark, but the measured values differ from the
    # disavowed record's fingerprint: this is a genuine re-capture and must
    # grade normally without anyone editing the invalidation list.
    inv = tmp_path / "invalidated.json"
    inv.write_text(json.dumps([{
        "step": "latency_mesh1", "mark": "r4",
        "match": {"p50_ms": 183.6}, "reason": "guard bug"}]))
    rec = {"rc": 0, "mark": "r4",
           "result": {"p50_ms": 140.2, "mesh_devices": 1}}
    _, rows = summarize(tmp_path, {"latency_mesh1": rec},
                        ["--mark", "r4", "--invalidated", str(inv)])
    assert rows["latency_mesh1"][0] == "PASS"


def test_unreadable_invalidation_list_fails_closed(tmp_path):
    # An unreadable (truncated / merge-conflicted) disavowal list must
    # FAIL CLOSED (ADVICE r5): no record can prove it is not disavowed, so
    # every step grades stale — never PASS — and the exit code is nonzero
    # even though nothing graded FAIL.
    inv = tmp_path / "invalidated.json"
    inv.write_text('[{"step": "x",')  # merge-conflict / truncation artifact
    rec = {"rc": 0, "mark": "r4", "result": {"p50_ms": 183.6}}
    proc, rows = summarize(tmp_path, {"latency_mesh1": rec},
                           ["--mark", "r4", "--invalidated", str(inv)])
    assert "unreadable" in proc.stdout
    assert rows["latency_mesh1"][0] == "stale"
    assert proc.returncode != 0
    # An entry with no match fingerprint can never fire: warn, don't ignore
    # silently (match-all would break re-capture supersession by design).
    # Entry-level damage stays fail-open — the rest of the list still works.
    inv.write_text(json.dumps([{"step": "latency_mesh1", "mark": "r4",
                                "reason": "no fingerprint"}]))
    proc, rows = summarize(tmp_path, {"latency_mesh1": rec},
                           ["--mark", "r4", "--invalidated", str(inv)])
    assert "WARNING" in proc.stdout and "fingerprint" in proc.stdout
    assert rows["latency_mesh1"][0] == "PASS"
    assert proc.returncode == 0


def test_crashed_criteria_step_grades_fail_not_absent(tmp_path):
    # A step that died before printing its result JSON (rc != 0, no
    # "result") is a regression that crashed instead of degrading; absent
    # would not count toward the exit code and the artifact would read
    # clean. "yielded" (killed for a driver bench) stays absent.
    crashed = {"rc": 1, "stderr_tail": ["AssertionError: mesh missing"]}
    proc, rows = summarize(tmp_path, {"gang_e2e": dict(crashed),
                                      "flood": dict(crashed),
                                      "soak": dict(crashed)})
    for name in ("gang_e2e", "flood", "soak"):
        assert rows[name][0] == "FAIL", rows[name]
    assert proc.returncode == 1
    _, rows = summarize(tmp_path, {"flood": {"rc": "yielded"}})
    assert rows["flood"][0] == "absent"


def test_gang_e2e_gates_on_engagement_and_bounds(tmp_path):
    good = {"rc": 0, "result": {
        "gang": 8, "n": 12, "burst": 6, "gang_engaged": True,
        "ganged_ok": 18, "plain_ok": 18, "ganged_errors": 0,
        "plain_errors": 0, "ganged_p50_ms": 64.1, "plain_p50_ms": 10.9,
        "machinery_added_p50_ms": 53.2,
        "p50_bound_ms": 500.0, "machinery_bound_ms": 400.0}}
    _, rows = summarize(tmp_path, {"gang_e2e": good})
    assert rows["gang_e2e"][0] == "PASS"
    # The r4 failure mode: the mesh guard silently not engaging the gang.
    bad = json.loads(json.dumps(good))
    bad["result"]["gang_engaged"] = False
    _, rows = summarize(tmp_path, {"gang_e2e": bad})
    assert rows["gang_e2e"][0] == "FAIL"
    # Machinery blowing its bound (the record carries its own bound).
    bad = json.loads(json.dumps(good))
    bad["result"]["machinery_added_p50_ms"] = 450.0
    _, rows = summarize(tmp_path, {"gang_e2e": bad})
    assert rows["gang_e2e"][0] == "FAIL"
    # A dropped request (ok != n + burst) in either mode.
    bad = json.loads(json.dumps(good))
    bad["result"]["plain_ok"] = 17
    _, rows = summarize(tmp_path, {"gang_e2e": bad})
    assert rows["gang_e2e"][0] == "FAIL"


def test_soak_gates_on_errors_and_leaks(tmp_path):
    rec = {"rc": 0, "result": {"ops": 160, "ok": 160, "aborted": 0, "error": 0,
                               "leaks": 0, "ok_per_sec": 18.0}}
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "PASS"
    rec["result"]["leaks"] = 2
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "FAIL"
    rec["result"]["leaks"] = 0
    rec["result"]["error"] = 1
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "FAIL"


def test_soak_gates_on_outcome_mix(tmp_path):
    """VERDICT r5 item 6: the ok/aborted/timeout mix is an explicit PASS
    criterion — the old gate silently tolerated 19% non-ok as long as
    nothing errored or leaked."""
    # The soak workload is 20% deliberate aborts: ok at exactly 80% with
    # the rest aborted is the expected healthy mix.
    rec = {"rc": 0, "result": {"ops": 160, "ok": 130, "aborted": 30,
                               "error": 0, "leaks": 0, "ok_per_sec": 22.0}}
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "PASS"
    # NORMAL requests failing as "aborted" (ok below the 80% floor) must
    # fail even though errors and leaks are zero.
    rec["result"].update(ok=120, aborted=40)
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "FAIL"
    # Accounting must close: ops that vanished from the outcome counters
    # (neither ok nor aborted nor error) can never summarize clean.
    rec["result"].update(ok=130, aborted=20)
    _, rows = summarize(tmp_path, {"soak": rec})
    assert rows["soak"][0] == "FAIL"
    assert "UNACCOUNTED" in rows["soak"][1]


def test_exit_code_reflects_failures(tmp_path):
    ok = {"flood": {"rc": 0, "result": {"req_per_sec": 15.0, "p50_ms": 900}}}
    proc, _ = summarize(tmp_path, ok)
    assert proc.returncode == 0
    bad = {"flood": {"rc": 0, "result": {"req_per_sec": 9.4, "p50_ms": 2000}}}
    proc, rows = summarize(tmp_path, bad)
    assert proc.returncode == 1 and rows["flood"][0] == "FAIL"
