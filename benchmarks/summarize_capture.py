"""Judge the latest evidence capture against the round-4 success criteria.

Reads BENCH_latency.json and prints one PASS/FAIL/absent line per criterion
(VERDICT r3 "Next round" item 1 plus this round's additions), so a fresh
on-chip capture turns into an actionable gap list in one command:

    python benchmarks/summarize_capture.py [--mark r4]

Criteria (anchors: VERDICT.md items 1/2/5, BASELINE.md north stars):
  headline   ≥ 1e9 H/s on platform tpu
  flood      ≥ 14 req/s (≈75% of the r3-measured 18.6/s device ceiling);
             when the record carries hashes_per_ok_vs_bound, also ≤ 1.2x
  batch      ≤ 1.2x the per-solve hash bound
  fairness   added_p50 ≥ 0 (a tax, not a credit)
  precache   hit p50 ≤ 25 ms with zero errors (cache hit, not device wait)
  cancel     post-cancel added_p50 within the residue bound; when the
             record carries probe_launches_per_solve, a strict majority of
             probes must solve on their first applied readback
  tests_tpu  rc 0
  soak       zero errors, zero leaked jobs, AND the expected outcome mix:
             ok + aborted + error must account for every op and ok must be
             ≥ 80% of ops (the workload is 20% deliberate aborts; every
             normal request must succeed — VERDICT r5 item 6)
  gang_e2e   gang engaged, all requests validate, p50/machinery in-bounds
  yield_drill driver's exact command rc 0 on tpu in <=120 s THROUGH a
             yielding capture, announce flag cleaned up after
  gang_ab    machinery delta reported (informational)

Invalidated records (VERDICT r4 item 4): a capture record the docs have
disavowed (e.g. r4's latency_mesh1 183.6 ms, measured through a guard bug)
must be UN-GRADABLE — never PASS — even though its rc is 0 and its mark
matches. benchmarks/invalidated.json lists them declaratively; a matching
record grades as `stale` with the reason printed. Matching is pinned to the
step + mark + a result-field fingerprint, so a genuine re-capture under the
same mark (different measured values) automatically supersedes the entry.
"""

from __future__ import annotations

# No _bootstrap import on purpose: the summarizer is pure-JSON arithmetic,
# and _bootstrap's jax import costs ~2 s per invocation (it runs once per
# test case in tests/test_summarize_capture.py).

import argparse
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def res(record):
    return (record or {}).get("result") or {}


class InvalidationsUnreadable(Exception):
    """The invalidation list exists but cannot be parsed.

    Fail CLOSED (ADVICE r5): the old warn-and-continue meant a truncated /
    merge-conflicted list silently re-enabled PASS for every disavowed
    record in any pipeline that logs stdout nobody reads. Callers must
    treat affected records as un-gradable (stale) or exit nonzero, never
    grade them PASS.
    """


def load_invalidations(path=None):
    """Declarative list of disavowed records (benchmarks/invalidated.json).

    Each entry: {"step": name, "mark": mark-or-null, "match": {result-field:
    value, ...}, "reason": text}. A record is invalidated only when the step
    name matches, the mark matches (a null mark matches any), and EVERY
    match field equals the record's result value — the fingerprint is what
    lets a re-capture under the same mark supersede the entry without
    editing this file.

    A list that exists but cannot be parsed raises InvalidationsUnreadable
    — the absence of a list is "nothing disavowed", but an unreadable one
    is "cannot know what is disavowed", which must never grade PASS.
    """
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "invalidated.json")
        if not os.path.exists(path):
            return []  # no disavowal list in this checkout: nothing to do
    try:
        with open(path) as f:
            entries = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise InvalidationsUnreadable(f"{path}: {e}") from e
    if not isinstance(entries, list):
        raise InvalidationsUnreadable(f"{path}: not a JSON list")
    kept = []
    for e in entries:
        if not (isinstance(e, dict) and e.get("step") and e.get("match")):
            # An entry without a result-field fingerprint can never match
            # (and match-all semantics would break re-capture supersession):
            # surface it instead of silently grading the record PASS.
            print(f"WARNING: invalidation entry ignored (needs 'step' and a "
                  f"non-empty 'match' fingerprint): {json.dumps(e)[:120]}",
                  flush=True)
            continue
        kept.append(e)
    return kept


def invalidation_reason(name, rec, entries):
    r = res(rec)
    for e in entries:
        if e.get("step") != name:
            continue
        if e.get("mark") is not None and rec.get("mark") != e.get("mark"):
            continue
        match = e.get("match") or {}
        if match and all(r.get(k) == v for k, v in match.items()):
            return e.get("reason", "invalidated (no reason recorded)")
    return None


def main() -> int:
    p = argparse.ArgumentParser("capture summary vs round criteria")
    p.add_argument("--mark", default=None,
                   help="only trust steps recorded with this mark")
    p.add_argument("--path", default=os.path.join(REPO, "BENCH_latency.json"))
    p.add_argument("--invalidated", default=None,
                   help="override the invalidation list path (tests)")
    args = p.parse_args()
    try:
        with open(args.path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"no capture to summarize: {e}")
        return 1

    try:
        invalidations = load_invalidations(args.invalidated)
        invalidations_unreadable = None
    except InvalidationsUnreadable as e:
        # Fail CLOSED: with the disavowal list unreadable, no record can
        # prove it is NOT disavowed — every step grades stale and the exit
        # code is nonzero until the list is fixed (ADVICE r5).
        invalidations = []
        invalidations_unreadable = str(e)
        print(f"ERROR: invalidation list unreadable ({e}); failing closed — "
              "all steps grade stale until the list is fixed", flush=True)
    stale = {}  # step name -> invalidation reason (for the row printer)

    def step(name):
        rec = data.get(name)
        if not isinstance(rec, dict):
            return None
        if args.mark and rec.get("mark") != args.mark:
            return None  # stale: from a previous revision's capture
        if invalidations_unreadable is not None:
            stale[name] = ("invalidation list unreadable "
                           f"({invalidations_unreadable}); cannot prove "
                           "this record is not disavowed")
            return None
        reason = invalidation_reason(name, rec, invalidations)
        if reason is not None:
            stale[name] = reason
            return None  # disavowed: un-gradable, never PASS
        return rec

    rows = []

    def row(name, ok, detail):
        if ok is None and name in stale:
            rows.append((name, "stale", f"INVALIDATED: {stale[name]}"))
            return
        rows.append((name, {True: "PASS", False: "FAIL", None: "absent"}[ok], detail))

    def crit(name):
        """(result, crash_detail) for a graded step.

        A record under the current mark whose rc is neither 0 nor "yielded"
        is a CRASH: the step died before printing its result JSON (a
        regression that raises instead of degrading). It must grade FAIL,
        not absent — "absent" doesn't count toward the exit code, so a hard
        break would read as missing evidence and summarize clean. "yielded"
        (killed to hand the chip to a driver bench) stays absent: not the
        code's failure.
        """
        rec = step(name)
        if rec is None:
            return {}, None
        if rec.get("rc", 0) not in (0, "yielded"):
            tail = (rec.get("stderr_tail") or rec.get("tail") or [""])[-1]
            return {}, f"step failed rc={rec.get('rc')} {tail}".strip()
        return res(rec), None

    r, crash = crit("headline")
    if crash:
        row("headline", False, crash)
    elif r:
        row("headline", r.get("platform") == "tpu" and r.get("value", 0) >= 1e9,
            f"{r.get('value', 0)/1e9:.3f} GH/s on {r.get('platform')}")
    else:
        row("headline", None, "no fresh record")

    r = step("tests_tpu")
    row("tests_tpu", (r or {}).get("rc") == 0 if r else None,
        f"rc={(r or {}).get('rc')}" if r else "no fresh record")

    r, crash = crit("flood")
    if crash:
        row("flood", False, crash)
    elif r:
        # The e2e overscan signal (same 1.2x criterion as the batch step)
        # gates alongside throughput when the record carries it. Errors gate
        # FIRST: with errors > 0 neither ratio is trustworthy (per-ok
        # inflates — device hashes spent on errored requests sit only in
        # its numerator; per-req dilutes — an errored request that aborted
        # cheaply is credited a full 1/p budget), and a flood run with
        # failures is not a PASS anyway. With errors == 0 the two ratios
        # are equal; prefer the error-adjusted one, falling back to per-ok
        # for records predating it (ADVICE r4).
        ratio = r.get("hashes_per_req_vs_bound", r.get("hashes_per_ok_vs_bound"))
        ok = (r.get("req_per_sec", 0) >= 14 and r.get("errors", 0) == 0
              and (ratio is None or ratio <= 1.2))
        detail = (f"{r.get('req_per_sec')} req/s, p50 {r.get('p50_ms')} ms, "
                  f"errors {r.get('errors', 0)}")
        if ratio is not None:
            detail += f", {ratio}x the 1/p bound"
        row("flood", ok, detail)
    else:
        row("flood", None, "no fresh record")

    r, crash = crit("batch")
    if crash:
        row("batch", False, crash)
    elif r and r.get("device_hashes") and r.get("batch") and r.get("difficulty"):
        # ratio of scanned hashes to the 1/p expectation per solve
        p_solve = (2**64 - int(r["difficulty"], 16)) / 2**64
        bound = r["batch"] / p_solve
        ratio = round(r["device_hashes"] / bound, 3)
        row("batch", ratio <= 1.2,
            f"hashes/solve = {ratio}x the 1/p bound "
            f"({r['solves_per_sec']} solves/s)")
    else:
        row("batch", None, "no fresh record")

    r, crash = crit("fairness")
    if crash:
        row("fairness", False, crash)
    elif r:
        row("fairness", r.get("added_p50_ms", -1) >= 0,
            f"added_p50 {r.get('added_p50_ms')} ms (solo {r.get('solo_p50_ms')}, "
            f"mixed {r.get('mixed_p50_ms')})")
    else:
        row("fairness", None, "no fresh record")

    r, crash = crit("cancel")
    if crash:
        row("cancel", False, crash)
    elif r:
        # Residue bound in ms: bound_windows of scan at flagship throughput
        # (~3.7 ms/window) plus the launch round trips the drain inherently
        # serializes — the run loop awaits the corpse launch's readback, and
        # the probe's own launch pays one more. Price those at the SAME
        # capture's measured padded-launch floor (overhead step) so a slow
        # chip day widens the bound with the evidence in hand; fall back
        # to doubling for jitter when no overhead record landed.
        floor = res(step("overhead")).get("pad_batch16_8win_ms")
        if floor:
            bound_ms = r.get("bound_windows", 20) * 3.7 + 2 * floor
        else:
            bound_ms = r.get("bound_windows", 20) * 3.7 * 2
        ok = r.get("added_p50_ms", 1e9) <= bound_ms
        detail = f"added_p50 {r.get('added_p50_ms')} ms vs ~{bound_ms:.0f} ms bound"
        probe = r.get("probe_launches_per_solve")
        if probe:
            # A STRICT majority of post-cancel probes must solve on their
            # first applied readback — the corpse-aware full-width head
            # working (a 50/50 split is half the probes degraded: fail).
            first = probe.get("1", probe.get(1, 0))
            ok = ok and first * 2 > sum(probe.values())
            detail += f", probe launches {probe}"
        row("cancel", ok, detail)
    else:
        row("cancel", None, "no fresh record")

    r, crash = crit("precache")
    if crash:
        row("precache", False, crash)
    elif r:
        # The hit path does zero device work; r2 measured p50 1.8 ms. Allow
        # generous headroom — anything near one HTTP round trip passes, a
        # hit that waits on the device (~100+ ms through the chip) fails.
        row("precache", (r.get("hit_p50_ms") or 1e9) <= 25 and r.get("errors") == 0,
            f"hit p50 {r.get('hit_p50_ms')} ms, pipeline p50 "
            f"{r.get('pipeline_p50_ms')} ms, errors {r.get('errors')}")
    else:
        row("precache", None, "no fresh record")

    r, crash = crit("gang_e2e")
    if crash:
        row("gang_e2e", False, crash)
    elif r:
        # Full-stack drive of the ganged engine on the virtual 8-mesh: the
        # gang must actually engage, every request (sequential + burst, both
        # modes) must validate, and the ganged p50 / e2e machinery delta
        # must sit inside the bounds the record itself carries (gang_e2e.py
        # self-gates with the same arithmetic; grading it here makes a gang
        # regression fail the round artifact, not just a unit test).
        want = r.get("n", 0) + r.get("burst", 0)
        machinery = r.get("machinery_added_p50_ms")
        ok = (r.get("gang_engaged") is True
              and r.get("ganged_errors", 1) == 0
              and r.get("plain_errors", 1) == 0
              and r.get("ganged_ok") == want and r.get("plain_ok") == want
              and (r.get("ganged_p50_ms") or 1e9) <= r.get("p50_bound_ms", 500)
              and machinery is not None
              and machinery <= r.get("machinery_bound_ms", 400))
        row("gang_e2e", ok,
            f"gang {r.get('gang')}: ganged p50 {r.get('ganged_p50_ms')} ms, "
            f"machinery +{machinery} ms, errors "
            f"{r.get('ganged_errors')}/{r.get('plain_errors')}")
    else:
        row("gang_e2e", None, "no fresh record")

    r, crash = crit("yield_drill")
    if crash:
        row("yield_drill", False, crash)
    elif r:
        # The chip-yield protocol exercised for real: a concurrent capture
        # must yield and the driver's exact command must land rc 0 on TPU
        # inside its shortest budget. The record's own ok folds all of it.
        row("yield_drill", r.get("ok") is True,
            f"driver rc={r.get('driver_rc')} in {r.get('driver_seconds')}s "
            f"on {r.get('driver_platform')}, holder_yielded="
            f"{r.get('holder_yielded')}")
    else:
        row("yield_drill", None, "no fresh record")

    r, crash = crit("soak")
    if crash:
        row("soak", False, crash)
    elif r:
        # soak.py self-gates (rc 1 on error/leak); mirror it — AND gate
        # the outcome MIX explicitly (VERDICT r5 item 6): the workload is
        # 20% deliberate client aborts (soak.py one_op kind==4) and 80%
        # normal/raised requests that must ALL succeed, so a PASS needs
        # ok ≥ 80% of ops and the accounting to close (ok+aborted+error
        # == ops). The old error/leak-only gate silently tolerated any
        # ok/aborted split — a stack failing 19% of NORMAL requests as
        # "aborted" summarized clean.
        ops = r.get("ops", 0)
        ok, aborted, errors = r.get("ok", 0), r.get("aborted", 0), r.get("error", 1)
        accounted = ok + aborted + errors == ops and ops > 0
        row("soak",
            errors == 0 and r.get("leaks", 1) == 0 and accounted
            and ok >= 0.8 * ops,
            f"ops {ops}, ok {ok}, aborted {aborted}, errors {errors}, "
            f"leaks {r.get('leaks')}, {r.get('ok_per_sec')}/s"
            + ("" if accounted else " [MIX UNACCOUNTED]"))
    else:
        row("soak", None, "no fresh record")

    for informational in ("roofline", "gang_ab", "latency_mesh1", "latency_base",
                          "latency_8x", "latency_base_x2ladder", "overhead",
                          "chaos_crossproc", "throughput_sweep"):
        r = res(step(informational))
        if r:
            keep = {k: v for k, v in r.items()
                    if isinstance(v, (int, float, str)) and k != "bench"}
            row(informational, True, json.dumps(keep)[:140])
        else:
            row(informational, None, "no fresh record")

    width = max(len(n) for n, _, _ in rows)
    failures = 0
    for name, status, detail in rows:
        print(f"{name:<{width}}  {status:<6}  {detail}")
        failures += status == "FAIL"
    return 1 if failures or invalidations_unreadable else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
