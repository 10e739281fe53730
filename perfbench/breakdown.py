"""One traced run of a cell, broken down layer by layer (not a benchmark run).

    python3 perfbench/breakdown.py --workload <cell> --seed <n> --seconds <s> \
        --trace_dir <dir> [--out <file>]

Runs the cell as ``run.py --trace 1`` does and prints the same result line,
with the cell's end-to-end metrics added under ``end_to_end`` and two
breakdowns:

* ``stages``: the mean of every request stage over the window, server side
  and worker side (``dpow_request_stage_seconds``), the server chain
  ``accept`` .. ``reply`` summed, and the mean of the server's
  ``dpow_server_request_seconds`` per work type, which that chain should
  add up to;
* ``launch_cycle``: the device's idle seconds in the window by the host
  span that labels each gap (``xplane.reduce``), and the loop lag: device
  idle after the head launch's ``dpow.launch.readback`` ended and before
  its ``dpow.engine.apply`` began.

The raw trace and its summary stay in ``--trace_dir``.
"""

from __future__ import annotations

import argparse
import asyncio
import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import stages  # noqa: E402

SERVER_CHAIN = ("accept", "queue", "publish", "result_in", "winner", "resolve", "reply")
SERVER_STAGES = SERVER_CHAIN + ("cancel",)
WORKER_STAGES = ("dispatch", "submit", "pack", "device", "result")
WORK_TYPES = ("ondemand", "precache", "unresolved")


def stage_breakdown(w: harness.WindowData) -> dict:
    return {
        "server_ms": {s: stages.mean_ms(w.server, s) for s in SERVER_STAGES},
        "worker_ms": {s: stages.mean_ms(w.engine, s) for s in WORKER_STAGES},
        "server_chain_ms": stages.sum_ms(w.server, SERVER_CHAIN),
        "request_ms": {t: stages.series_mean_ms(w.server, "dpow_server_request_seconds",
                                                "work_type", t) for t in WORK_TYPES},
    }


def _overlap(intervals: list, starts: list, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by sorted disjoint ``intervals``."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    j = bisect.bisect_left(starts, hi)
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals[i:j])


def launch_cycle(trace_dir: str, reduced: dict) -> dict:
    """The device's idle in the window by gap label, and the loop lag."""
    import xplane

    pd = xplane.load(xplane.find_xplane(trace_dir))
    lo, hi = xplane.window_of(pd)
    devices = xplane.device_ops(pd)
    if not devices:
        return {}
    spans = [(s, e) for _n, s, e in devices[0][1]]
    idle_gaps = xplane.gaps(spans, lo, hi)
    host = xplane.host_events(pd)
    readback_ends = sorted(e for _t, n, _s, e in host if n == "dpow.launch.readback")
    apply_starts = sorted(s for _t, n, s, _e in host if n == "dpow.engine.apply")
    # Results apply in launch order: each apply is paired with the earliest
    # readback not yet paired that ended before it began (the head launch's).
    lags, i = [], 0
    for a in apply_starts:
        if i < len(readback_ends) and readback_ends[i] <= a:
            if lo <= a < hi:
                lags.append((readback_ends[i], a))
            i += 1
    idle = reduced["devices"][0]["idle"]
    idle_s = sum(idle.values())
    gap_starts = [s for s, _e in idle_gaps]
    lag_idle = sum(_overlap(idle_gaps, gap_starts, s, e) for s, e in lags)
    return {
        "window_s": reduced["window_s"],
        "idle_s": idle_s,
        "idle_by_label_s": sorted(([k, v] for k, v in idle.items()), key=lambda x: -x[1]),
        "with_work_idle_s": idle_s - idle.get("dpow.engine.idle", 0.0),
        "applies": sum(1 for a in apply_starts if lo <= a < hi),
        "loop_lag": {
            "pairs": len(lags),
            "mean_ms": 1e-6 * sum(e - s for s, e in lags) / len(lags) if lags else None,
            "device_idle_s": lag_idle * 1e-9,
        },
    }


def main(argv=None) -> int:
    import catalog
    import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace_dir", required=True)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    try:
        run.prepare()
        cell = catalog.find_cell(args.workload)
        session = harness.Session(cell)
        session.check_chip()
        os.makedirs(args.trace_dir, exist_ok=True)
        w = asyncio.run(run.measure(session, args.seed, args.seconds, args.trace_dir))
        run.reduce_trace(w, args.trace_dir, keep=True)
    except (harness.BenchError, catalog.CatalogError) as e:
        print(f"breakdown: {e}", file=sys.stderr)
        return 1
    res = run.result(w, cell, True)
    res["end_to_end"] = harness.read_metrics(w, cell.end_to_end)
    res["stages"] = stage_breakdown(w)
    if w.trace and w.trace.get("devices"):
        res["launch_cycle"] = launch_cycle(args.trace_dir, w.trace)
    line = json.dumps(res)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
