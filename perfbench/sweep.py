"""Find the knee of an open-loop cell: one set-up, a ladder of offered rates.

    python3 perfbench/sweep.py --workload v21-receive --rates 10,20,30 --seconds 20

Each rung replaces the ``rate`` of the cell's open-loop part(s) and measures
one window on the same server and worker. Per rung it prints the offered
rate, the judged requests completed per second, p50/p95 from the intended
send, and how many judged requests were still waiting when the window
closed (a backlog that grows with the rung means the rate is past the
knee). Not a benchmark run: it picks the rate that a cell's traffic file
then fixes as a number.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def with_rate(traffic: dict, rate: float) -> dict:
    t = copy.deepcopy(traffic)
    for part in t.get("parts", [t]):
        if part["kind"] == "open_poisson":
            part["rate"] = rate
    return t


def rung(w: harness.WindowData, rate: float) -> dict:
    import latencyof
    import percentile

    judged = w.judged()
    done = [r for r in judged if r["done"] is not None and w.valid(r)]
    waiting = sum(1 for r in judged if r["done"] is None or r["done"] > w.t1)
    xs = latencyof.samples_ms(w)
    others = {}
    for r in w.completed_in_window():
        if not r["judged"]:
            others[r["cls"]] = others.get(r["cls"], 0) + 1
    return {
        "rate": rate, "judged": len(judged), "valid": len(done),
        "completed_per_s": sum(1 for r in done if r["done"] <= w.t1) / w.seconds,
        "waiting_at_close": waiting,
        "p50_ms": percentile.nearest_rank(xs, 50) if xs else None,
        "p95_ms": percentile.nearest_rank(xs, 95) if xs else None,
        "background_completed": others,
        "lag_max_ms": w.lag_max_s * 1e3,
        "checks": harness.checks(w),
    }


async def sweep(session, rates, seconds, seed):
    out = []
    try:
        await session.start()
        for i, rate in enumerate(rates):
            w = await session.window(seed + i, seconds,
                                     traffic=with_rate(session.cell.traffic, rate), warm_s=3.0)
            r = rung(w, rate)
            harness.log(json.dumps(r))
            out.append(r)
    finally:
        await session.close()
    return out


def main(argv=None) -> int:
    import catalog
    import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    run.prepare()
    session = harness.Session(catalog.find_cell(args.workload))
    session.check_chip()
    rates = [float(x) for x in args.rates.split(",")]
    print(json.dumps({"workload": args.workload,
                      "rungs": asyncio.run(sweep(session, rates, args.seconds, args.seed))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
