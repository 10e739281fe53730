"""Readings of the comparison that decides ``correct``: the program's and the
control's, on one set-up per side.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \
        --control_seeds 21,22,23 --seconds 51

The program side runs the cell as ``run.py`` does, window after window on
one set-up, and prints each window's numbers compared (``invalid_works``,
``unanswered``): the lower readings. The control side runs the same program
and traffic, but every request asks for a threshold one bit easier than its
class states (half the effort: the nearest step below the stated
guarantee), with the server's ``--max_multiplier`` opened to 16 so that it
admits them; the reference still judges the class threshold. About half of
the control's works fall below it, so the control must come out not correct:
its smallest reading is the upper one. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

CONTROL_EASE_BITS = 1
CONTROL_SERVER_FLAGS = ("--max_multiplier", "16")


def reading(w: harness.WindowData, side: str) -> dict:
    chk = harness.checks(w)
    works = sum(1 for r in w.records if r["status"] == "work")
    return {"side": side, "seed": w.seed, "correct": harness.is_correct(chk),
            "works": works, "requests": len(w.records),
            **{k: v["value"] for k, v in chk.items()}}


async def readings(session: harness.Session, seeds, seconds: float, side: str) -> list:
    out = []
    try:
        await session.start()
        for seed in seeds:
            r = reading(await session.window(seed, seconds), side)
            harness.log(json.dumps(r))
            out.append(r)
    finally:
        await session.close()
    return out


def control_session(cell) -> harness.Session:
    return harness.Session(cell, ease_bits=CONTROL_EASE_BITS, server_extra=CONTROL_SERVER_FLAGS)


def main(argv=None) -> int:
    import catalog
    import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="", help="program seeds, comma-separated")
    p.add_argument("--control_seeds", default="", help="control seeds, comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    run.prepare()
    cell = catalog.find_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = []
    if seeds:
        program = harness.Session(cell)
        program.check_chip()
        out += asyncio.run(readings(program, seeds, args.seconds, "program"))
    if cseeds:
        control = control_session(cell)
        control.check_chip()
        out += asyncio.run(readings(control, cseeds, args.seconds, "control"))
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
