"""Run one benchmark cell once and print its result as the last stdout line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from BENCHMARK.json (see perfbench/README.md). With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the window
is traced by the JAX profiler and the result carries its per-layer metrics.
Every run checks every returned work against the hashlib reference and
prints the numbers compared, each beside its limit, as its last stderr
lines and under ``checks`` in the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402  (reads no jax; records the process start)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--trace_dir", default=None,
                   help="keep the raw trace and its summary here (default: a temporary dir)")
    return p.parse_args(argv)


def prepare() -> None:
    """Put the checkout on the path and its compile cache in place, before
    anything imports jax."""
    harness.use_checkout_cache()
    if not os.path.isdir(os.path.join(harness.ROOT, "tpu_dpow")):
        raise harness.BenchError(f"no tpu_dpow package in {harness.ROOT}: nothing to measure")
    if harness.ROOT not in sys.path:
        sys.path.insert(0, harness.ROOT)
    from tpu_dpow.utils import enable_compilation_cache

    enable_compilation_cache()


async def measure(session: harness.Session, seed: int, seconds: float,
                  trace_dir: str | None) -> harness.WindowData:
    """Set up, measure one window, tear down. setup_s is read at the
    window's start."""
    setup = {}
    try:
        await session.start()
        w = await session.window(
            seed, seconds, trace_dir=trace_dir,
            on_t0=lambda: setup.setdefault("s", harness.process_age()))
    finally:
        await session.close()
    w.setup_s = setup["s"]
    return w


def reduce_trace(w: harness.WindowData, trace_dir: str, keep: bool) -> None:
    import xplane

    pd = xplane.load(xplane.find_xplane(trace_dir))
    w.trace = xplane.reduce(pd)
    if keep:
        with open(os.path.join(trace_dir, "summary.json"), "w") as f:
            json.dump({"summary": xplane.summary(pd),
                       "breakdown": w.trace.get("breakdown"),
                       "window_s": w.trace["window_s"], "busy_s": w.trace.get("busy_s"),
                       "kernel_events": sorted({n for d in w.trace["devices"]
                                                for n, _s, _e in d["events"]})}, f, indent=1)


def result(w: harness.WindowData, cell, trace: bool) -> dict:
    chk = harness.checks(w)
    entries = cell.per_layer if trace else cell.end_to_end
    judged = w.judged()
    device = {"platform": w.platform, "kind": w.device_kind, "count": w.device_count,
              "memory_peak_bytes": w.memory_peak_bytes}
    if trace and w.trace and w.trace.get("devices") and w.platform == "tpu":
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    out = {
        "correct": harness.is_correct(chk),
        "attempted": len(judged),
        "failed": sum(1 for r in judged if not w.valid(r)),
        "metrics": harness.read_metrics(w, entries),
        "device": device,
    }
    if trace and w.trace and w.trace.get("breakdown") and w.platform == "tpu":
        out["breakdown"] = w.trace["breakdown"]
    out["summary"] = harness.summarize(w)
    out["checks"] = chk
    return out


def run(args) -> dict:
    """One run; raises BenchError (no result) when it cannot measure."""
    import catalog

    prepare()
    cell = catalog.find_cell(args.workload)
    session = harness.Session(cell)
    session.check_chip()
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        w = asyncio.run(measure(session, args.seed, args.seconds, trace_dir))
        if trace_dir is not None:
            reduce_trace(w, trace_dir, keep=args.trace_dir is not None)
    finally:
        if trace_dir is not None and args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return result(w, cell, bool(args.trace))


def emit(res: dict) -> None:
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res))
    sys.stdout.flush()


def _terminated(signum, frame):
    raise SystemExit(128 + signum)  # unwinds the finally blocks: no orphan child


def main(argv=None) -> int:
    args = parse_args(argv)
    signal.signal(signal.SIGTERM, _terminated)
    import catalog

    try:
        res = run(args)
    except (harness.BenchError, catalog.CatalogError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
