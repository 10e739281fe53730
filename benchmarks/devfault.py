#!/usr/bin/env python
"""Time-to-evacuate for the device fault domains (docs/resilience.md).

Measures the REAL-clock latency from the watchdog declaring a wedged fan
device suspect to the first control poll of the recovery launch on a
healthy device — i.e. how long a production TPU preemption pins its rows
BEYOND the configured suspect deadline. (The suspect deadline itself is
policy, not overhead: `--device_suspect_after` trades false positives
against stranding time, and the measured tail here is the mechanism's
own cost — eject, re-partition, re-dispatch, first poll.)

Per trial: a fresh 8-device persistent fan engine (the acceptance-test
geometry) gets one unreachable request partitioned across the fan; chaos
(FaultyDevice) wedges device 3 at its control poll; the watchdog
(SystemClock, sub-second deadline for bench turnaround) declares it
suspect, evacuates the dead shard's remainder onto the 7 healthy
devices, and the stamp of the recovery launch's first poll closes the
interval. Box-calibrated knobs: the span is short (persistent_steps=8)
so healthy devices FINISH and are accounted by their final poll block
instead of time-slicing 8 virtual devices over 2 cores with poll gaps
wider than the deadline, and the default --suspect_after (2 s) sits
above this box's worst-case healthy poll gap — both are measurement
hygiene, not mechanism requirements.

    JAX_PLATFORMS=cpu python benchmarks/devfault.py --n 10 --out /path/to/out.json

CPU note: virtual CPU devices share the host's cores; the measured path
(watchdog sweep -> eject -> re-partition -> dispatch -> first poll) is
host-side bookkeeping + one XLA dispatch either way, so the CPU capture
is representative of the mechanism, not of TPU compile/dispatch times.
"""

import os
import sys

# The fan needs >= 2 devices: force virtual CPU devices BEFORE any jax
# import (the tests/conftest.py trick), unless a real multi-chip platform
# is configured.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import _bootstrap  # noqa: F401,E402

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import time  # noqa: E402


UNREACH = (1 << 64) - 2


class TrialSpoiled(RuntimeError):
    """Environment noise, not mechanism failure: on a 2-core box running 8
    virtual devices, scheduling stalls can push a HEALTHY device's polls
    past the deadline too — the cascade quarantines everyone (safe: the
    engine fails fast and probes re-admit, but there is no degraded-width
    recovery launch left to stamp). Spoiled trials are retried and
    counted in the capture."""


async def one_trial(suspect_after: float, probe_interval: float) -> dict:
    import numpy as np

    from tpu_dpow import obs
    from tpu_dpow.backend import DevicesExhausted, WorkCancelled
    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.chaos import FaultyDevice
    from tpu_dpow.models import WorkRequest

    obs.reset()
    b = JaxWorkBackend(
        kernel="xla", sublanes=8, iters=8, devices=8, max_batch=1,
        run_mode="persistent", persistent_steps=8, control_poll_steps=1,
        pipeline=1, device_suspect_after=suspect_after,
        device_probe_interval=probe_interval,
    )
    await b.setup()
    # Warm the full-fan AND degraded-width (recovery) launch shapes so the
    # measurement is the evacuation mechanism, not XLA compile (in the
    # engine itself a cold recovery compile is covered by the watchdog's
    # first-poll grace window, not by the suspect deadline).
    from tpu_dpow.ops import search as _search

    probe = _search.pack_params(bytes(32), 1, base=0)
    healthy = tuple(d for i, d in enumerate(b.fan) if i != 3)
    for devs in (None, healthy):
        await b._submit_launch(
            np.stack([probe]), b.persistent_steps, devices=devs
        )
    stamps = {}
    declare = b._declare_suspect

    def stamped_declare(d):
        stamps.setdefault("suspect", time.monotonic())
        declare(d)

    b._declare_suspect = stamped_declare
    fd = FaultyDevice()
    fd.install()
    try:
        fd.hang_at_poll(3, 2)
        h = os.urandom(32).hex().upper()
        task = asyncio.ensure_future(
            b.generate(WorkRequest(h, UNREACH, nonce_range=(1 << 40, 1 << 30)))
        )
        deadline = time.monotonic() + 60
        while ("poll", 3, 2) not in fd.events:
            assert time.monotonic() < deadline, "device never wedged"
            await asyncio.sleep(0.002)
        wedged_rec = next(r for r in b._inflight if r.control is not None)
        # the watchdog fires on the real clock; wait for the RECOVERY
        # launch (degraded width: fan_map == [0]) to take its first poll
        recovery_poll = None
        while recovery_poll is None:
            if b._devices_exhausted:
                raise TrialSpoiled("false-positive cascade quarantined all")
            assert time.monotonic() < deadline, "no recovery launch polled"
            degraded = [d for d in range(8) if d != 3]
            for rec in list(b._inflight):
                if rec.control is not None and rec.fan_map == degraded:
                    stamps_t = [
                        rec.control.last_poll(s)[0] for s in range(7)
                    ]
                    seen = [t for t in stamps_t if t is not None]
                    if seen:
                        recovery_poll = min(seen)
                        break
            await asyncio.sleep(0.001)
        evac_ms = (recovery_poll - stamps["suspect"]) * 1e3
        await b.cancel(h)
        try:
            await task
        except (WorkCancelled, DevicesExhausted):
            pass
        fd.release(3)
        drain = time.monotonic() + 30
        while not wedged_rec.thread_done.is_set() and time.monotonic() < drain:
            await asyncio.sleep(0.002)
    finally:
        fd.uninstall()
        await b.close()
    return {"evacuate_ms": evac_ms}


async def run(n: int, suspect_after: float) -> dict:
    import jax

    lat = []
    spoiled = 0
    for i in range(n):
        for _attempt in range(4):
            try:
                t = await one_trial(suspect_after, probe_interval=30.0)
                break
            except TrialSpoiled as e:
                spoiled += 1
                print(f"# trial {i + 1}/{n} spoiled ({e}); retrying")
        else:
            raise RuntimeError("4 consecutive spoiled trials — box too noisy")
        lat.append(t["evacuate_ms"])
        print(f"# trial {i + 1}/{n}: suspect->recovery-poll "
              f"{t['evacuate_ms']:.1f} ms")
    lat.sort()
    platform = jax.devices()[0].platform
    return {
        "mark": "r12",
        "platform": platform,
        "cpu_fallback": platform != "tpu",
        "issue": "ISSUE 12: device fault domains — hung-launch watchdog, "
                 "range evacuation, quarantine",
        "cmd": f"JAX_PLATFORMS=cpu python benchmarks/devfault.py --n {n} "
               f"--suspect_after {suspect_after}",
        "config": {
            "devices": 8,
            "run_mode": "persistent",
            "control_poll_steps": 1,
            "device_suspect_after_s": suspect_after,
        },
        "time_to_evacuate_ms": {
            "what": "watchdog suspect declaration -> first control poll of "
                    "the recovery launch on a healthy device (the "
                    "mechanism's own cost: eject + kill-fence + "
                    "re-partition + dispatch + poll; excludes the "
                    "configured suspect deadline, which is policy)",
            "p50": statistics.median(lat),
            "p95": lat[min(len(lat) - 1, int(0.95 * len(lat)))],
            "min": lat[0],
            "max": lat[-1],
            "trials": len(lat),
            "spoiled_trials_retried": spoiled,
            "spoiled_meaning": "8-virtual-devices-on-2-cores scheduling "
                "stalls occasionally push a HEALTHY device past the "
                "deadline too; the cascade quarantines everything (safe "
                "fail-fast, but no degraded launch left to stamp) — an "
                "oversubscription artifact real multi-chip hosts do not "
                "share",
        },
        "note": "CPU capture: virtual CPU fan, geometry sublanes=8 "
                "iters=8 (window 8192). The measured path is host "
                "bookkeeping + one XLA dispatch + one poll; on a real chip "
                "the dispatch leg grows by the launch overhead (not "
                "measured).",
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=10, help="trials")
    ap.add_argument("--suspect_after", type=float, default=2.0,
                    help="watchdog suspect deadline (s) for the bench")
    ap.add_argument("--out", default=None, help="write the capture here")
    ns = ap.parse_args()
    result = asyncio.run(run(ns.n, ns.suspect_after))
    text = json.dumps(result, indent=1)
    print(text)
    if ns.out:
        with open(ns.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
