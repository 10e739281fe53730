"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. Its planes are
the host (``/host:CPU``, one line per thread) and each device
(``/device:TPU:<n>``, with a line of XLA ops). The measured window is the
host span named ``perfbench_window`` that the harness opens and closes at
the window's edges.

* busy: the union of the device's op intervals inside the window, averaged
  over the devices used; idle share = 1 - busy / window.
* ops: each op name's device time inside the window.
* gaps: each stretch of the window in which the device ran nothing,
  labelled by the host event that overlaps it most (what the host was doing
  while the chip waited).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "perfbench_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops",)
IDLE_LABEL = "host: nothing traced"
SHORT_GAP_NS = 10_000       # gaps shorter than this are pooled, not labelled
LOOKBACK_NS = 2e9           # host events that began this long before a gap may cover it


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


# -- interval arithmetic ---------------------------------------------------


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# -- reading the planes ----------------------------------------------------


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


def window_of(pd) -> tuple:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW_SPAN:
                    return s, e
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def device_ops(pd) -> list:
    """[(plane name, [(op name, start_ns, end_ns), ...])] per device plane."""
    out = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name in OP_LINES:
                evs.extend(_events(line))
        out.append((plane.name, evs))
    return out


def host_events(pd) -> list:
    """[(thread name, event name, start_ns, end_ns)] of every host line."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if e > s and name != WINDOW_SPAN:
                    out.append((line.name, name, s, e))
    return out


class HostIndex:
    """Host events sorted by start, for labelling gaps."""

    def __init__(self, host: list):
        self.events = sorted(host, key=lambda h: h[2])
        self.starts = [h[2] for h in self.events]

    def label(self, s: float, e: float) -> str:
        if e - s < SHORT_GAP_NS:
            return f"gaps under {SHORT_GAP_NS // 1000} us"
        best, best_overlap = IDLE_LABEL, 0.0
        i = bisect.bisect_left(self.starts, s - LOOKBACK_NS)
        j = bisect.bisect_left(self.starts, e)
        for _thread, name, hs, he in self.events[i:j]:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name[:120], overlap
        return best


_HLO = re.compile(r"^%?([\w.\-]+) = (\S+)")


def short_name(op: str) -> str:
    """An XLA op's trace name is its whole HLO instruction; keep the op's
    name and result shape (``pallas_search_chunk_batch.1 u32[16,1]...``)."""
    m = _HLO.match(op)
    return f"{m.group(1)} {m.group(2)}" if m else op[:120]


def reduce(pd, top: int = 10) -> dict:
    lo, hi = window_of(pd)
    devices = device_ops(pd)
    if not devices:
        return {"window_s": (hi - lo) * 1e-9, "devices": []}
    host = HostIndex(host_events(pd))
    per_dev = []
    for plane, evs in devices:
        spans = [(s, e) for _n, s, e in evs]
        ops = {}
        for name, s, e in evs:
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                key = short_name(name)
                ops[key] = ops.get(key, 0.0) + d * 1e-9
        idle = {}
        for gs, ge in gaps(spans, lo, hi):
            label = host.label(gs, ge)
            idle[label] = idle.get(label, 0.0) + (ge - gs) * 1e-9
        per_dev.append({
            "plane": plane,
            "busy_s": covered(spans, lo, hi) * 1e-9,
            "ops": ops,
            "idle": idle,
            "events": [(n, s, e) for n, s, e in evs if e > lo and s < hi],
        })
    ops, idle = {}, {}
    for d in per_dev:
        for k, v in d["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / len(per_dev)
        for k, v in d["idle"].items():
            idle[k] = idle.get(k, 0.0) + v / len(per_dev)
    return {
        "window_s": (hi - lo) * 1e-9,
        "window_ns": (lo, hi),
        "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "devices": per_dev,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda x: -x[1])[:top],
            "idle_gaps": sorted(([k, v] for k, v in idle.items()), key=lambda x: -x[1])[:top],
        },
    }


def summary(pd, per_line: int = 8) -> dict:
    """Planes, lines and their commonest event names: for looking at a
    trace by hand before trusting the reduction."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            counts = {}
            n = 0
            for name, s, e in _events(line):
                counts[name] = counts.get(name, 0) + 1
                n += 1
            top = sorted(counts.items(), key=lambda x: -x[1])[:per_line]
            lines.append({"line": line.name, "events": n, "top": top})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
