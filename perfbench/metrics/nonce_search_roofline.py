"""nonce_search_roofline: the nonce-search kernel's share of its roofline
(device trace for the time, program counter for the work), in %.

    nonces evaluated x OPS_PER_HASH / (kernel device time x VPU u32 peak)

The kernel is pure VPU compute (no HBM traffic in its steady state), so the
VPU's u32 operation rate bounds it; OPS_PER_HASH and the peak are frozen in
roofline.py and peaks.json, so the count does not depend on how a kernel
does the work.

Time: the device time of the kernel's events (the Pallas custom call of the
search, found by name) that END inside the traced window, whole. Work: the
window's growth of ``dpow_engine_hashes_total`` -- every row of every launch
applied in the window, scanned up to its hit or to the end of its span. A
launch is applied on the host just after its kernel ends, so the two count
the same launches. Padding rows (difficulty 0) stop at their first group of
tiles; each adds ``group x sublanes x 128`` nonces, counted from the batch in
the kernel's result shape less the live rows the engine packed.
"""

import re

import promtext
import roofline

KERNEL = re.compile(r"search.*custom_call_target=\"tpu_custom_call\"")
BATCH = re.compile(r"^%?[\w.\-]+ = u32\[(\d+)")


def kernel_events(trace) -> list:
    lo, hi = trace["window_ns"]
    return [(name, s, e) for d in trace["devices"] for name, s, e in d["events"]
            if KERNEL.search(name) and lo <= e <= hi]


def read(w, name):
    t = w.trace
    if not t or not t.get("devices"):
        return None
    evs = kernel_events(t)
    secs = sum(e - s for _n, s, e in evs) * 1e-9 / len(t["devices"])
    nonces = promtext.delta(w.engine[0], w.engine[1], "dpow_engine_hashes_total")
    if secs <= 0 or nonces <= 0:
        return None
    rows = sum(int(m.group(1)) for n, _s, _e in evs if (m := BATCH.match(n)))
    live = promtext.delta(w.engine[0], w.engine[1], "dpow_engine_batch_occupancy_sum")
    g = w.extra.get("geometry", {})
    pads = max(0.0, rows - live)
    nonces += pads * g.get("group", 0) * g.get("sublanes", 0) * 128
    return 100.0 * nonces * roofline.OPS_PER_HASH / (secs * roofline.peak(w.device_kind))
