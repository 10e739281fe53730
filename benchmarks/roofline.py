"""VPU roofline for the Blake2b kernel: ops/hash, implied ceiling, MFU.

VERDICT r4 item 5: "1.107 GH/s beats the 1e9 target by 11%, but nobody has
shown what the chip's u32-op ceiling implies." This derives all three terms
from first principles and prints one JSON line:

  ops/hash   — counted from the TRACED kernel dataflow, not hand arithmetic:
               ``pow_meets_difficulty(unroll=True)`` (the exact hot-loop body
               the Pallas kernel inlines, final-round-pruned compress_h0) is
               traced to a jaxpr with a (8, 128)-tile nonce and SCALAR
               message/difficulty words. Every eqn whose output carries the
               tile shape is one VPU lane-op per nonce; eqns that stay scalar
               are nonce-invariant (Mosaic/XLA hoist them out of the tile
               loop), and the shape split accounts for that hoisting by
               construction. Splat broadcasts of scalars into the tile are
               counted separately (lane splat is ~free on the VPU) and
               reported, not added.
  VPU ops/s  — v5e ships no published VPU number, so it is derived from the
               published MXU peak: 197 bf16 TFLOP/s = 4 MXUs x 128x128 MACs
               x 2 flops x clock  =>  clock ~= 1.503 GHz. The VPU is an
               (8, 128) grid with 4 ALUs per cell (one u32 op each per
               cycle): 1024 x 4 x 1.503e9 ~= 6.16e12 u32 ops/s.
  MFU        — measured H/s x ops/hash / VPU ops/s, with measured H/s read
               from BENCH_latency.json's headline record (platform tpu only).

Also prints the ceiling expressed as H/s (ceiling_hs = VPU ops/s divided by
ops/hash) so "how much faster could ANY Blake2b kernel go on this chip"
has a number. Per-tile overhead outside the traced body (nonce-offset adds,
the min-reduce, the every-8-tiles early-exit cond) is ~10 vector ops per
1024-nonce tile — well under 1% of ops/hash — and is noted, not modeled.

Usage: python benchmarks/roofline.py [--json-only]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# v5e TensorCore clock, derived from the published bf16 peak (197 TFLOP/s)
# and MXU geometry (4 MXUs of 128x128, 2 flops/MAC):
#   clock = 197e12 / (4 * 128*128 * 2) ~= 1.503 GHz
V5E_BF16_TFLOPS = 197e12
V5E_MXUS = 4
V5E_CLOCK_HZ = V5E_BF16_TFLOPS / (V5E_MXUS * 128 * 128 * 2)
# VPU: (8, 128) cells x 4 ALUs, 1 u32 op per ALU per cycle.
V5E_VPU_LANES = 8 * 128
V5E_VPU_ALUS_PER_LANE = 4
V5E_VPU_OPS_PER_SEC = V5E_VPU_LANES * V5E_VPU_ALUS_PER_LANE * V5E_CLOCK_HZ

TILE = (8, 128)


def count_ops_per_hash() -> dict:
    """Trace the kernel hot-loop body and bucket its eqns by shape."""
    import jax
    import jax.numpy as jnp

    from tpu_dpow.ops import blake2b

    def body(nlo, nhi, m0, m1, m2, m3, m4, m5, m6, m7, dlo, dhi):
        return blake2b.pow_meets_difficulty(
            (nlo, nhi), [m0, m1, m2, m3, m4, m5, m6, m7], (dlo, dhi),
            unroll=True,
        )

    tile = jax.ShapeDtypeStruct(TILE, jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.uint32)
    jaxpr = jax.make_jaxpr(body)(tile, tile, *([scalar] * 10))

    vector = 0        # one VPU lane-op per nonce
    splats = 0        # scalar -> tile broadcasts (lane splat, ~free)
    converts = 0      # tile-shaped dtype casts (carry bool -> u32: a select)
    scalar_ops = 0    # nonce-invariant: hoisted out of the tile loop
    for eqn in jaxpr.jaxpr.eqns:
        out_shapes = [getattr(v.aval, "shape", ()) for v in eqn.outvars]
        is_tile = any(s == TILE for s in out_shapes)
        name = eqn.primitive.name
        if not is_tile:
            scalar_ops += 1
        elif name == "broadcast_in_dim":
            splats += 1
        elif name == "convert_element_type":
            converts += 1
        else:
            vector += 1
    return {
        "ops_per_hash": vector + converts,
        "ops_per_hash_ex_casts": vector,
        "tile_splats": splats,
        "hoisted_scalar_ops": scalar_ops,
    }


def measured_headline_hs() -> "tuple[float, str | None] | tuple[None, None]":
    """Latest trustworthy TPU headline: (H/s, mark) or (None, None).

    Reads the same artifact the enclosing capture writes (the
    TPU_DPOW_BENCH_OUT override capture_evidence honors, else the repo
    file) and applies the same trust rules the summarizer does: a record
    whose rc isn't 0 is a crash whose partial result the grader refuses,
    and benchmarks/invalidated.json disavowals are honored — an MFU
    derived from either would be exactly the false evidence those
    mechanisms exist to block.
    """
    path = (os.environ.get("TPU_DPOW_BENCH_OUT")
            or os.path.join(REPO, "BENCH_latency.json"))
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None, None
    rec = data.get("headline")
    if not isinstance(rec, dict) or rec.get("rc", 0) != 0:
        return None, None
    import summarize_capture as sc

    try:
        invalidations = sc.load_invalidations()
    except sc.InvalidationsUnreadable:
        # Fail closed with the summarizer: an unreadable disavowal list
        # means this headline cannot be proven trustworthy.
        return None, None
    if sc.invalidation_reason("headline", rec, invalidations):
        return None, None
    r = sc.res(rec)
    if r.get("platform") == "tpu" and r.get("value"):
        return float(r["value"]), rec.get("mark")
    return None, None


def launch_overhead_model(
    *,
    window_scan_ms: float = 30.0,
    chunked_windows: int = 16,
    persistent_windows: int = 256,
    poll_steps: int = 8,
    poll_cost_local_ms: float = 0.05,
) -> dict:
    """Per-launch overhead model: the fraction of wall time the device
    actually scans, per run mode (ISSUE 10).

    Chunked mode pays one launch overhead (dispatch + readback round trip)
    per ``chunked_windows`` windows of scan; persistent mode pays it per
    ``persistent_windows`` windows plus one control-poll host touch every
    ``poll_steps`` windows (ops/control.py io_callback, a host touch).
    Device utilization bounds achievable MFU: measured kernel MFU x
    utilization is what the engine can sustain end to end. The inputs (30
    ms scan per window at the default TPU geometry, 8 ms launch overhead)
    are assumptions — a MODEL, labeled as such, not measured on the chip.
    """
    out = {
        "window_scan_ms": window_scan_ms,
        "chunked_windows": chunked_windows,
        "persistent_windows": persistent_windows,
        "poll_steps": poll_steps,
        "derived": True,
    }
    for regime, overhead_ms, poll_ms in (
        ("local", 8.0, poll_cost_local_ms),
    ):
        scan_c = chunked_windows * window_scan_ms
        util_c = scan_c / (scan_c + overhead_ms)
        scan_p = persistent_windows * window_scan_ms
        polls = persistent_windows / max(1, poll_steps)
        util_p = scan_p / (scan_p + overhead_ms + polls * poll_ms)
        out[regime] = {
            "launch_overhead_ms": overhead_ms,
            "poll_cost_ms": poll_ms,
            "chunked_utilization": round(util_c, 4),
            "persistent_utilization": round(util_p, 4),
            "utilization_gain": round(util_p / util_c, 4),
        }
    return out


def main() -> None:
    p = argparse.ArgumentParser("VPU roofline + MFU for the Blake2b kernel")
    p.add_argument("--hs", type=float, default=None,
                   help="override measured H/s (default: BENCH_latency.json "
                   "headline, tpu records only)")
    args = p.parse_args()

    counts = count_ops_per_hash()
    ops = counts["ops_per_hash"]
    ceiling_hs = V5E_VPU_OPS_PER_SEC / ops
    out = {
        "bench": "vpu_roofline",
        **counts,
        "v5e_clock_ghz": round(V5E_CLOCK_HZ / 1e9, 4),
        "vpu_ops_per_sec": round(V5E_VPU_OPS_PER_SEC, 0),
        "ceiling_hs": round(ceiling_hs, 0),
        "ceiling_ghs": round(ceiling_hs / 1e9, 3),
        # The ceiling (and the MFU computed from it below) rests on
        # unverifiable hardware assumptions — clock back-derived from the
        # published MXU peak, (8,128)x4 ALU geometry, one u32 op per ALU
        # per cycle — so these fields are ESTIMATES, not measurements
        # (ADVICE r5). measured_hs alone is a measurement.
        "derived": True,
        "uncertainty": "ceiling_hs/mfu are estimates: clock and VPU ALU "
                       "geometry are derived, not published; treat as an "
                       "order-of-magnitude bound, not a measured fact",
    }
    if args.hs is not None:
        hs, mark = args.hs, "override"
    else:
        hs, mark = measured_headline_hs()
    if hs:
        out["measured_hs"] = hs
        out["measured_mark"] = mark
        out["implied_u32_ops_per_sec"] = round(hs * ops, 0)
        out["mfu"] = round(hs * ops / V5E_VPU_OPS_PER_SEC, 4)
    else:
        out["measured_hs"] = None
        out["note"] = "no tpu headline record; pass --hs to compute MFU"
    # Engine-level MFU = kernel MFU x device utilization; the model prices
    # the chunked-vs-persistent launch structure (ISSUE 10 — the remaining
    # lever on the r4 79% -> >90% MFU target).
    out["launch_overhead_model"] = launch_overhead_model()
    if out.get("mfu"):
        local = out["launch_overhead_model"]["local"]
        out["engine_mfu_chunked"] = round(
            out["mfu"] * local["chunked_utilization"], 4
        )
        out["engine_mfu_persistent"] = round(
            out["mfu"] * local["persistent_utilization"], 4
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
