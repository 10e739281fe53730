"""The yardstick of the nonce-search kernel: operations per hash and the peak.

OPS_PER_HASH is frozen here. It is the count of tile-shaped (per-nonce) VPU
operations in the traced hot-loop body of the kernel as it stood when the
benchmark was defined (``tpu_dpow.ops.blake2b.pow_meets_difficulty`` with
``unroll=True``, an (8, 128) tile of nonces and scalar message and
threshold words): 3,989 u32 lane operations, not counting the 414
bool-to-u32 carry casts (4,403 with them). The lower count is frozen, so a
kernel that drops the casts cannot read over 100% of the roofline; a kernel
that does the same work some other way is measured against the same count.
Splats of scalars into the tile (0 here) and nonce-invariant scalar work
(109 ops, hoisted out of the tile loop) are not counted. The per-tile
overhead outside the body (offset adds, the min-reduction, the early-exit
cond: ~10 ops per 1,024 nonces) is not counted either.

``count_ops_per_hash`` is the counting method, copied from
``benchmarks/roofline.py``; ``perfbench/tests/test_yardstick.py`` runs it on
today's body, so drift in the kernel shows as a failed test, never as a
silently moved yardstick.

The peak is per ``device_kind`` in ``peaks.json``. A device missing there is
an error, not a default.
"""

from __future__ import annotations

import json
import os

OPS_PER_HASH = 3989
TILE = (8, 128)
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str, key: str = "vpu_u32_ops_per_s") -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"device_kind {device_kind!r} not in {PEAKS}")
    return float(table[device_kind][key])


def count_ops_per_hash() -> dict:
    """Trace the kernel's hot-loop body and bucket its equations by shape
    (imports the program: only the yardstick's own test calls this)."""
    import jax
    import jax.numpy as jnp

    from tpu_dpow.ops import blake2b

    def body(nlo, nhi, m0, m1, m2, m3, m4, m5, m6, m7, dlo, dhi):
        return blake2b.pow_meets_difficulty(
            (nlo, nhi), [m0, m1, m2, m3, m4, m5, m6, m7], (dlo, dhi), unroll=True)

    tile = jax.ShapeDtypeStruct(TILE, jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.uint32)
    jaxpr = jax.make_jaxpr(body)(tile, tile, *([scalar] * 10))
    vector = splats = converts = scalar_ops = 0
    for eqn in jaxpr.jaxpr.eqns:
        is_tile = any(getattr(v.aval, "shape", ()) == TILE for v in eqn.outvars)
        name = eqn.primitive.name
        if not is_tile:
            scalar_ops += 1
        elif name == "broadcast_in_dim":
            splats += 1
        elif name == "convert_element_type":
            converts += 1
        else:
            vector += 1
    return {"ops_per_hash_ex_casts": vector, "carry_casts": converts,
            "tile_splats": splats, "hoisted_scalar_ops": scalar_ops}
