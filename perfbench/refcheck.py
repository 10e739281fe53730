"""The plain reference: Nano proof of work checked with hashlib alone.

A work ``w`` (16 hex digits, the nonce big-endian) is valid for block hash
``h`` at threshold ``d`` when the 8-byte blake2b digest of
``nonce_le || h``, read little-endian, is at least ``d``
(docs.nano.org, Integration guides, Work generation). Nothing of
``tpu_dpow`` is imported here.
"""

from __future__ import annotations

import hashlib

TWO64 = 1 << 64


def work_value(block_hash: str, work: str) -> int:
    nonce = bytes.fromhex(work)
    if len(nonce) != 8:
        raise ValueError(f"work {work!r} is not 8 bytes")
    digest = hashlib.blake2b(nonce[::-1] + bytes.fromhex(block_hash), digest_size=8).digest()
    return int.from_bytes(digest, "little")


def work_valid(block_hash: str, work: str, threshold: int) -> bool:
    try:
        return work_value(block_hash, work) >= threshold
    except (ValueError, TypeError):
        return False


def expected_effort(threshold: int) -> float:
    """Mean nonces a search needs at ``threshold``: 2^64 / (2^64 - d)."""
    return TWO64 / (TWO64 - threshold)


def eased(threshold: int, bits: int = 1) -> int:
    """The threshold ``bits`` bits easier: (2^64 - d) * 2^bits."""
    return TWO64 - ((TWO64 - threshold) << bits)
