import hashlib
import random

import refcheck


def _solve(block_hash: str, threshold: int) -> str:
    """Brute-force a valid work with hashlib alone (low threshold)."""
    h = bytes.fromhex(block_hash)
    for nonce in range(1 << 20):
        le = nonce.to_bytes(8, "little")
        d = hashlib.blake2b(le + h, digest_size=8).digest()
        if int.from_bytes(d, "little") >= threshold:
            return le[::-1].hex()
    raise AssertionError("no work found")


def test_valid_work_passes_and_a_wrong_nonce_is_rejected():
    rng = random.Random(3)
    threshold = 0xFFF0000000000000
    for _ in range(5):
        block_hash = rng.randbytes(32).hex().upper()
        work = _solve(block_hash, threshold)
        assert refcheck.work_valid(block_hash, work, threshold)
        wrong = f"{int(work, 16) ^ 1:016x}"
        # A flipped nonce is valid only by a 1-in-4096 chance; these seeds
        # are fixed, so it is not.
        assert not refcheck.work_valid(block_hash, wrong, threshold)


def test_work_checked_at_the_threshold_asked_for():
    block_hash = "00" * 32
    work = _solve(block_hash, 0xFF00000000000000)
    value = refcheck.work_value(block_hash, work)
    assert refcheck.work_valid(block_hash, work, value)
    assert not refcheck.work_valid(block_hash, work, value + 1)


def test_malformed_work_is_invalid_not_an_exception():
    assert not refcheck.work_valid("00" * 32, "xyz", 0)
    assert not refcheck.work_valid("00" * 32, "00" * 7, 0)


def test_expected_effort_and_easing():
    assert refcheck.expected_effort(0xFFFFFFC000000000) == 2.0 ** 26
    assert refcheck.expected_effort(0xFFFFFE0000000000) == 2.0 ** 23
    assert refcheck.expected_effort(0xFFFFFFF800000000) == 2.0 ** 29
    assert refcheck.eased(0xFFFFFFC000000000) == 0xFFFFFF8000000000
    assert refcheck.expected_effort(refcheck.eased(0xFFFFFE0000000000)) == 2.0 ** 22
