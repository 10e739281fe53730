"""Kernel benchmark: Blake2b nonce-search throughput on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "H/s", "vs_baseline": N, "platform": ...}

vs_baseline is measured against BASELINE.json's north-star target of
1e9 Blake2b hashes/sec/chip (the reference itself publishes no numbers —
SURVEY.md §6).

The measurement runs in one bounded child process, so a backend that wedges
at start-up cannot hang the caller, and the chip is released when the child
exits. There is no CPU fallback: a run that did not measure on a TPU prints
its failure to stderr and exits non-zero. This number is the raw kernel scan
rate, not a served-path cell; the cell runner that replaces it is ROADMAP A1.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

TARGET_HS = 1e9  # BASELINE.json north_star: >= 1e9 H/s/chip on v5e
CHILD_TIMEOUT = 600  # s: cold compile plus the measured launches

_children = set()  # live measurement children, reaped by the signal handler


# The evidence capture tooling (benchmarks/capture_evidence.py) yields the
# single-client chip while a bare bench run announces itself (shared helpers
# in tpu_dpow.utils); capture-spawned runs (TPU_DPOW_EVIDENCE_CAPTURE set)
# skip the announcement — they ARE the capture.
def _announce_foreign_bench() -> None:
    from tpu_dpow.utils import announce_foreign_chip_user

    announce_foreign_chip_user()


def _clear_foreign_bench() -> None:
    from tpu_dpow.utils import clear_foreign_chip_user

    clear_foreign_chip_user()


def measure(reps: int = 8) -> dict:
    from tpu_dpow.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax

    from tpu_dpow.ops import pallas_kernel, search

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev.platform!r}")

    # Unreachable difficulty => every launch scans its whole window, giving
    # a clean hashes/second measurement (the found path exits *early*, so
    # this is the conservative lower bound on scan rate).
    params = np.stack(
        [search.pack_params(bytes(range(32)), (1 << 64) - 1, 7 << 40)]
    )
    # v5e geometry (benchmarks/throughput.py sweep): a 32x128 tile, 1024
    # inner iterations, 64 sequential windows per dispatch (early-exit check
    # every 8 tiles) — the persistent-kernel shape that amortizes the
    # per-dispatch floor.
    sublanes, iters, nblocks, group = 32, 1024, 64, 8
    chunk = sublanes * 128 * iters * nblocks

    def launch(p):
        return pallas_kernel.pallas_search_chunk_batch(
            p, pallas_kernel.window_count(nblocks, nblocks),
            sublanes=sublanes, iters=iters, nblocks=nblocks, group=group,
        )

    pj = jax.device_put(params, dev)
    np.asarray(launch(pj))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = launch(pj)
    np.asarray(out)
    dt = time.perf_counter() - t0
    hs = reps * chunk / dt
    return {
        "metric": "blake2b_hash_throughput_per_chip",
        "value": hs,
        "unit": "H/s",
        "vs_baseline": hs / TARGET_HS,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "chunk": chunk,
        "reps": reps,
        "seconds": dt,
    }


def _run_child(timeout: float) -> "tuple[dict | None, str]":
    """One bounded measurement child → (parsed JSON or None, failure label).

    Uses Popen (not subprocess.run) so the SIGTERM handler can reap the
    child if the caller's timeout kills this parent — an orphan would keep
    holding the chip.
    """
    # Block termination signals across the spawn: a SIGTERM landing between
    # Popen() and the _children registration would orphan the child.
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGINT})
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--inproc"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        _children.add(proc)
    finally:
        signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGTERM, signal.SIGINT})
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None, f"timeout>{timeout:.0f}s"
    finally:
        _children.discard(proc)
    if proc.returncode != 0:
        tail = (stderr or "").strip().splitlines()
        return None, f"rc={proc.returncode} {tail[-1][:120] if tail else ''}".strip()
    for line in reversed(stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(out, dict) and "value" in out:
            return out, ""
    return None, "rc=0 but no JSON result line"


def _terminated(signum, frame):
    # The caller's timeout is killing us: reap the child so nothing keeps
    # holding the chip. No result line: nothing was measured.
    for child in list(_children):
        try:
            child.kill()
        except OSError:
            pass
    _clear_foreign_bench()  # os._exit skips atexit; don't leave a stale flag
    os._exit(128 + signum)


def main() -> int:
    if sys.argv[1:] == ["--inproc"]:
        print(json.dumps(measure()))
        return 0
    signal.signal(signal.SIGTERM, _terminated)
    signal.signal(signal.SIGINT, _terminated)
    _announce_foreign_bench()
    result, why = _run_child(CHILD_TIMEOUT)
    if result is None:
        print(f"bench: measurement failed: {why}", file=sys.stderr)
        return 1
    if result.get("platform") != "tpu":
        print(f"bench: not measured on a TPU: {result}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
