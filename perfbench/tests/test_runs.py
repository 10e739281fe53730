"""Whole runs of the harness on the CPU: server child, worker, load
generator, the reference check. Nothing here is a measurement; the cells
are tiny (see cpu.py)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import control
import cpu

PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERFBENCH)


def test_a_sound_run_is_correct_and_reports_its_metrics():
    cell = cpu.tiny_cell("tiny-backlog")
    w, res = cpu.run_cell(cell, seed=2**31 + 12345)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"setup_s", "work_rate_ghs", "work_p50_ms"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_the_control_comes_out_not_correct():
    """Every request asks one bit below its class threshold: the program
    serves that, and the reference, judging the class threshold, finds
    about half the works invalid."""
    cell = cpu.tiny_cell("tiny-backlog")
    session_kw = {"ease_bits": control.CONTROL_EASE_BITS,
                  "server_extra": control.CONTROL_SERVER_FLAGS}
    w, res = cpu.run_cell(cell, seed=99, **session_kw)
    assert not res["correct"]
    works = sum(1 for r in w.records if r["status"] == "work")
    assert res["checks"]["invalid_works"]["value"] > works // 4


def _drop_odd_rows(orig):
    """Half of every batch left out: the results of the rows whose block
    hash ends in an odd digit are dropped where the engine reads them."""
    def apply(self, rec, lo_arr, hi_arr):
        lo, hi = np.array(lo_arr), np.array(hi_arr)
        for i, job in enumerate(rec.jobs):
            if int(job.block_hash[-1], 16) % 2:
                lo[i] = hi[i] = 0xFFFFFFFF
        return orig(self, rec, lo, hi)
    return apply


def _alter_answers(orig):
    """An answer altered where it is produced: the kernel's winning nonce
    comes back with its lowest bit flipped (all but nonce 0, which only the
    engine's own set-up self-test finds)."""
    def offsets_to_nonces(params_batch, offs):
        lo, hi = orig(params_batch, offs)
        lo = np.array(lo)
        hit = (lo != 0xFFFFFFFF) & ((lo != 0) | (np.asarray(hi) != 0))
        lo[hit] ^= 1
        return lo, hi
    return staticmethod(offsets_to_nonces)


@pytest.mark.parametrize("fault", ["half_batch_left_out", "answer_altered"])
def test_a_broken_timed_path_comes_out_not_correct(fault):
    from tpu_dpow.backend import jax_backend

    cls = jax_backend.JaxWorkBackend
    if fault == "half_batch_left_out":
        name, patched = "_apply_plain_rows", _drop_odd_rows(cls._apply_plain_rows)
    else:
        name, patched = "_offsets_to_nonces", _alter_answers(cls._offsets_to_nonces)
    saved = cls.__dict__[name]
    setattr(cls, name, patched)
    try:
        cell = cpu.tiny_cell("tiny-short-timeout")
        w, res = cpu.run_cell(cell, seed=5, seconds=2.0)
    finally:
        setattr(cls, name, saved)
    assert not res["correct"]
    assert res["checks"]["unanswered"]["value"] > 0


def test_a_traced_run_on_the_cpu_prints_no_device_metric(tmp_path):
    cell = cpu.tiny_cell("tiny-mixed")
    w, res = cpu.run_cell(cell, seed=77, trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    assert "scan_useful_share.tail" in res["metrics"]
    for name in ("device_idle_share.tail", "device_idle_share.rate", "nonce_search_roofline"):
        assert name not in res["metrics"]
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload", "spec-saturated",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
