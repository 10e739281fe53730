import types

import catalog
import harness
import promtext
import refcheck

BASE = 0xFFFFFFC000000000
RECEIVE = 0xFFFFFE0000000000


def _w(records, thresholds, engine=({}, {}), server=({}, {}), seconds=10.0, trace=None):
    return harness.WindowData(
        cell="c", config={}, traffic={"kind": "closed_backlog", "timeout": 30},
        thresholds=thresholds, seed=1, seconds=seconds, t0=100.0, t1=100.0 + seconds,
        records=records, lag_max_s=0.0, engine=engine, server=server,
        platform="tpu", device_kind="TPU v5 lite", device_count=1,
        memory_peak_bytes=0, trace=trace)


def _valid_record(cls, threshold, done, block_hash="00" * 32):
    # Find a real valid work so the reference check inside passes.
    import hashlib

    h = bytes.fromhex(block_hash)
    n = 0
    while True:
        le = n.to_bytes(8, "little")
        if int.from_bytes(hashlib.blake2b(le + h, digest_size=8).digest(), "little") >= threshold:
            break
        n += 1
    return {"cls": cls, "hash": block_hash, "work": le[::-1].hex(), "status": "work",
            "judged": True, "intended": done - 1.0, "sent": done - 1.0, "done": done}


def test_work_rate_sums_expected_effort_of_valid_work_in_the_window():
    low = 0xFF00000000000000  # 256 expected hashes: quick to solve here
    recs = [_valid_record("base", low, 101.0), _valid_record("base", low, 105.0),
            _valid_record("base", low, 99.0),   # before the window
            _valid_record("base", low, 111.0)]  # after it
    bad = dict(recs[0], work="0000000000000000", done=102.0)
    if refcheck.work_valid(bad["hash"], bad["work"], low):
        bad["work"] = "0000000000000001"
    recs.append(bad)
    w = _w(recs, {"base": low})
    got = catalog.reader("work_rate_ghs").read(w, "work_rate_ghs")
    assert got == 2 * 256.0 / 10.0 / 1e9


def test_work_rate_uses_the_class_threshold_not_the_asked_one():
    low = 0xFF00000000000000
    rec = _valid_record("base", low, 101.0)
    w = _w([rec], {"base": low})
    assert catalog.reader("work_rate_ghs").read(w, "work_rate_ghs") == 256.0 / 10.0 / 1e9
    # The same work judged at a harder class threshold is not delivered work.
    hard = _w([rec], {"base": 0xFFFFFFFFFFFFFFF0})
    assert catalog.reader("work_rate_ghs").read(hard, "work_rate_ghs") is None


def test_scan_useful_share_and_queue_waits_read_counter_deltas():
    low = 0xFF00000000000000
    e0 = promtext.parse('dpow_engine_hashes_total{engine="jax"} 1000\n'
                        'dpow_engine_queue_wait_seconds_sum{engine="jax"} 1.0\n'
                        'dpow_engine_queue_wait_seconds_count{engine="jax"} 10\n')
    e1 = promtext.parse('dpow_engine_hashes_total{engine="jax"} 2024\n'
                        'dpow_engine_queue_wait_seconds_sum{engine="jax"} 1.5\n'
                        'dpow_engine_queue_wait_seconds_count{engine="jax"} 20\n')
    s0 = promtext.parse('dpow_sched_queue_wait_seconds_sum{work_class="ondemand"} 0\n'
                        'dpow_sched_queue_wait_seconds_count{work_class="ondemand"} 0\n')
    s1 = promtext.parse('dpow_sched_queue_wait_seconds_sum{work_class="ondemand"} 0.02\n'
                        'dpow_sched_queue_wait_seconds_count{work_class="ondemand"} 4\n')
    w = _w([_valid_record("base", low, 101.0), _valid_record("base", low, 102.0)],
           {"base": low}, engine=(e0, e1), server=(s0, s1))
    assert catalog.reader("scan_useful_share.rate").read(w, "scan_useful_share.rate") == 50.0
    assert abs(catalog.reader("engine_queue_wait_ms").read(w, "engine_queue_wait_ms") - 50.0) < 1e-9
    assert abs(catalog.reader("admission_wait_ms").read(w, "admission_wait_ms") - 5.0) < 1e-9


def test_a_reader_with_nothing_to_read_returns_none():
    w = _w([], {"base": BASE})
    for name in ("scan_useful_share.tail", "engine_queue_wait_ms", "admission_wait_ms",
                 "device_idle_share.tail", "nonce_search_roofline", "work_rate_ghs"):
        assert catalog.reader(name).read(w, name) is None, name


def test_device_metrics_are_never_read_off_the_chip():
    trace = {"window_s": 1.0, "window_ns": (0.0, 1e9), "busy_s": 0.5,
             "devices": [{"events": [("_kernel_blocks", 0.0, 5e8)]}]}
    w = _w([], {"base": BASE}, trace=trace)
    w.platform = "cpu"
    entries = [{"name": "device_idle_share.tail", "unit": "%", "source": "device_trace"}]
    assert harness.read_metrics(w, entries) == {}
    w.platform = "tpu"
    assert harness.read_metrics(w, entries) == {
        "device_idle_share.tail": {"value": 50.0, "unit": "%"}}
