"""The trace reduction, on interval sets and on a trace recorded on the chip
(PR 22: spec-saturated, 10 s traced window, TPU v5 lite)."""

import os

import catalog
import pytest
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "spec-saturated-10s.xplane.pb")


def test_union_clip_and_gaps():
    ivs = [(0, 10), (5, 20), (30, 40), (38, 45), (100, 120)]
    assert xplane.union(ivs) == [(0, 20), (30, 45), (100, 120)]
    assert xplane.covered(ivs, 10, 110) == 10 + 15 + 10
    assert xplane.gaps(ivs, 10, 110) == [(20, 30), (45, 100)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]
    assert xplane.covered([(0, 5)], 10, 20) == 0


def test_short_names_keep_the_op_and_its_shape():
    long = ('%pallas_search_chunk_batch.1 = u32[16,1]{1,0:T(8,128)S(1)} custom-call('
            'u32[16,12]{1,0:T(8,128)} %params_batch.1), custom_call_target="tpu_custom_call"')
    assert xplane.short_name(long) == "pallas_search_chunk_batch.1 u32[16,1]{1,0:T(8,128)S(1)}"


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(xplane.load(RECORDED))


def test_recorded_trace_busy_idle_and_ops(recorded):
    r = recorded
    assert abs(r["window_s"] - 10.002247991) < 1e-9
    assert abs(r["busy_s"] - 9.508901514) < 1e-9
    ops = dict(r["breakdown"]["device_ops"])
    kernel = ops["pallas_search_chunk_batch.1 u32[16,1]{1,0:T(8,128)S(1)}"]
    assert abs(kernel - 9.50889396) < 1e-9
    idle = dict(r["breakdown"]["idle_gaps"])
    # The chip waited while the host read the last launch's result back.
    assert max(idle, key=idle.get) == "np.asarray(jax.Array)"
    assert abs(sum(idle.values()) - (r["window_s"] - r["busy_s"])) < 1e-9


def test_recorded_trace_kernel_time_counts_whole_events_ending_in_the_window(recorded):
    roof = catalog.reader("nonce_search_roofline")
    evs = roof.kernel_events(recorded)
    assert len(evs) == 17
    assert all(roof.BATCH.match(n).group(1) == "16" for n, _s, _e in evs)
    secs = sum(e - s for _n, s, e in evs) * 1e-9
    assert abs(secs - 9.871047996) < 1e-6


def test_recorded_trace_roofline_arithmetic(recorded):
    import harness
    import promtext
    import roofline

    e0 = promtext.parse("dpow_engine_hashes_total{engine=\"jax\"} 0\n"
                        "dpow_engine_batch_occupancy_sum 0\n")
    e1 = promtext.parse("dpow_engine_hashes_total{engine=\"jax\"} 1e10\n"
                        "dpow_engine_batch_occupancy_sum 270\n")
    w = harness.WindowData(
        cell="c", config={}, traffic={}, thresholds={}, seed=1, seconds=10.0, t0=0, t1=10,
        records=[], lag_max_s=0, engine=(e0, e1), server=({}, {}), platform="tpu",
        device_kind="TPU v5 lite", device_count=1, memory_peak_bytes=0, trace=recorded,
        extra={"geometry": {"group": 8, "sublanes": 32}})
    got = catalog.reader("nonce_search_roofline").read(w, "nonce_search_roofline")
    nonces = 1e10 + (17 * 16 - 270) * 8 * 32 * 128
    want = 100 * nonces * roofline.OPS_PER_HASH / (9.871047996 * 6.15625e12)
    assert abs(got - want) < 1e-4
    assert catalog.reader("device_idle_share.rate").read(w, "x") == pytest.approx(
        100 * (1 - 9.508901514 / 10.002247991))
