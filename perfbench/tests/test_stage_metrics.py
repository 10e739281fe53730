"""The readers of the request stages and of the engine's idle with work, on
synthetic counter pages and a synthetic ``xplane.reduce()`` dict."""

import catalog
import harness
import promtext
import pytest

STAGE_READERS = ("http_face_ms", "server_result_ms", "broker_round_trip_ms",
                 "worker_handler_ms", "engine_solve_ms")


def _page(stages: dict) -> dict:
    """A /metrics page holding ``stage -> (sum seconds, count)``, beside a
    histogram of another name whose stage labels must not be read."""
    lines = []
    for stage, (total, count) in stages.items():
        lines += [f'dpow_request_stage_seconds_bucket{{stage="{stage}",le="+Inf"}} {count}',
                  f'dpow_request_stage_seconds_sum{{stage="{stage}"}} {total}',
                  f'dpow_request_stage_seconds_count{{stage="{stage}"}} {count}',
                  f'dpow_other_seconds_sum{{stage="{stage}"}} 1000',
                  f'dpow_other_seconds_count{{stage="{stage}"}} 1']
    return promtext.parse("\n".join(lines) + "\n")


def _w(engine=({}, {}), server=({}, {}), trace=None):
    return harness.WindowData(
        cell="c", config={}, traffic={}, thresholds={}, seed=1, seconds=10.0,
        t0=0.0, t1=10.0, records=[], lag_max_s=0.0, engine=engine, server=server,
        platform="tpu", device_kind="TPU v5 lite", device_count=1,
        memory_peak_bytes=0, trace=trace)


def _read(name, w):
    return catalog.reader(name).read(w, name)


# Server: 10 requests before the window, 30 more inside it.
SERVER = (_page({"accept": (0.01, 10), "reply": (0.02, 10), "result_in": (0.2, 10),
                 "winner": (0.03, 10), "resolve": (0.01, 10)}),
          _page({"accept": (0.04, 40), "reply": (0.05, 40), "result_in": (0.8, 40),
                 "winner": (0.12, 40), "resolve": (0.07, 40)}))
# Worker: 20 jobs inside the window.
ENGINE = (_page({"submit": (0.0, 0), "pack": (0.0, 0), "device": (0.0, 0),
                 "result": (0.0, 0)}),
          _page({"submit": (0.004, 20), "pack": (0.02, 20), "device": (0.24, 20),
                 "result": (0.006, 20)}))


def test_stage_readers_take_the_window_mean_of_their_own_series():
    w = _w(engine=ENGINE, server=SERVER)
    assert _read("http_face_ms", w) == pytest.approx(1.0 + 1.0)      # 0.03/30 + 0.03/30
    assert _read("server_result_ms", w) == pytest.approx(3.0 + 2.0)  # 0.09/30 + 0.06/30
    assert _read("worker_handler_ms", w) == pytest.approx(0.2)
    assert _read("engine_solve_ms", w) == pytest.approx(12.0)
    # 20 ms from publish to the winning result, 1 + 12 + 0.3 of it in the worker.
    assert _read("broker_round_trip_ms", w) == pytest.approx(20.0 - 13.3)


@pytest.mark.parametrize("name", STAGE_READERS)
def test_a_stage_reader_with_nothing_observed_returns_none(name):
    assert _read(name, _w()) is None
    # The pages of a program that has no such stage: every other stage moved.
    page = _page({"queue": (1.0, 5), "publish": (1.0, 5), "cancel": (1.0, 5)})
    assert _read(name, _w(engine=({}, page), server=({}, page))) is None
    # Observed before the window, not inside it.
    assert _read(name, _w(engine=(ENGINE[1], ENGINE[1]),
                          server=(SERVER[1], SERVER[1]))) is None


def test_broker_round_trip_needs_both_sides():
    assert _read("broker_round_trip_ms", _w(server=SERVER)) is None
    assert _read("broker_round_trip_ms", _w(engine=ENGINE)) is None


def _trace(idle_per_device, window_s=10.0):
    return {"window_s": window_s, "window_ns": (0.0, window_s * 1e9),
            "busy_s": window_s - sum(idle_per_device[0].values()),
            "devices": [{"plane": f"/device:TPU:{i}", "busy_s": 0.0, "ops": {},
                         "idle": idle, "events": []}
                        for i, idle in enumerate(idle_per_device)]}


def test_idle_with_work_leaves_out_only_the_engine_idle_label():
    idle = {"dpow.engine.idle": 2.0, "dpow.engine.wait": 0.5, "dpow.launch.readback": 0.25,
            "dpow.engine.apply": 0.2, "gaps under 10 us": 0.05}
    w = _w(trace=_trace([idle]))
    for name in ("idle_with_work_share.rate", "idle_with_work_share.tail"):
        assert _read(name, w) == pytest.approx(100.0 * 1.0 / 10.0)
    # ... and never more than the device's idle share.
    assert _read("idle_with_work_share.tail", w) <= _read("device_idle_share.tail", w)


def test_idle_with_work_averages_over_the_chips():
    w = _w(trace=_trace([{"dpow.engine.wait": 1.0}, {"dpow.engine.wait": 3.0,
                                                     "dpow.engine.idle": 4.0}]))
    assert _read("idle_with_work_share.tail", w) == pytest.approx(20.0)


def test_idle_with_work_reads_nothing_from_a_program_without_engine_spans():
    """A program without the engine spans labels its gaps with JAX calls:
    idle with work cannot be told from idle for lack of demand."""
    w = _w(trace=_trace([{"np.asarray(jax.Array)": 2.5, "host: nothing traced": 0.5}]))
    assert _read("idle_with_work_share.tail", w) is None
    # A window with no idle at all reads 0, spans or not.
    assert _read("idle_with_work_share.rate", _w(trace=_trace([{}]))) == 0.0


def test_idle_with_work_with_nothing_to_read_returns_none():
    assert _read("idle_with_work_share.tail", _w()) is None
    assert _read("idle_with_work_share.rate", _w(trace={"window_s": 1.0, "devices": []})) is None
