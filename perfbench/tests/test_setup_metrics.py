"""The reader of the worker's jit trace seconds before the window, on the
page the worker's registry renders and on synthetic pages."""

import catalog
import harness
import promtext

from tpu_dpow.obs.prom import render
from tpu_dpow.obs.registry import Registry


def _w(engine=({}, {})):
    return harness.WindowData(
        cell="c", config={}, traffic={}, thresholds={}, seed=1, seconds=10.0,
        t0=0.0, t1=10.0, records=[], lag_max_s=0.0, engine=engine, server=({}, {}),
        platform="tpu", device_kind="TPU v5 lite", device_count=1,
        memory_peak_bytes=0)


def _read(w):
    return catalog.reader("setup_trace_s").read(w, "setup_trace_s")


def _page(**phases):
    reg = Registry()
    c = reg.counter("dpow_engine_jit_seconds_total", "", ("phase",))
    for phase, seconds in phases.items():
        c.inc(seconds, phase)
    reg.counter("dpow_other_seconds_total", "", ("phase",)).inc(100.0, "trace")
    return promtext.parse(render(reg))


def test_setup_trace_reads_the_trace_phase_at_the_window_start():
    start = _page(trace=4.5, lower=7.25, compile=30.0)
    end = _page(trace=9.0, lower=8.0, compile=31.0)
    assert _read(_w(engine=(start, end))) == 4.5


def test_setup_trace_without_the_counter_returns_none():
    assert _read(_w()) is None
    # A program without the counter: other families on the page, none of it.
    other = _page()
    assert _read(_w(engine=(other, other))) is None
    # Only other phases counted before the window: no trace seconds to read.
    assert _read(_w(engine=(_page(compile=2.0), _page(trace=1.0)))) is None
