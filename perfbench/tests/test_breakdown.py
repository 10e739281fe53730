"""breakdown.py: the stage means of a whole CPU run, and the launch-cycle
reading of a synthetic trace."""

from types import SimpleNamespace as NS

import breakdown
import cpu
import pytest
import xplane


def test_the_server_chain_adds_up_to_the_request_latency_on_a_cpu_run():
    cell = cpu.tiny_cell("tiny-backlog")
    w, res = cpu.run_cell(cell, seed=2**31 + 777)
    assert res["correct"], res["checks"]
    st = breakdown.stage_breakdown(w)
    assert None not in st["server_ms"].values(), st
    # The worker runs in another process than the server: its dispatch has
    # no publish to be timed from; its own chain is observed.
    assert st["worker_ms"]["dispatch"] is None
    assert None not in (st["worker_ms"][s] for s in ("submit", "pack", "device", "result"))
    request = st["request_ms"]["ondemand"]
    assert st["server_chain_ms"] == pytest.approx(request, rel=0.05)


def _ev(name, s, e):
    return NS(name=name, start_ns=s, duration_ns=e - s)


def _pd():
    ms = 1_000_000
    host = [
        _ev(xplane.WINDOW_SPAN, 0, 100 * ms),
        # launch 1: readback ends at 10, applied at 14; launch 2: 30 -> 31
        _ev("dpow.launch.readback", 9 * ms, 10 * ms),
        _ev("dpow.launch.readback", 29 * ms, 30 * ms),
        _ev("dpow.engine.apply", 14 * ms, 15 * ms),
        _ev("dpow.engine.apply", 31 * ms, 32 * ms),
    ]
    device = [_ev("kernel", 0, 10 * ms), _ev("kernel", 16 * ms, 30 * ms),
              _ev("kernel", 40 * ms, 100 * ms)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="t", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=device)]),
    ])


def test_launch_cycle_splits_idle_by_label_and_reads_the_loop_lag(monkeypatch):
    pd = _pd()
    monkeypatch.setattr(xplane, "find_xplane", lambda d: "x.xplane.pb")
    monkeypatch.setattr(xplane, "load", lambda path: pd)
    reduced = xplane.reduce(pd)
    lc = breakdown.launch_cycle("unused", reduced)
    assert lc["idle_s"] == pytest.approx(0.016)  # 10-16 and 30-40 ms
    assert lc["applies"] == 2 and lc["loop_lag"]["pairs"] == 2
    assert lc["loop_lag"]["mean_ms"] == pytest.approx((4 + 1) / 2)
    # Idle between readback end and apply start: 10-14 and 30-31 ms.
    assert lc["loop_lag"]["device_idle_s"] == pytest.approx(0.005)
    # Each gap takes the label of the span that overlaps it most: the apply.
    assert lc["idle_by_label_s"] == [["dpow.engine.apply", pytest.approx(0.016)]]
    assert lc["with_work_idle_s"] == pytest.approx(0.016)
