"""tpu_dpow.obs contract: registry semantics, renderer goldens, tracing,
the /metrics HTTP surface, and the payload trace-id grammar.

Tier-1 (unmarked): everything here is pure host code — no device, no
sockets beyond loopback aiohttp.
"""

import asyncio
import concurrent.futures
import math

import pytest

from tpu_dpow import obs
from tpu_dpow.obs.registry import (
    LOG2_BUCKETS,
    MAX_SERIES,
    OVERFLOW_LABEL,
    MetricError,
    Registry,
)
from tpu_dpow.obs.trace import Tracer
from tpu_dpow.transport import mqtt_codec as mc


# ---------------------------------------------------------------- registry


def test_counter_gauge_basics_and_labels():
    reg = Registry()
    c = reg.counter("x_total", "help", ("kind",))
    c.inc(1, "a")
    c.inc(2.5, "a")
    c.inc(1, "b")
    assert c.value("a") == 3.5 and c.value("b") == 1
    with pytest.raises(MetricError):
        c.inc(-1, "a")  # counters are monotonic
    with pytest.raises(MetricError):
        c.inc(1)  # label arity enforced
    g = reg.gauge("g", "help")
    g.set(5)
    g.dec(2)
    assert g.value() == 3


def test_registry_get_or_create_shares_and_rejects_mismatch():
    reg = Registry()
    a = reg.counter("shared_total", "", ("l",))
    b = reg.counter("shared_total", "", ("l",))
    assert a is b  # two components share one family
    with pytest.raises(MetricError):
        reg.gauge("shared_total", "")  # kind mismatch
    with pytest.raises(MetricError):
        reg.counter("shared_total", "", ("other",))  # label mismatch
    h = reg.histogram("shared_seconds", "", buckets=(1, 2))
    assert reg.histogram("shared_seconds", "", buckets=(1, 2)) is h
    with pytest.raises(MetricError):
        reg.histogram("shared_seconds", "")  # bucket ladder mismatch


def test_label_cardinality_bounded_with_overflow_series():
    reg = Registry()
    c = reg.counter("card_total", "", ("v",))
    for i in range(MAX_SERIES * 3):
        c.inc(1, f"value-{i}")
    series = c.collect()
    assert len(series) == MAX_SERIES
    # nothing lost: the fold-over series absorbed the excess
    assert sum(series.values()) == MAX_SERIES * 3
    assert series[(OVERFLOW_LABEL,)] == MAX_SERIES * 3 - (MAX_SERIES - 1)
    # existing series keep counting even at capacity
    c.inc(1, "value-0")
    assert c.value("value-0") == 2


def test_histogram_log2_bucket_edges():
    # The fixed ladder: consecutive powers of two, 2^-13 .. 2^5.
    assert LOG2_BUCKETS[0] == 2.0**-13 and LOG2_BUCKETS[-1] == 32.0
    for lo, hi in zip(LOG2_BUCKETS, LOG2_BUCKETS[1:]):
        assert hi == 2 * lo
    reg = Registry()
    h = reg.histogram("h_seconds", "")
    # An observation exactly ON an edge lands in that edge's bucket (le is
    # inclusive, per Prometheus), one ulp above lands in the next.
    h.observe(0.25)
    h.observe(0.250001)
    h.observe(1e9)  # +Inf bucket
    rows = dict(h.collect()[()]["buckets"])
    assert rows[0.25] == 1
    assert rows[0.5] == 2
    assert rows[math.inf] == 3
    assert h.collect()[()]["count"] == 3


def test_histogram_cumulative_monotone_and_sum():
    reg = Registry()
    h = reg.histogram("m_seconds", "", ("stage",))
    values = [0.0001, 0.004, 0.004, 0.1, 2.0, 50.0]
    for v in values:
        h.observe(v, "queue")
    data = h.collect()[("queue",)]
    counts = [c for _, c in data["buckets"]]
    assert counts == sorted(counts)  # cumulative never decreases
    assert counts[-1] == len(values)
    assert data["sum"] == pytest.approx(sum(values))


def test_registry_thread_safety_under_executor_hammering():
    """Counters/histograms are mutated from engine executor threads; no
    increments may be lost under contention."""
    reg = Registry()
    c = reg.counter("threads_total", "", ("who",))
    h = reg.histogram("threads_seconds", "")
    N, W = 2000, 8

    def hammer(i):
        for _ in range(N):
            c.inc(1, f"w{i % 4}")
            h.observe(0.001)

    with concurrent.futures.ThreadPoolExecutor(max_workers=W) as pool:
        list(pool.map(hammer, range(W)))
    assert sum(c.collect().values()) == N * W
    assert h.collect()[()]["count"] == N * W


def test_snapshot_machine_readable():
    reg = Registry()
    reg.counter("s_total", "", ("k",)).inc(2, "a")
    reg.histogram("s_seconds", "").observe(0.01)
    snap = reg.snapshot()
    assert snap["s_total"]["kind"] == "counter"
    assert snap["s_total"]["series"]["a"] == 2
    hseries = snap["s_seconds"]["series"][""]
    assert hseries["count"] == 1 and isinstance(hseries["buckets"], list)


# ---------------------------------------------------------------- renderer


GOLDEN = """\
# HELP dpow_demo_requests_total Requests served
# TYPE dpow_demo_requests_total counter
dpow_demo_requests_total{work_type="ondemand"} 3
dpow_demo_requests_total{work_type="precache"} 1.5
# HELP dpow_demo_seconds Latency
# TYPE dpow_demo_seconds histogram
dpow_demo_seconds_bucket{stage="queue",le="0.5"} 1
dpow_demo_seconds_bucket{stage="queue",le="2"} 2
dpow_demo_seconds_bucket{stage="queue",le="+Inf"} 3
dpow_demo_seconds_sum{stage="queue"} 4.4
dpow_demo_seconds_count{stage="queue"} 3
# HELP dpow_demo_up "quoted" and back\\\\slashed
# TYPE dpow_demo_up gauge
dpow_demo_up{node="a\\"b\\\\c"} 1
"""


def test_renderer_golden_prometheus_text_v004():
    reg = Registry()
    c = reg.counter("dpow_demo_requests_total", "Requests served",
                    ("work_type",))
    c.inc(3, "ondemand")
    c.inc(1.5, "precache")
    h = reg.histogram("dpow_demo_seconds", "Latency", ("stage",),
                      buckets=(0.5, 2.0))
    for v in (0.4, 1.0, 3.0):
        h.observe(v, "queue")
    g = reg.gauge("dpow_demo_up", '"quoted" and back\\slashed', ("node",))
    g.set(1, 'a"b\\c')
    assert obs.render(reg) == GOLDEN


def test_parse_text_roundtrips_renderer_output():
    reg = Registry()
    reg.counter("rt_total", "", ("k",)).inc(7, "x")
    reg.histogram("rt_seconds", "").observe(0.01)
    page = obs.render(reg)
    parsed = obs.parse_text(page)
    assert parsed["rt_total"] == [({"k": "x"}, 7.0)]
    assert parsed["rt_seconds_count"] == [({}, 1.0)]
    infs = [v for labels, v in parsed["rt_seconds_bucket"]
            if labels["le"] == "+Inf"]
    assert infs == [1.0]


def test_histogram_quantile_estimate():
    # 100 obs uniform-ish: 50 in (0, 1], 50 in (1, 2] -> p50 ~= 1.0
    rows = [(1.0, 50), (2.0, 100), (math.inf, 100)]
    assert obs.histogram_quantile(rows, 0.5) == pytest.approx(1.0)
    assert obs.histogram_quantile(rows, 0.75) == pytest.approx(1.5)
    assert obs.histogram_quantile([], 0.5) is None


# ------------------------------------------------------------------ traces


def test_tracer_span_chain_and_stage_histogram():
    reg = Registry()
    t = Tracer(registry=reg)
    tid = t.begin("HASH" * 16)
    t.mark_hash("HASH" * 16, "queue")
    t.mark(tid, "publish")
    spans = t.spans(tid)
    assert [s for s, _ in spans] == ["accept", "queue", "publish"]
    assert spans[0][1] == 0.0 and all(d >= 0 for _, d in spans)
    h = reg.histogram("dpow_request_stage_seconds", "", ("stage",))
    assert h.count_of("queue") == 1 and h.count_of("publish") == 1


def _stage_sum(reg, stage):
    series = reg.histogram("dpow_request_stage_seconds", "", ("stage",)).collect()
    return series[(stage,)]["sum"] if (stage,) in series else None


def test_tracer_times_each_stage_from_its_parent_while_interleaved():
    """cancel and reply run in different tasks after winner/resolve: each
    is timed from its own parent, not from whichever mark came last."""
    reg = Registry()
    t = Tracer(registry=reg)
    tid = t.begin("H" * 64, "receive", at=100.0)
    for stage, at in (("accept", 100.5), ("queue", 101.0), ("publish", 102.0),
                      ("result_in", 110.0), ("winner", 111.0),
                      ("resolve", 113.0), ("cancel", 114.0), ("reply", 117.0)):
        t.mark(tid, stage, at=at)
    assert _stage_sum(reg, "result_in") == 8.0  # from publish
    assert _stage_sum(reg, "cancel") == 3.0     # from winner, not resolve
    assert _stage_sum(reg, "reply") == 4.0      # from resolve, not cancel
    assert dict(t.spans(tid))["reply"] == 4.0
    # The server chain sums to receive -> reply.
    chain = ("accept", "queue", "publish", "result_in", "winner", "resolve", "reply")
    assert sum(_stage_sum(reg, s) for s in chain) == 17.0


def test_tracer_parentless_stage_is_not_observed():
    """A worker in another process starts its trace empty at alias():
    dispatch has no publish to be timed from, so nothing is observed,
    while the worker's own chain from dispatch on is."""
    reg = Registry()
    t = Tracer(registry=reg)
    tid = "0123456789abcdef"
    t.alias("W" * 64, tid)
    t.mark_hash("W" * 64, "dispatch", at=5.0)
    t.mark_hash("W" * 64, "submit", at=5.25)
    t.mark_hash("W" * 64, "pack", at=5.5)
    assert _stage_sum(reg, "dispatch") is None
    assert _stage_sum(reg, "submit") == 0.25
    assert _stage_sum(reg, "pack") == 0.5
    # A stage outside the table keeps the previous-mark rule.
    t.mark(tid, "custom", at=6.0)
    assert _stage_sum(reg, "custom") == 0.5


def test_tracer_mark_at_stamps_the_given_instant():
    reg = Registry()
    t = Tracer(registry=reg)
    tid = t.begin("A" * 64, "receive", at=t.now() - 2.0)
    t.mark(tid, "accept")
    assert 2.0 <= _stage_sum(reg, "accept") < 3.0
    t.mark_hash("A" * 64, "queue", at=t.get(tid)[-1][1] + 0.125)
    assert _stage_sum(reg, "queue") == 0.125
    assert [s for s, _ in t.get(tid)] == ["receive", "accept", "queue"]


def test_span_never_imports_jax():
    """obs.span is a no-op in a process that never imported jax (the
    server), and stays one: it must not pull jax in."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from tpu_dpow import obs\n"
            "with obs.span('dpow.engine.wait'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'span imported jax'\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_span_is_a_trace_annotation_once_jax_is_imported():
    import jax

    with obs.span("dpow.engine.apply") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_engine_counts_jit_seconds_by_phase_once_per_process():
    """JAX's compile events feed dpow_engine_jit_seconds_total{phase}; two
    engines in one process still count each event once, and a jit traced
    inside another's trace is not counted again."""
    import jax

    from tpu_dpow.backend.jax_backend import JIT_PHASES, JaxWorkBackend

    JaxWorkBackend(kernel="xla", sublanes=8, iters=8)
    JaxWorkBackend(kernel="xla", sublanes=8, iters=8)
    seconds = obs.get_registry().counter(
        "dpow_engine_jit_seconds_total", "", ("phase",))
    assert sorted(JIT_PHASES.values()) == ["compile", "lower", "trace"]
    before = {p: seconds.value(p) for p in JIT_PHASES.values()}
    for i, event in enumerate(JIT_PHASES):
        jax.monitoring.record_event_duration_secs(event, 0.25 * (i + 1), fun_name="f")
    jax.monitoring.record_event_duration_secs("/jax/other_duration", 7.0)
    got = {p: seconds.value(p) - before[p] for p in JIT_PHASES.values()}
    assert got == pytest.approx({"trace": 0.25, "lower": 0.5, "compile": 0.75})

    trace_event = next(e for e, p in JIT_PHASES.items() if p == "trace")
    jax.monitoring.record_scalar(trace_event, 0.0, fun_name="outer")
    jax.monitoring.record_scalar(trace_event, 0.0, fun_name="inner")
    jax.monitoring.record_event_duration_secs(trace_event, 1.0, fun_name="inner")
    jax.monitoring.record_event_duration_secs(trace_event, 3.0, fun_name="outer")
    assert seconds.value("trace") - before["trace"] == pytest.approx(0.25 + 3.0)


def test_tracer_unknown_ids_are_noops_and_store_is_bounded():
    from tpu_dpow.obs import trace as trace_mod

    t = Tracer(registry=Registry())
    t.mark("feedfeedfeedfeed", "queue")  # unknown: silently ignored
    t.mark_hash("NOPE", "queue")
    for i in range(trace_mod.MAX_TRACES + 10):
        t.begin(f"K{i}")
    assert len(t._traces) <= trace_mod.MAX_TRACES
    assert len(t._aliases) <= trace_mod.MAX_TRACES
    # alias() takes WIRE-SUPPLIED ids — an untrusted peer spraying fresh
    # ids must hit the same LRU bound, not grow the store forever.
    for i in range(trace_mod.MAX_TRACES + 500):
        t.alias(f"H{i}", f"{i:016x}")
    assert len(t._traces) <= trace_mod.MAX_TRACES
    assert len(t._aliases) <= trace_mod.MAX_TRACES


def test_trace_id_wire_validation():
    assert obs.is_trace_id("0123456789abcdef")
    assert not obs.is_trace_id("0123456789ABCDEF")  # uppercase: not ours
    assert not obs.is_trace_id("xyz")
    assert not obs.is_trace_id("0123456789abcde")  # 15 chars
    tid = obs.new_trace_id()
    assert obs.is_trace_id(tid)


# ------------------------------------------------- payload trace-id grammar


def test_payload_helpers_roundtrip_and_backward_compat():
    tid = obs.new_trace_id()
    p = mc.encode_work_payload("AB", 0xFFFFFFC000000000, tid)
    assert p == f"AB,ffffffc000000000,{tid}"
    assert mc.parse_work_payload(p) == ("AB", "ffffffc000000000", tid, None)
    # pre-trace peers' payloads parse unchanged
    assert mc.parse_work_payload("AB,ffffffc000000000") == (
        "AB", "ffffffc000000000", None, None)
    # a non-trace trailing token is ignored, not crashed on
    assert mc.parse_work_payload("AB,fff,garbage")[2] is None
    with pytest.raises(ValueError):
        mc.parse_work_payload("AB")

    r = mc.encode_result_payload("AB", "beef", "nano_x", tid)
    assert mc.parse_result_payload(r) == ("AB", "beef", "nano_x", tid)
    assert mc.parse_result_payload("AB,beef,nano_x") == (
        "AB", "beef", "nano_x", None)
    with pytest.raises(ValueError):
        mc.parse_result_payload("AB,beef")


# -------------------------------------------------------- /metrics surface


def test_metrics_route_serves_prometheus_text():
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        reg = Registry()
        reg.counter("route_total", "").inc(4)
        app = web.Application()
        obs.add_metrics_route(app, reg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.content_type == "text/plain"
            text = await resp.text()
            assert "route_total 4" in text
            parsed = obs.parse_text(text)
            assert parsed["route_total"] == [({}, 4.0)]
        finally:
            await client.close()

    asyncio.run(asyncio.wait_for(main(), timeout=30))


def test_client_app_serves_metrics_endpoint():
    """The worker's own /metrics face (config.metrics_port=0 binds an
    ephemeral port) — the scrape surface for a fleet of clients."""
    import aiohttp

    from tpu_dpow.client import ClientConfig, DpowClient
    from tpu_dpow.transport.broker import Broker
    from tpu_dpow.transport.inproc import InProcTransport

    async def main():
        broker = Broker()
        server_t = InProcTransport(broker, client_id="hb")
        await server_t.connect()

        async def heartbeat():
            while True:
                await server_t.publish("heartbeat", "", qos=0)
                await asyncio.sleep(0.05)

        hb = asyncio.ensure_future(heartbeat())
        config = ClientConfig(backend="jax", metrics_port=0,
                              startup_heartbeat_wait=3.0)
        from tpu_dpow.backend.jax_backend import JaxWorkBackend

        client = DpowClient(
            config, InProcTransport(broker, client_id="w-metrics"),
            backend=JaxWorkBackend(kernel="xla", sublanes=8, iters=8),
        )
        await client.setup()
        try:
            assert client.metrics_port and client.metrics_port > 0
            url = f"http://127.0.0.1:{client.metrics_port}/metrics"
            async with aiohttp.ClientSession() as http:
                async with http.get(url) as resp:
                    assert resp.status == 200
                    text = await resp.text()
            assert "dpow_client_queue_depth" in text
        finally:
            hb.cancel()
            await client.close()
            await server_t.close()

    asyncio.run(asyncio.wait_for(main(), timeout=60))
