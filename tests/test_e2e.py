"""Full-stack end-to-end: HTTP service → server → broker → clients → JAX
backend → result → winner election → HTTP response. SURVEY.md §7's
"minimum end-to-end slice", plus the TCP-transport variant.
"""

import asyncio
import json

import aiohttp
import numpy as np
import pytest

from tpu_dpow.backend.jax_backend import JaxWorkBackend
from tpu_dpow.client import ClientConfig, DpowClient
from tpu_dpow.models import WorkType
from tpu_dpow.server import DpowServer, ServerConfig, hash_key
from tpu_dpow.server.api import ServerRunner
from tpu_dpow.store import MemoryStore
from tpu_dpow.transport import default_users
from tpu_dpow.transport.broker import Broker
from tpu_dpow.transport.inproc import InProcTransport
from tpu_dpow.transport.tcp import TcpBrokerServer, TcpTransport
from tpu_dpow.utils import nanocrypto as nc

RNG = np.random.default_rng(31)
EASY_BASE = 0xFF00000000000000  # ~256 hashes expected: instant on CPU jax
PAYOUT_1 = nc.encode_account(bytes(range(32)))
PAYOUT_2 = nc.encode_account(bytes(range(1, 33)))


def random_hash():
    return RNG.bytes(32).hex().upper()


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


def make_client(transport, payout, **config_overrides):
    config = ClientConfig(
        payout_address=payout, startup_heartbeat_wait=3.0, **config_overrides
    )
    # warm_shapes=True: serve from already-compiled launch shapes and grow
    # the ladder in the BACKGROUND. With it off (the plain-CPU default), a
    # burst's first batched pack compiles INLINE on the dispatch path —
    # ~4-6 s for the batch-16 shape on this host, racing the 5 s default
    # service timeout. That race was the long-standing soak flake
    # (test_e2e_soak_with_cancels_and_timeouts timing out ~1 in 5 when
    # earlier tests perturbed arrival timing): every request of a burst
    # stalls behind one cold compile. tests/test_backend.py pins the
    # no-unwarmed-shape-on-the-dispatch-path property as the regression
    # guard.
    backend = JaxWorkBackend(kernel="xla", sublanes=8, iters=8, warm_shapes=True)
    return DpowClient(config, transport, backend=backend)


async def start_stack(broker, n_clients=2, **server_overrides):
    config = ServerConfig(
        base_difficulty=EASY_BASE,
        throttle=1000.0,
        heartbeat_interval=0.05,
        statistics_interval=3600.0,
        service_port=0, service_ws_port=0, upcheck_port=0, block_cb_port=0,
        **server_overrides,
    )
    store = MemoryStore()
    server = DpowServer(config, store, InProcTransport(broker, client_id="server"))
    runner = ServerRunner(server, config)
    await runner.start()
    await store.hset(
        "service:svc",
        {"api_key": hash_key("secret"), "public": "N", "display": "svc",
         "website": "", "precache": "0", "ondemand": "0"},
    )
    await store.sadd("services", "svc")

    clients = []
    payouts = [PAYOUT_1, PAYOUT_2]
    for i in range(n_clients):
        c = make_client(
            InProcTransport(broker, client_id=f"worker{i}", clean_session=False),
            payouts[i % 2],
        )
        await c.setup()
        c.start_loops()
        clients.append(c)
    return runner, server, store, clients


async def stop_stack(runner, clients):
    for c in clients:
        await c.close()
    await runner.stop()


def test_e2e_http_service_request():
    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(broker)
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h,
                               "account": PAYOUT_1, "id": 7}
                ) as resp:
                    body = await resp.json()
                assert body.get("id") == 7, body
                assert "work" in body, body
                nc.validate_work(h, body["work"], EASY_BASE)
                # exactly one client was credited (winner election held)
                await asyncio.sleep(0.1)
                credits = 0
                for payout in (PAYOUT_1, PAYOUT_2):
                    got = await store.hget(f"client:{payout}", "ondemand")
                    credits += int(got or 0)
                assert credits == 1
                # losers were told to cancel; no client still grinds
                for c in clients:
                    assert not c.work_handler.ongoing
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_burst_of_requests():
    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(broker)
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                hashes = [random_hash() for _ in range(8)]

                async def one(h):
                    async with http.post(
                        url, json={"user": "svc", "api_key": "secret", "hash": h,
                                   "timeout": 20}
                    ) as resp:
                        return await resp.json()

                bodies = await asyncio.gather(*(one(h) for h in hashes))
                for h, body in zip(hashes, bodies):
                    assert "work" in body, body
                    nc.validate_work(h, body["work"], EASY_BASE)
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_precache_then_instant_hit():
    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(broker, debug=True)
        try:
            h = random_hash()
            await server.block_arrival_handler(h, PAYOUT_1, None)
            # workers precache it
            for _ in range(300):
                work = await store.get(f"block:{h}")
                if work and work != "0":
                    break
                await asyncio.sleep(0.02)
            nc.validate_work(h, work, EASY_BASE)
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h}
                ) as resp:
                    body = await resp.json()
            assert body["work"] == work
            assert await store.hget("service:svc", "precache") == "1"
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_over_tcp_transport():
    """Same flow with the server and a worker on real TCP sockets + ACLs."""

    async def main():
        broker = Broker(users=default_users())
        tcp_server = TcpBrokerServer(broker, port=0)
        await tcp_server.start()
        port = tcp_server.port

        config = ServerConfig(
            base_difficulty=EASY_BASE, throttle=1000.0,
            heartbeat_interval=0.05, statistics_interval=3600.0,
            service_port=0, service_ws_port=0, upcheck_port=0, block_cb_port=0,
        )
        store = MemoryStore()
        server = DpowServer(
            config, store,
            TcpTransport(port=port, username="dpowserver", password="dpowserver",
                         client_id="server"),
        )
        runner = ServerRunner(server, config)
        await runner.start()
        await store.hset("service:svc", {"api_key": hash_key("secret"),
                                         "public": "N", "precache": "0",
                                         "ondemand": "0"})
        await store.sadd("services", "svc")

        client = make_client(
            TcpTransport(port=port, username="client", password="client",
                         client_id="w-tcp", clean_session=False),
            PAYOUT_1,
        )
        await client.setup()
        client.start_loops()
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h,
                               "timeout": 20}
                ) as resp:
                    body = await resp.json()
            assert "work" in body, body
            nc.validate_work(h, body["work"], EASY_BASE)
        finally:
            await client.close()
            await runner.stop()
            await tcp_server.stop()

    run(main())


def test_e2e_soak_with_cancels_and_timeouts():
    """Chaos soak: a mixed stream of normal requests, client-timeout
    aborts, and duplicate hashes racing, against two workers. Afterwards
    the stack must be fully drained: no ongoing work, no leaked backend
    jobs, and every normal request got valid work. (The reference can only
    test this against a live swarm — SURVEY.md §4.)"""

    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(broker, n_clients=2)
        try:
            url = f"http://127.0.0.1:{runner.ports['service']}/service/"
            results = {"ok": 0, "timeout": 0, "error": 0}

            async def normal(http, i):
                h = random_hash()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h}
                ) as resp:
                    body = await resp.json()
                if "work" in body:
                    nc.validate_work(h, body["work"], EASY_BASE)
                    results["ok"] += 1
                else:
                    results["error"] += 1

            async def duplicated(http, i):
                # same hash from two "services" concurrently: dedup + shared
                # result must serve both
                h = random_hash()
                async def one():
                    async with http.post(
                        url, json={"user": "svc", "api_key": "secret", "hash": h}
                    ) as resp:
                        return await resp.json()
                a, b = await asyncio.gather(one(), one())
                for body in (a, b):
                    if "work" in body:
                        nc.validate_work(h, body["work"], EASY_BASE)
                        results["ok"] += 1
                    else:
                        results["error"] += 1

            async def impatient(http, i):
                # client walks away mid-request (connection abort path)
                h = random_hash()
                try:
                    async with http.post(
                        url,
                        json={"user": "svc", "api_key": "secret", "hash": h},
                        timeout=aiohttp.ClientTimeout(total=0.02),
                    ) as resp:
                        await resp.json()
                except asyncio.TimeoutError:
                    results["timeout"] += 1

            async with aiohttp.ClientSession() as http:
                tasks = []
                for i in range(8):
                    tasks.append(normal(http, i))
                    if i % 2 == 0:
                        tasks.append(duplicated(http, i))
                    if i % 3 == 0:
                        tasks.append(impatient(http, i))
                await asyncio.gather(*tasks)

            assert results["error"] == 0, results
            assert results["ok"] == 8 + 2 * 4, results
            # drain: give cancels/credits a beat, then nothing may linger
            await asyncio.sleep(0.3)
            for c in clients:
                assert not c.work_handler.ongoing
                backend = c.work_handler.backend
                live = [
                    j for j in getattr(backend, "_jobs", {}).values()
                    if not j.future.done()
                ]
                assert not live
            assert not server.work_futures
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_over_mqtt_wire():
    """Full flow with the server and worker speaking REAL MQTT 3.1.1 to the
    broker (the reference's native protocol: its hbmqtt server/client and
    Mosquitto would slot into exactly this wire, reference
    server/dpow/mqtt.py, client/dpow_client.py)."""
    from tpu_dpow.transport.mqtt import MqttTransport

    async def main():
        broker = Broker(users=default_users())
        tcp_server = TcpBrokerServer(broker, port=0)
        await tcp_server.start()
        port = tcp_server.port

        config = ServerConfig(
            base_difficulty=EASY_BASE, throttle=1000.0,
            heartbeat_interval=0.05, statistics_interval=3600.0,
            service_port=0, service_ws_port=0, upcheck_port=0, block_cb_port=0,
        )
        store = MemoryStore()
        server = DpowServer(
            config, store,
            MqttTransport(port=port, username="dpowserver", password="dpowserver",
                          client_id="server"),
        )
        runner = ServerRunner(server, config)
        await runner.start()
        await store.hset("service:svc", {"api_key": hash_key("secret"),
                                         "public": "N", "precache": "0",
                                         "ondemand": "0"})
        await store.sadd("services", "svc")

        client = make_client(
            MqttTransport(port=port, username="client", password="client",
                          client_id="w-mqtt", clean_session=False),
            PAYOUT_2,
        )
        await client.setup()
        client.start_loops()
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h,
                               "timeout": 20}
                ) as resp:
                    body = await resp.json()
            assert "work" in body, body
            nc.validate_work(h, body["work"], EASY_BASE)
            # Crediting is deliberately ASYNC after the response: the
            # result handler resolves the waiter's future first, then
            # fans out the QoS-1 cancel (a real PUBACK round trip on this
            # wire) and only then runs the crediting gather — so the HTTP
            # reply routinely lands before the hincrby does. Await the
            # eventual credit instead of racing it.
            credited = None
            for _ in range(100):
                credited = await store.hget(f"client:{PAYOUT_2}", "ondemand")
                if credited is not None:
                    break
                await asyncio.sleep(0.05)
            assert int(credited or 0) == 1
        finally:
            await client.close()
            await runner.stop()
            await tcp_server.stop()

    run(main())


def test_e2e_precache_flood_and_frontier_churn():
    """Precache at scale: a burst of confirmations across many accounts all
    land as instant service hits; a frontier advance retires the stale
    precache (reference dpow_server.py:191-205 semantics) and the retired
    hash falls back to on-demand."""
    import secrets as _secrets

    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(broker, debug=True)
        try:
            # 12 distinct accounts confirm one block each in a burst
            accounts = [nc.encode_account(_secrets.token_bytes(32)) for _ in range(12)]
            hashes = [random_hash() for _ in range(12)]
            for h, acct in zip(hashes, accounts):
                await server.block_arrival_handler(h, acct, None)
            # frontier churn: account 0 confirms a NEWER block on top of its
            # frontier -> the old frontier's precache must be retired
            newer = random_hash()
            await server.block_arrival_handler(newer, accounts[0], hashes[0])
            wanted = hashes[1:] + [newer]

            from tpu_dpow.server.app import WORK_PENDING

            async def settled(h):
                for _ in range(500):
                    w = await store.get(f"block:{h}")
                    if w and w != WORK_PENDING:
                        return w
                    await asyncio.sleep(0.02)
                raise AssertionError(f"precache never landed for {h}")

            works = await asyncio.gather(*(settled(h) for h in wanted))
            for h, w in zip(wanted, works):
                nc.validate_work(h, w, EASY_BASE)
            # every request is now an instant precache hit
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                for h, w in zip(wanted, works):
                    async with http.post(
                        url, json={"user": "svc", "api_key": "secret", "hash": h}
                    ) as resp:
                        body = await resp.json()
                    assert body.get("work") == w, body
                hits = await store.hget("service:svc", "precache")
                assert int(hits) == len(wanted)
                # the retired frontier is no longer precached: a request for
                # it is served on demand (fresh work, ondemand counter)
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": hashes[0]}
                ) as resp:
                    body = await resp.json()
                nc.validate_work(hashes[0], body["work"], EASY_BASE)
                assert int(await store.hget("service:svc", "ondemand") or 0) >= 1
            # drained: no worker still grinding
            await asyncio.sleep(0.2)
            for c in clients:
                assert not c.work_handler.ongoing
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_mqtt_worker_drop_gets_cancel_on_reconnect():
    """QoS-1 redelivery through the REAL client stack: a worker whose MQTT
    connection dies right when the server fans out a cancel must receive
    that cancel on reconnect (durable session + un-PUBACKed salvage) and
    stop grinding the hash. The reference depends on Mosquitto for exactly
    this (reference client/dpow_client.py:143-147)."""
    from tpu_dpow.transport.mqtt import MqttTransport

    async def main():
        broker = Broker(users=default_users())
        tcp_server = TcpBrokerServer(broker, port=0)
        await tcp_server.start()
        port = tcp_server.port

        config = ServerConfig(
            base_difficulty=EASY_BASE, throttle=1000.0,
            heartbeat_interval=0.05, statistics_interval=3600.0,
            service_port=0, service_ws_port=0, upcheck_port=0, block_cb_port=0,
        )
        store = MemoryStore()
        server = DpowServer(
            config, store,
            MqttTransport(port=port, username="dpowserver", password="dpowserver",
                          client_id="server"),
        )
        runner = ServerRunner(server, config)
        await runner.start()

        client = make_client(
            MqttTransport(port=port, username="client", password="client",
                          client_id="w-drop", clean_session=False),
            PAYOUT_1,
        )
        await client.setup()
        client.start_loops()
        try:
            # Hand the worker a hash it can never solve, directly over the
            # work topic (no service request: nothing resolves early).
            hard = random_hash()
            await server.transport.publish(
                "work/ondemand", f"{hard},{(1 << 64) - 1:016x}", qos=0
            )
            for _ in range(100):
                await asyncio.sleep(0.02)
                if hard in client.work_handler.ongoing:
                    break
            assert hard in client.work_handler.ongoing

            # Cut the worker's actual socket with reconnection held off for
            # a few attempts (a real network outage, not a blip): the broker
            # detaches the durable session and the QoS-1 cancel published
            # during the outage lands in its offline queue.
            real_open = client.transport._open
            outage = {"n": 4}

            async def failing_open():
                if outage["n"] > 0:
                    outage["n"] -= 1
                    raise ConnectionError("network down (test)")
                await real_open()

            client.transport._open = failing_open
            client.transport._writer.close()
            session = broker.sessions["w-drop"]
            for _ in range(100):
                await asyncio.sleep(0.02)
                if session.queue is None:
                    break
            assert session.queue is None, "broker never noticed the cut"
            await server.transport.publish("cancel/ondemand", hard, qos=1)
            assert [m.payload for m in session.offline] == [hard]

            # The client's rx loop reconnects on its own (same durable
            # client_id); the queued cancel must arrive and stop the work.
            for _ in range(300):
                await asyncio.sleep(0.02)
                if hard not in client.work_handler.ongoing:
                    break
            assert hard not in client.work_handler.ongoing, (
                "queued QoS-1 cancel never reached the reconnected worker"
            )
        finally:
            await client.close()
            await runner.stop()
            await tcp_server.stop()

    run(main())


def test_e2e_metrics_and_span_chain_for_one_request():
    """Observability acceptance: one in-process HTTP request must
    (a) bump the ondemand request counter, (b) leave a complete span chain
    receive → accept → queue → publish → dispatch → submit → pack → device
    → result → result_in → winner → resolve → reply,
    and (c) surface it all — request-latency histogram, per-stage spans,
    engine batch-occupancy and device-time — as valid Prometheus text on
    GET /metrics of the server upcheck port."""
    from tpu_dpow import obs

    async def main():
        reg = obs.get_registry()
        tracer = obs.get_tracer()
        requests_before = reg.counter(
            "dpow_server_requests_total", labelnames=("work_type",)
        ).value("ondemand")
        stage_hist = reg.histogram(
            "dpow_request_stage_seconds", labelnames=("stage",))
        stage_counts_before = {
            s: stage_hist.count_of(s)
            for s in ("accept", "queue", "publish", "dispatch", "submit",
                      "pack", "device", "result", "result_in", "winner",
                      "resolve", "cancel", "reply")
        }
        broker = Broker()
        runner, server, store, clients = await start_stack(broker, n_clients=1)
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h}
                ) as resp:
                    body = await resp.json()
                assert "work" in body, body

                # (a) the ondemand counter moved by exactly this request
                assert reg.counter(
                    "dpow_server_requests_total", labelnames=("work_type",)
                ).value("ondemand") == requests_before + 1

                # (b) complete span chain for the request's trace
                tid = tracer.id_for(h)
                assert tid is not None
                stages = [s for s, _ in tracer.get(tid)]
                for want in ("receive", "accept", "queue", "publish",
                             "dispatch", "submit", "pack", "device", "result",
                             "result_in", "winner", "resolve", "reply"):
                    assert want in stages, (want, stages)
                assert stages[0] == "receive"
                assert stages.index("accept") < stages.index("publish")
                assert stages.index("publish") < stages.index("result")
                assert stages.index("result") < stages.index("result_in")
                assert stages.index("winner") < stages.index("resolve")
                assert stages.index("resolve") < stages.index("reply")
                # ... and each stage observed into the shared histogram
                for s, before in stage_counts_before.items():
                    assert stage_hist.count_of(s) > before, s

                # (c) the Prometheus surface on the upcheck port
                murl = f"http://127.0.0.1:{runner.ports['upcheck']}/metrics"
                async with http.get(murl) as resp:
                    assert resp.status == 200
                    text = await resp.text()
                parsed = obs.parse_text(text)
                assert any(
                    labels.get("work_type") == "ondemand" and value >= 1
                    for labels, value in parsed["dpow_server_requests_total"]
                )
                # request-latency histogram present and populated
                assert any(
                    labels.get("work_type") == "ondemand" and value >= 1
                    for labels, value in parsed["dpow_server_request_seconds_count"]
                )
                # per-stage spans on the wire
                wire_stages = {
                    labels["stage"]
                    for labels, value in parsed["dpow_request_stage_seconds_count"]
                    if value >= 1
                }
                for want in ("queue", "publish", "dispatch", "device", "result"):
                    assert want in wire_stages, (want, wire_stages)
                # engine metrics through the same registry
                assert any(
                    value >= 1 for _, value in
                    parsed["dpow_engine_batch_occupancy_count"]
                )
                assert any(
                    labels.get("engine") == "jax" and value >= 1
                    for labels, value in parsed["dpow_engine_device_seconds_count"]
                )
                assert any(
                    labels.get("engine") == "jax" and value >= 1
                    for labels, value in parsed["dpow_engine_solutions_total"]
                )
                # machine-readable twin of the same surface
                snap = obs.snapshot()
                assert snap["dpow_server_requests_total"]["series"]["ondemand"] >= 1
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_server_stage_chain_brackets_the_request_latency():
    """The server's stage chain receive → reply breaks down the request's
    dpow_server_request_seconds observation: no longer than it, and at
    least 90% of it."""
    from tpu_dpow import obs

    async def main():
        reg = obs.get_registry()
        tracer = obs.get_tracer()
        latency = reg.histogram(
            "dpow_server_request_seconds", labelnames=("work_type",))

        def observed():
            series = latency.collect().get(("ondemand",))
            return (series["sum"], series["count"]) if series else (0.0, 0)

        broker = Broker()
        runner, server, store, clients = await start_stack(broker, n_clients=1)
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                sum0, n0 = observed()
                async with http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h}
                ) as resp:
                    body = await resp.json()
                assert "work" in body, body
                sum1, n1 = observed()
                assert n1 == n0 + 1
                request_s = sum1 - sum0
                marks = dict(tracer.get(tracer.id_for(h)))
                chain = marks["reply"] - marks["receive"]
                # (1 us: the resolution of a difference of two wall stamps)
                assert 0.9 * request_s <= chain <= request_s + 1e-6, (chain, request_s)
                # ... and the server stages, each timed from its parent, sum
                # to the same chain.
                spans = dict(tracer.spans(tracer.id_for(h)))
                server_chain = sum(spans[s] for s in (
                    "accept", "queue", "publish", "result_in", "winner",
                    "resolve", "reply"))
                assert abs(server_chain - chain) < 1e-6
        finally:
            await stop_stack(runner, clients)

    run(main())


def test_e2e_late_worker_heals_stranded_request():
    """The republish heal at full-stack level: a request POSTs while ZERO
    workers are connected (its QoS-0 work publish fires into the void), a
    worker joins afterwards, and the request completes off a re-publish —
    no client-side retry, no error. The reference strands this request
    until timeout."""

    async def main():
        broker = Broker()
        runner, server, store, clients = await start_stack(
            broker, n_clients=0, work_republish_interval=0.3
        )
        late = None
        try:
            async with aiohttp.ClientSession() as http:
                url = f"http://127.0.0.1:{runner.ports['service']}/service/"
                h = random_hash()
                post = asyncio.ensure_future(http.post(
                    url, json={"user": "svc", "api_key": "secret", "hash": h,
                               "timeout": 15},
                ))
                await asyncio.sleep(0.5)  # original publish long gone
                late = make_client(
                    InProcTransport(broker, client_id="late-worker"), PAYOUT_1
                )
                await late.setup()
                late.start_loops()
                resp = await asyncio.wait_for(post, 20)
                body = await resp.json()
                assert "work" in body, body
                nc.validate_work(h, body["work"], EASY_BASE)
                assert server.work_republished >= 1
        finally:
            await stop_stack(runner, [late] if late else [])

    run(main())
