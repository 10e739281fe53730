"""Put the repo root on sys.path so `python benchmarks/x.py` finds tpu_dpow.

Scripts import this as their first import; the script's own directory is
sys.path[0], so `import _bootstrap` resolves here without the repo root.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Every bench runs as its own process, and each distinct launch shape is
# its own compile. Share the one persistent cache (tpu_dpow.utils); every
# bench warms before timing, so a cache hit removes warmup cost, never the
# measured path. Configured via env: pure-host benches never import jax,
# and children inherit it.
from tpu_dpow.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache(min_compile_secs=0.5)


async def start_full_stack(debug: bool = False, backend_factory=None,
                           base_difficulty=None):
    """In-process full stack for the e2e benches (flood, precache).

    Broker + server + HTTP runner + one worker client on the jax backend,
    registered under service credentials bench/bench and warmed. One copy on
    purpose: the two benches measuring the same stack must not drift apart
    in how they configure it. Caller tears down with
    ``await stack.client.close(); await stack.runner.stop()``.

    ``debug=True`` makes every confirmed block precache-eligible
    (server/app.py block_arrival_handler) without seeding frontiers first.
    ``backend_factory`` and ``base_difficulty`` (gang_e2e, which grades
    the virtual CPU mesh) override the worker backend and the server's
    base threshold while keeping every other stack knob identical. Without
    a factory the worker is the engine at its defaults, and JAX must find
    a TPU: there is no CPU fallback.
    """
    from types import SimpleNamespace

    import jax

    from tpu_dpow.backend.jax_backend import JaxWorkBackend
    from tpu_dpow.client import ClientConfig, DpowClient
    from tpu_dpow.server import DpowServer, ServerConfig, hash_key
    from tpu_dpow.server.api import ServerRunner
    from tpu_dpow.store import MemoryStore
    from tpu_dpow.transport import default_users
    from tpu_dpow.transport.broker import Broker
    from tpu_dpow.transport.inproc import InProcTransport
    from tpu_dpow.utils import nanocrypto as nc

    dev = jax.devices()[0]
    if backend_factory is None and dev.platform != "tpu":
        raise RuntimeError(
            f"the e2e benches measure the chip; JAX found {dev.platform!r}")
    config = ServerConfig(
        base_difficulty=base_difficulty or nc.BASE_DIFFICULTY,
        throttle=100000.0,
        heartbeat_interval=0.5,
        statistics_interval=3600.0,
        default_timeout=30.0,
        debug=debug,
        service_port=0, service_ws_port=0, upcheck_port=0, block_cb_port=0,
    )
    broker = Broker(users=default_users())
    store = MemoryStore()
    server = DpowServer(
        config, store,
        InProcTransport(broker, client_id="server",
                        username="dpowserver", password="dpowserver"),
    )
    runner = ServerRunner(server, config)
    await runner.start()
    await store.hset(
        "service:bench",
        {"api_key": hash_key("bench"), "public": "N", "display": "bench",
         "website": "", "precache": "0", "ondemand": "0"},
    )
    await store.sadd("services", "bench")

    backend = backend_factory() if backend_factory is not None else JaxWorkBackend()
    client = DpowClient(
        ClientConfig(payout_address=nc.encode_account(bytes(range(32))),
                     startup_heartbeat_wait=3.0),
        InProcTransport(broker, client_id="worker", clean_session=False,
                        username="client", password="client"),
        backend=backend,
    )
    await client.setup()
    client.start_loops()
    await wait_for_warmup(backend, timeout=360)
    return SimpleNamespace(
        runner=runner, store=store, server=server, client=client,
        backend=backend, on_tpu=dev.platform == "tpu", ports=runner.ports,
        base_difficulty=config.base_difficulty,
    )


async def wait_for_warmup(backend, timeout: float = 600.0) -> None:
    """Block until the backend's launch-shape warm task finishes (if any).

    Steady-state benchmarks call this after setup so batched launches run at
    their real width instead of measuring XLA compile queueing. A warm-up
    that does not finish, or leaves a rung of the ladder cold, is an error:
    what would be measured then is compiling.
    """
    import asyncio

    warm_task = getattr(backend, "_warm_task", None)
    if warm_task is None:
        return
    await asyncio.wait_for(asyncio.shield(warm_task), timeout=timeout)
    cold = backend.cold_shapes()
    if cold:
        raise RuntimeError(f"warm-up left launch shapes uncompiled: {cold}")


def drain_solves(backend, counter) -> None:
    """Fold the timeline's solve records into ``counter`` and clear it.

    Benchmarks reporting launches-per-solve histograms call this after each
    measured request: the engine's timeline deque is bounded (maxlen 1024),
    so reading it only at the end silently evicts early solves on large
    runs. No-op for backends without a timeline (native).
    """
    tl = getattr(backend, "timeline", None)
    if tl is None:
        return
    counter.update(
        t["launches"] for kind, t in tl if kind == "solve" and "launches" in t
    )
    tl.clear()
