"""The benchmark's load generator: a JAX-free child process.

    python3 perfbench/loadgen.py < plan.json > records.json

The plan (one JSON object on stdin) names the traffic mix, the seed, the
service URL, the thresholds by class and the timeline on the machine's
monotonic clock (shared by every process on the host):

    t_start  traffic begins (the short run before the window)
    t0, t1   the measured window
    patience seconds past its send that a reply may take before it counts
             as never come (the request's own timeout plus a minute)

Requests are ``POST /service/`` with ``difficulty`` in hex. Every request is
recorded with its intended send time, its send time and its reply; the
latency of a request is taken from its intended send, so a stall that delays
later sends is counted against them. The generator's own lag (send minus
intended) and its longest garbage-collection pause are reported beside the
records.

Arrivals are drawn from the seed: the same seed gives the same requests.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import random
import sys
import time

import aiohttp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import catalog  # noqa: E402


def api_key(service: str) -> str:
    return f"key-{service}"


def services_of(traffic: dict) -> list:
    """Every service name a traffic mix sends as (for registration)."""
    return list(catalog.generator(traffic["kind"]).services(traffic))


class Context:
    """What a generator kind is given: the clock, the seed, the thresholds
    and the one way to send a request."""

    def __init__(self, plan: dict, session: aiohttp.ClientSession):
        self.plan = plan
        self.seed = plan["seed"]
        self.t_start, self.t0, self.t1 = plan["t_start"], plan["t0"], plan["t1"]
        self.thresholds = {k: int(v, 16) for k, v in plan["thresholds"].items()}
        self.url = plan["url"]
        self.patience = float(plan["patience"])
        self.session = session
        self.records = []
        self.tasks = []

    def rng(self, part: str) -> random.Random:
        return random.Random(f"{self.seed}:{part}")

    @staticmethod
    def now() -> float:
        return time.monotonic()

    async def sleep_until(self, t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            await asyncio.sleep(dt)

    def fire(self, **kw) -> asyncio.Task:
        """Send one request now, without waiting for its reply."""
        task = asyncio.ensure_future(self.post(**kw))
        self.tasks.append(task)
        return task

    async def post(self, *, service: str, block_hash: str, cls: str,
                   intended: float, judged: bool, timeout: int, loop: str) -> dict:
        threshold = self.thresholds[cls]
        rec = {
            "service": service, "hash": block_hash, "cls": cls,
            "threshold": f"{threshold:016x}", "judged": judged, "loop": loop,
            "intended": intended, "sent": time.monotonic(), "done": None,
            "status": None, "work": None, "reply": None,
        }
        self.records.append(rec)
        body = {"user": service, "api_key": api_key(service), "hash": block_hash,
                "difficulty": rec["threshold"], "timeout": int(timeout)}
        try:
            async with self.session.post(
                self.url, json=body,
                timeout=aiohttp.ClientTimeout(total=self.patience),
            ) as r:
                text = await r.text()
            rec["done"] = time.monotonic()
            try:
                reply = json.loads(text)
            except ValueError:
                reply = {"error": f"HTTP {r.status}: {text[:200]}"}
            if r.status == 200 and isinstance(reply, dict) and "work" in reply:
                rec["status"], rec["work"] = "work", str(reply["work"])
            else:
                rec["status"] = "busy" if r.status == 429 else "error"
                rec["reply"] = str(reply)[:200]
        except asyncio.TimeoutError:
            rec["status"], rec["reply"] = "never", f"no reply within {self.patience:.0f}s"
        except aiohttp.ClientError as e:
            rec["done"] = time.monotonic()
            rec["status"], rec["reply"] = "error", f"{type(e).__name__}: {e}"[:200]
        return rec


class GcPauses:
    """The longest pause of this process's garbage collector, by generation."""

    def __init__(self):
        self.max_s = 0.0
        self.max_gen = None
        self._t = None
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            if dt > self.max_s:
                self.max_s, self.max_gen = dt, info.get("generation")


def new_hash(rng: random.Random) -> str:
    return rng.getrandbits(256).to_bytes(32, "big").hex().upper()


async def main_async(plan: dict) -> dict:
    traffic = plan["traffic"]
    gen = catalog.generator(traffic["kind"])
    pauses = GcPauses()
    conn = aiohttp.TCPConnector(limit=0, force_close=False)
    async with aiohttp.ClientSession(connector=conn) as session:
        ctx = Context(plan, session)
        await gen.run(traffic, ctx)
        # Every request sent so far gets its reply or its patience.
        while True:
            pending = [t for t in ctx.tasks if not t.done()]
            if not pending:
                break
            await asyncio.gather(*pending)
    lags = [r["sent"] - r["intended"] for r in ctx.records]
    return {
        "records": ctx.records,
        "lag_max_s": max(lags, default=0.0),
        "gc_pause_max_s": pauses.max_s,
        "gc_pause_max_gen": pauses.max_gen,
    }


def main() -> int:
    plan = json.load(sys.stdin)
    out = asyncio.run(main_async(plan))
    json.dump(out, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
