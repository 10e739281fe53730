"""Closed loop: each client keeps one request outstanding.

Parameters: ``clients`` (count, one service each), ``cls`` (threshold
class), ``service_prefix``, ``timeout`` (seconds), ``judged`` (default
true).

A client sends its first request when traffic starts and its next one the
moment a reply comes, until the window closes; the next request's intended
send time is that moment. A request is in the window when its reply comes
inside it.
"""

from __future__ import annotations


def services(p: dict) -> list:
    return [f"{p.get('service_prefix', 'client')}-{i}" for i in range(int(p["clients"]))]


async def _client(p: dict, ctx, service: str, hashes) -> None:
    from loadgen import new_hash

    intended = ctx.t_start
    judged = bool(p.get("judged", True))
    while intended < ctx.t1:
        await ctx.sleep_until(intended)
        rec = await ctx.fire(service=service, block_hash=new_hash(hashes), cls=p["cls"],
                             intended=intended, judged=judged, timeout=p["timeout"],
                             loop="closed")
        intended = rec["done"] if rec["done"] is not None else ctx.now()


async def run(p: dict, ctx) -> None:
    import asyncio

    names = services(p)
    await asyncio.gather(*(
        _client(p, ctx, name, ctx.rng(f"{name}:hashes")) for name in names))
