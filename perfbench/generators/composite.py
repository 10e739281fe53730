"""Several mixes at once: ``parts`` is a list of traffic objects, each with
its own ``kind``; they run side by side on one clock and one seed, each part
drawing from its own stream of it."""

from __future__ import annotations

import asyncio


def services(p: dict) -> list:
    import catalog

    out = []
    for part in p["parts"]:
        out += [s for s in catalog.generator(part["kind"]).services(part) if s not in out]
    return out


async def run(p: dict, ctx) -> None:
    import catalog

    await asyncio.gather(*(
        catalog.generator(part["kind"]).run(part, ctx) for part in p["parts"]))
