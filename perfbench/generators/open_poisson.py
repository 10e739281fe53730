"""Open loop: requests arrive on a schedule, whatever the replies do.

Parameters: ``rate`` (requests/s), ``cls`` (threshold class), ``services``
(count), ``service_prefix``, ``zipf_s`` (service popularity), ``timeout``
(seconds, sent with each request), ``judged`` (default true),
``stratum_s`` (default: the whole span).

Arrivals are Poisson in shape, but every seed gets the same set of gaps in
another order. The window is cut into strata of about ``stratum_s``
seconds; each stratum holds exactly ``round(rate * its length)`` arrivals,
and its gaps are the exponential distribution's quantiles at
``(i + 0.5) / n``, scaled to fill the stratum and shuffled by the seed. The
short run before the window gets its own such strata. So a seed changes
which gap comes when inside a stratum, not how much work a window offers,
nor how much of it lands in any one stratum: a surge longer than a stratum
is another mix's business.
"""

from __future__ import annotations

import math


def services(p: dict) -> list:
    return [f"{p.get('service_prefix', 'svc')}-{i}" for i in range(int(p["services"]))]


def gaps(n: int, span: float, rng) -> list:
    """``n`` shuffled exponential-quantile gaps summing to ``span``."""
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(q)
    scale = span / sum(q)
    return [g * scale for g in q]


def arrivals(rate: float, start: float, span: float, rng, stratum: float | None = None) -> list:
    k = max(1, round(span / stratum)) if stratum else 1
    piece = span / k
    n = max(1, round(rate * piece))
    out = []
    for j in range(k):
        t = start + j * piece
        for g in gaps(n, piece, rng):
            out.append(t)
            t += g
    return out


async def run(p: dict, ctx) -> None:
    from loadgen import new_hash

    rng = ctx.rng(p.get("service_prefix", "svc") + ":arrivals")
    pick = ctx.rng(p.get("service_prefix", "svc") + ":services")
    hashes = ctx.rng(p.get("service_prefix", "svc") + ":hashes")
    names = services(p)
    s = float(p.get("zipf_s", 0.0))
    weights = [1.0 / (k + 1) ** s for k in range(len(names))]
    rate = float(p["rate"])
    stratum = p.get("stratum_s")
    times = (arrivals(rate, ctx.t_start, ctx.t0 - ctx.t_start, rng, stratum)
             if ctx.t0 > ctx.t_start else [])
    times += arrivals(rate, ctx.t0, ctx.t1 - ctx.t0, rng, stratum)
    judged = bool(p.get("judged", True))
    for t in times:
        service = pick.choices(names, weights)[0]
        block_hash = new_hash(hashes)
        await ctx.sleep_until(t)
        ctx.fire(service=service, block_hash=block_hash, cls=p["cls"], intended=t,
                 judged=judged, timeout=p["timeout"], loop="open")
