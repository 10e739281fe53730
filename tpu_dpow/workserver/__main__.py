"""``python -m tpu_dpow.workserver [--listen 127.0.0.1:7000] [--backend jax]``

Drop-in replacement for the reference's vendored nano-work-server binary
(reference client/README.md:31 launches it as
``nano-work-server --gpu 0:0 -l 127.0.0.1:7000``): same HTTP JSON-RPC
surface, compute from this framework's TPU/native engines.
"""

from __future__ import annotations

import argparse
import asyncio
import logging

from ..backend import get_backend
from . import WorkServer


async def amain(argv=None) -> None:
    p = argparse.ArgumentParser("tpu-dpow work server")
    p.add_argument("--listen", "-l", default="127.0.0.1:7000", help="host:port")
    p.add_argument("--backend", default="jax", choices=["jax", "native"])
    p.add_argument("--threads", type=int, default=None,
                   help="native backend thread count")
    p.add_argument("--mesh_devices", type=int, default=0,
                   help="gang N local devices per hash; 0 = plain "
                   "single-device path (backend=jax)")
    p.add_argument("--verbose", action="store_true")
    ns = p.parse_args(argv)
    logging.basicConfig(level=logging.DEBUG if ns.verbose else logging.INFO)

    from ..utils import enable_compilation_cache, maybe_init_distributed

    enable_compilation_cache()
    maybe_init_distributed()

    host, _, port_str = ns.listen.rpartition(":")
    if not port_str.isdigit():
        p.error(f"--listen must be host:port, got {ns.listen!r}")
    # IPv6 literals arrive bracketed ('[::1]:7000' — the node RPC default
    # elsewhere is 'http://[::1]:7076'); getaddrinfo wants them bare.
    host = host.strip("[]")
    kwargs = {"threads": ns.threads} if ns.backend == "native" and ns.threads else {}
    if ns.backend == "jax" and ns.mesh_devices > 0:
        kwargs["mesh_devices"] = ns.mesh_devices
    server = WorkServer(
        get_backend(ns.backend, **kwargs), host or "127.0.0.1", int(port_str)
    )
    await server.start()
    try:
        await asyncio.Event().wait()
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await server.stop()


def main(argv=None) -> None:
    try:
        asyncio.run(amain(argv))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
