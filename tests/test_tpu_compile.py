"""The main path's kernels, compiled for a described TPU v5e at the
engine's default geometry — what the chip's compiler would refuse, caught
here without a chip (on-chip-measurement guide, section 2).

Nothing runs: these are compiles, not chip runs. The topology is described
inside a fixture, never at import, so every xdist worker collects the same
tests and only the worker given this file loads libtpu.

Every compile passes ``unroll=True`` (or steers the default): the rolled
kernel body crashes the TPU compiler with a stack overflow that kills the
process, and ``_default_unroll`` picks the rolled body here because this
process's default backend is the CPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tpu_dpow.ops import pallas_kernel, runloop
from tpu_dpow.ops.search import PARAMS_LEN

# The engine's defaults (backend/jax_backend.py JaxWorkBackend.__init__).
GEOMETRY = dict(sublanes=32, iters=1024, nblocks=8, group=8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


def _params(batch, sharding):
    return jax.ShapeDtypeStruct((batch, PARAMS_LEN), jnp.uint32, sharding=sharding)


def _count(sharding):
    """A runtime grid bound: a row or window count, an int32 operand."""
    return jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("windows", ["loop", "runtime"])
def test_pallas_chunk_batch_compiles(one_chip, batch, windows):
    """Every launch's row and window counts are int32 operands. ``loop``:
    the run loop's per-window program, its window bound nblocks.
    ``runtime``: the engine's chunked launch, its window bound run_steps
    (16) launches of nblocks windows."""
    geometry = dict(GEOMETRY)
    if windows == "runtime":
        geometry["nblocks"] *= 16
    compiled = pallas_kernel.pallas_search_chunk_batch.lower(
        _params(batch, one_chip), _count(one_chip), _count(one_chip),
        unroll=True, **geometry,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_persistent_controlled_run_compiles(one_chip):
    compiled = runloop.search_run_batch_controlled.lower(
        _params(1, one_chip),
        jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        max_steps=16, poll_steps=8, kernel="pallas", unroll=True, **GEOMETRY,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bound", ["window", "run_steps"])
def test_mesh_gang_compiles_on_four_chips(topo, monkeypatch, bound):
    """``window``: the bound of one launch's nblocks windows (what
    sharded_search_run launches). ``run_steps``: the engine's chunked mesh
    launch, its bound run_steps launches, capped as the engine caps it so
    the four shards' offsets stay below 2^31."""
    from tpu_dpow.parallel import BATCH_AXIS, make_mesh, sharded_search_chunk_batch

    # The gang has no unroll argument: steer the default it would take on
    # a chip (this process's default backend is the CPU).
    monkeypatch.setattr(pallas_kernel, "_default_unroll", lambda interpret: True)
    mesh = make_mesh(topo.devices[:4])
    geometry = dict(GEOMETRY)
    chunk = pallas_kernel.chunk_size(geometry["sublanes"], geometry["iters"])
    chunk *= geometry["nblocks"]
    if bound == "run_steps":
        steps = min(16, ((1 << 31) - 1) // (chunk * 4))
        geometry["nblocks"] *= steps
        chunk *= steps
    compiled = sharded_search_chunk_batch.lower(
        _params(16, NamedSharding(mesh, P(BATCH_AXIS, None))),
        _count(NamedSharding(mesh, P())),
        _count(NamedSharding(mesh, P())),
        mesh=mesh, chunk_per_shard=chunk, kernel="pallas", **geometry,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the pmin winner election
    assert len(np.unique([d.id for d in mesh.devices.flat])) == 4
