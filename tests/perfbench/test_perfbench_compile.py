"""Each cell's search program, compiled by the real TPU compiler for a
described v5e at the cell's real shapes: what the chip's compiler would
refuse (VMEM, HBM, tiling) is refused here, at no chip time. Nothing runs, so
this says nothing about answers or times. The topology is described inside a
module-scoped fixture, never at import (on-chip-measurement guide, 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip's allocator offers
K = 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _rescore_r(k: int) -> int:
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS

    return min(max(4 * k, RESCORE_R_BUCKETS[0]), RESCORE_R_BUCKETS[-1])


def _legacy_scan(sh, batch, cap, dim, rows):
    from weaviate_tpu.index import tpu

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    return tpu._search_full_fused.lower(
        S((cap, dim), jnp.float32), None, S((cap,), jnp.bool_),
        S((), jnp.int32), S((batch, dim), jnp.float32),
        S((cap // 32,), jnp.uint32), S((cap, 2), jnp.uint32),
        k=K, metric="cosine", use_allow=False, exact=False,
        active_chunks=-(-rows // tpu._SCAN_CHUNK),
        rescore_r=_rescore_r(K)).compile()


@pytest.mark.parametrize("batch", [256, 1])
def test_cohere_768_legacy_scan_compiles_and_fits(one_chip, batch):
    """cohere-768-cos: gmin is refused at d=768 (fits_vmem), so the legacy
    lax.scan program serves BatchSearch of 256 and Search alike."""
    from weaviate_tpu.ops import gmin_scan

    cap, dim, rows = 2 ** 20, 768, 1_000_000
    ncols = cap // gmin_scan.G
    assert not gmin_scan.fits_vmem(256, dim, ncols, -(-rows // ncols), 4)
    compiled = _legacy_scan(one_chip, batch, cap, dim, rows)
    assert _device_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas kernel


def test_sift_128_gmin_compiles_and_fits(one_chip):
    """sift-128-l2.batch256: the Pallas gmin kernel at capacity 2^22, with
    the block-laid copy of the store it rescoring reads."""
    from weaviate_tpu.ops import gmin_scan

    cap, dim, rows, batch = 2 ** 22, 128, 4_000_000, 256
    ncols = cap // gmin_scan.G
    active_g = -(-rows // ncols)
    assert gmin_scan.fits_vmem(batch, dim, ncols, active_g, 4)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = gmin_scan.search_gmin_fused.lower(
        S((cap, dim), jnp.float32), S((cap,), jnp.float32),
        S((cap,), jnp.bool_), S((), jnp.int32), S((batch, dim), jnp.float32),
        S((cap // 32,), jnp.uint32), S((cap, 2), jnp.uint32),
        use_allow=False, k=K, metric="l2-squared", rg=32, active_g=active_g,
        interpret=False,
        rescore_blk=S((ncols, gmin_scan.G * dim), jnp.float32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()       # Mosaic took it


def test_cohere_768_mesh_program_compiles_and_fits(topo):
    """cohere-768-cos-4m-mesh4: one shard_map program over four chips, 2^20
    rows a chip; the bytes are per device."""
    from weaviate_tpu.parallel import mesh_search as ms

    n_dev, n_loc, dim, batch = 4, 2 ** 20, 768, 256
    mesh = Mesh(topo.devices[:n_dev], (ms.SHARD_AXIS,))
    sharded = lambda *rest: NamedSharding(mesh, P(ms.SHARD_AXIS, *rest))  # noqa: E731
    rep = NamedSharding(mesh, P())
    S = jax.ShapeDtypeStruct
    cap = n_dev * n_loc
    compiled = ms.mesh_search_step.lower(
        S((cap, dim), jnp.float32, sharding=sharded(None)),
        S((cap,), jnp.float32, sharding=sharded()),
        S((cap,), jnp.bool_, sharding=sharded()),
        S((n_dev,), jnp.int32, sharding=rep),
        S((cap // 32,), jnp.uint32, sharding=sharded()),
        S((batch, dim), jnp.float32, sharding=rep),
        S((cap, 2), jnp.uint32, sharding=sharded(None)),
        k=K, metric="cosine", use_allow=False, use_norms=False, exact=False,
        fused=True, mesh=mesh).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text or \
        "collective-permute" in text
