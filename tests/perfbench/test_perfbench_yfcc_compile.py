"""yfcc-192-l2-tags: the programs the deployment runs, compiled by the real
TPU compiler for a described v5e at the configuration's own shapes (2^21 rows
of 192 components, groups of 256 slots). Nothing runs: this says what the
compiler accepts, what it allocates and which copies it plans, nothing about
answers or times. 192 components are one and a half lanes: the compiler keeps
such a store column-major and copies the WHOLE slab in front of every row
gather, which is why the per-slot programs read a lane-padded twin
(index/tpu.py `_row_store`); the tests hold both halves of that."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib.spec import Spec

HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip's allocator offers
GROUP = 256                        # slots of the cell's BatchSearch


@pytest.fixture(scope="module")
def cfg():
    return Spec().config("yfcc-192-l2-tags")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _cap(cfg) -> int:
    cap = 16384
    while cap < int(cfg["rows"]) + 8192:
        cap *= 2
    return cap


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _slab_copies(compiled, cap: int) -> list[str]:
    """The ops that copy or transpose an array of `cap` rows."""
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.search(rf"= f32\[{cap},\d+\]\S* (copy|transpose)\(", line)]


def _gather(sh, cap, width, r, k):
    from weaviate_tpu.index import tpu

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    s_pad = tpu._gather_slots(tpu._bucket_b(GROUP), r)
    return tpu._search_gathered_multi.lower(
        S((cap, width), jnp.float32), S((s_pad, width), jnp.float32),
        S((s_pad, r), jnp.int32), S((s_pad,), jnp.int32), S((), jnp.int32),
        S((cap,), jnp.bool_), S((cap,), jnp.uint32), S((cap,), jnp.uint32),
        k=k, metric="l2-squared",
        step=min(tpu._gather_step_slots(r, width), s_pad)).compile()


def test_every_row_bucket_of_the_per_slot_gather_compiles_without_a_slab_copy(
        one_chip, cfg):
    from weaviate_tpu.index import tpu

    cap, dim, k = _cap(cfg), int(cfg["dim"]), int(cfg["k"])
    assert (cap, dim) == (2 ** 21, 192)
    width = -(-dim // 128) * 128
    top, r, buckets = tpu.gather_max_rows(dim, cap), tpu._GATHER_MIN_ROWS, []
    while r <= top:
        buckets.append(r)
        r *= 4
    assert buckets == [128, 512, 2048, 8192, 32768]
    for r in buckets:
        compiled = _gather(one_chip, cap, width, r, k)
        assert not _slab_copies(compiled, cap), r
        assert _device_bytes(compiled) < HBM_BYTES // 2
        # the gathered block of one loop step, not of the whole group
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= 2 * tpu._GATHER_BLOCK_BYTES


def test_a_192_wide_store_is_copied_whole_before_a_gather(one_chip, cfg):
    """What the twin is for. If this stops holding (a compiler that gathers
    rows from the column-major slab in place), `_row_store` can go."""
    cap, dim = _cap(cfg), int(cfg["dim"])
    compiled = _gather(one_chip, cap, dim, 128, int(cfg["k"]))
    store = [line for line in compiled.as_text().splitlines()
             if "parameter(0)" in line and 'op_name="store"' in line]
    assert len(store) == 1 and f"f32[{cap},{dim}]{{0,1:" in store[0]  # column-major
    assert _slab_copies(compiled, cap)
    assert compiled.memory_analysis().temp_size_in_bytes > cap * dim * 4


@pytest.mark.parametrize("queries", [128, 256])
def test_the_groups_masked_scan_compiles_without_a_slab_copy(
        one_chip, cfg, queries):
    """One lax.scan program, every query under its own [capacity / 32]
    words, over the lane-padded twin."""
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS
    from weaviate_tpu.index import tpu

    cap, dim, k = _cap(cfg), int(cfg["dim"]), int(cfg["k"])
    width = -(-dim // 128) * 128
    # the two shapes a group of the cell's width can compile
    assert {tpu._scan_group_bucket(n, 256) for n in range(1, 257)} \
        == {128, 256}
    assert tpu._scan_group_bucket(queries - 1, 256) == queries
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = tpu._search_full_fused.lower(
        S((cap, width), jnp.float32), S((cap,), jnp.float32),
        S((cap,), jnp.bool_), S((), jnp.int32),
        S((queries, width), jnp.float32), S((queries, cap // 32), jnp.uint32),
        S((cap, 2), jnp.uint32), k=k, metric="l2-squared", use_allow=True,
        exact=False, active_chunks=-(-int(cfg["rows"]) // tpu._SCAN_CHUNK),
        rescore_r=min(max(4 * k, RESCORE_R_BUCKETS[0]),
                      RESCORE_R_BUCKETS[-1])).compile()
    assert not _slab_copies(compiled, cap)
    assert _device_bytes(compiled) < HBM_BYTES // 2
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("batch", [256, 8])
def test_the_unfiltered_scan_at_192_is_taken_by_mosaic(one_chip, cfg, batch):
    """No rejected kernel shape at one and a half lanes: the plan admits
    192-d and Mosaic compiles the gmin kernel at the widths a dispatch is
    padded to."""
    from weaviate_tpu.ops import gmin_scan

    cap, dim, rows = _cap(cfg), int(cfg["dim"]), int(cfg["rows"])
    ncols = cap // gmin_scan.G
    active_g = -(-rows // ncols)
    assert gmin_scan.fits_vmem(batch, dim, ncols, active_g, 4)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = gmin_scan.search_gmin_fused.lower(
        S((cap, dim), jnp.float32), S((cap,), jnp.float32),
        S((cap,), jnp.bool_), S((), jnp.int32), S((batch, dim), jnp.float32),
        S((cap // 32,), jnp.uint32), S((cap, 2), jnp.uint32),
        use_allow=False, k=int(cfg["k"]), metric="l2-squared", rg=32,
        active_g=active_g, interpret=False,
        rescore_blk=S((ncols, gmin_scan.G * dim), jnp.float32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()
