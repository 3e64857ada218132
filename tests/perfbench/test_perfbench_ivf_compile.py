"""`cohere-768-cos-ivf.single-c20`'s programs, compiled by the real TPU
compiler for a described v5e at the cell's real shapes (the way of
test_perfbench_compile.py: nothing runs, so this says nothing about answers
or times): the probed program over a store of 4,096 tiles x 352 slots x 768
float32 for ONE query probing 64 tiles, and the three programs of a
training at the last recluster's size. The probed program must read its
tiles IN PLACE: a dynamic slice of the whole store inside the loop's
distance fusion, no gather of rows, no tile-sized (let alone slab-sized)
temporary; the training must fit beside the slab it lays out anew."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip's allocator offers
NLIST, CAP_P, DIM, TOP_P, K = 4096, 352, 768, 64, 10
SLOTS = NLIST * CAP_P              # 1,441,792: eleven scan chunks
TILE_BYTES = CAP_P * DIM * 4


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
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _probed(one_chip, batch, use_allow=False):
    from weaviate_tpu.ops import ivf

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return ivf.search_ivf_tiles_fused.lower(
        S((SLOTS, DIM), jnp.float32), S((SLOTS,), jnp.bool_),
        S((batch, DIM), jnp.float32),
        S((SLOTS // 32,), jnp.uint32), S((NLIST, DIM), jnp.float32),
        S((SLOTS, 2), jnp.uint32), k=K, metric="cosine",
        use_allow=use_allow, top_p=TOP_P, cap_p=CAP_P).compile()


def test_the_capacity_of_the_layout_is_its_slots():
    from weaviate_tpu.index import tpu
    from weaviate_tpu.ops import ivf

    # the import's last layout is sized for 0.82M to 1.0M rows
    assert ivf.tile_capacity(937_504, NLIST) == CAP_P
    assert ivf.tile_capacity(1_000_000, NLIST) == CAP_P
    assert tpu._fit_capacity(SLOTS) == SLOTS == 11 * tpu._SCAN_CHUNK
    # the slots never pass 1.58 times the rows they were sized for
    for rows in (20_000, 123_456, 769_000, 1_000_000, 2_500_000):
        assert NLIST * ivf.tile_capacity(rows, NLIST) <= max(
            1.125 * 1.25 * 1.125 * rows, NLIST * 128)
    assert SLOTS * DIM * 4 / 2 ** 34 == pytest.approx(0.2578125)  # of 16 GiB


@pytest.mark.parametrize("use_allow", [False, True])
def test_one_query_reads_its_tiles_in_place(one_chip, use_allow):
    compiled = _probed(one_chip, 1, use_allow)
    text = compiled.as_text()
    # the loop's distance fusion takes the tile as a dynamic slice of the
    # WHOLE store: nothing is copied out of the slab first
    assert re.search(
        rf"f32\[{CAP_P},{DIM}\][^\n]* dynamic-slice\([^\n]*"
        rf"dynamic_slice_sizes=\{{{CAP_P},{DIM}\}}", text), \
        "no tile-shaped dynamic slice of the store"
    # no gather of store rows anywhere, and no tile-sized temporary
    assert not re.search(rf"f32\[[0-9,]*{DIM}\][^\n]* gather\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < TILE_BYTES


def test_a_small_batch_holds_no_more_than_its_tiles(one_chip):
    mem = _probed(one_chip, 4).memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 2 * TILE_BYTES


def test_a_training_fits_beside_the_slab_it_replaces(one_chip):
    from weaviate_tpu.index import tpu
    from weaviate_tpu.ops import ivf

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    old_cap = 10 * 131072  # the layout before it: 4,096 x 288 slots
    store = S((old_cap, DIM), jnp.float32)
    fit = ivf.kmeans_fit_device.lower(
        S((65536, DIM), jnp.float32), S((NLIST,), jnp.int32), iters=6,
        normalize=True).compile().memory_analysis()
    assign = ivf.nearest_partitions_device.lower(
        store, S((NLIST, DIM), jnp.float32),
        prefs=ivf.PREFS).compile().memory_analysis()
    relayout = tpu._relayout.lower(
        store, S((SLOTS,), jnp.int32)).compile().memory_analysis()
    assert relayout.output_size_in_bytes == SLOTS * DIM * 4
    assert relayout.temp_size_in_bytes < TILE_BYTES
    slab = old_cap * DIM * 4
    for m in (fit, assign):
        # a sample or a block of distances, never the slab again
        assert m.temp_size_in_bytes < slab // 4
    assert slab + relayout.output_size_in_bytes + max(
        fit.temp_size_in_bytes, assign.temp_size_in_bytes) < HBM_BYTES
