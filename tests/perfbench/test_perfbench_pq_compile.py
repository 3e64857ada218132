"""`cohere-768-cos-pq.batch256`'s search programs, compiled by the real TPU
compiler for a described v5e at the cell's real shapes (the way of
test_perfbench_compile.py: nothing runs, so this says nothing about answers
or times), over the bf16 copy of 2^21 x 768 rows, 256 queries, returning 40
candidates a query with their slots for the host's float32 rescoring. At 2 B
a component the Pallas group-min kernel takes this width (at 4 B it is
refused: `cohere-768-cos` runs the lax.scan program), with a block-laid
copy of the slab beside it; the lax.scan program is what serves where the
kernel is refused or broken. Each must fit beside the slab, the lax.scan
program with no slab-sized temporary (a bf16 slab re-laid or widened a
dispatch would be 3.2 to 6.4 GB), and neither gathers float32 rows on the
device: those are the host's."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip's allocator offers
K = 10
CAP, DIM, ROWS, BATCH = 2 ** 21, 768, 2_000_000, 256


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


@pytest.fixture(scope="module")
def compiled(one_chip):
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS
    from weaviate_tpu.index import tpu

    r = min(max(4 * K, RESCORE_R_BUCKETS[0]), RESCORE_R_BUCKETS[-1])
    assert r == 40
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return tpu._search_full_fused.lower(
        S((CAP, DIM), jnp.bfloat16), None, S((CAP,), jnp.bool_),
        S((), jnp.int32), S((BATCH, DIM), jnp.float32),
        S((CAP // 32,), jnp.uint32), S((CAP, 2), jnp.uint32),
        k=K, metric="cosine", use_allow=False, exact=False,
        active_chunks=-(-ROWS // tpu._SCAN_CHUNK), rescore_r=r,
        candidates=True).compile()


def test_the_group_min_kernel_takes_this_width_and_fits(one_chip):
    from weaviate_tpu.ops import gmin_scan

    ncols = CAP // gmin_scan.G
    active_g = -(-ROWS // ncols)
    assert gmin_scan.fits_vmem(BATCH, DIM, ncols, active_g, 2)
    assert not gmin_scan.fits_vmem(BATCH, DIM, ncols, active_g, 4)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = gmin_scan.search_gmin_fused.lower(
        S((CAP, DIM), jnp.bfloat16), None, S((CAP,), jnp.bool_),
        S((), jnp.int32), S((BATCH, DIM), jnp.float32),
        S((CAP // 32,), jnp.uint32), S((CAP, 2), jnp.uint32),
        use_allow=False, k=40, metric="cosine", rg=32, active_g=active_g,
        interpret=False,
        rescore_blk=S((ncols, gmin_scan.G * DIM), jnp.bfloat16),
        with_slots=True).compile()
    m = compiled.memory_analysis()
    slab = CAP * DIM * 2
    assert m.argument_size_in_bytes >= 2 * slab      # the slab and its blocks
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < HBM_BYTES
    assert m.temp_size_in_bytes < slab // 4, m.temp_size_in_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text                  # Mosaic took it
    assert re.search(r"ENTRY[^\n]*->\s*s32\[256,160\]", text), text[:2000]


def test_pq_scan_compiles_and_fits_beside_the_slab(compiled):
    m = compiled.memory_analysis()
    slab = CAP * DIM * 2
    assert m.argument_size_in_bytes >= slab
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < HBM_BYTES
    # one [256, 131072] f32 distance block a step is 128 MB; a temporary of
    # a quarter of the slab would be the slab re-laid or widened
    assert m.temp_size_in_bytes < slab // 4, m.temp_size_in_bytes
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas kernel


def test_pq_scan_returns_candidates_and_gathers_no_row(compiled):
    text = compiled.as_text()
    # [256, 4 x 40] int32: distances, the doc ids' two words, the slots
    assert re.search(r"ENTRY[^\n]*->\s*s32\[256,160\]", text), \
        text[:2000]
    # no [256, 40, 768] block of gathered rows in any precision
    assert "[256,40,768]" not in text
