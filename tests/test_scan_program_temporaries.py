"""The legacy scan's program makes no slab-sized temporary: compiled by the
real TPU compiler for a described v5e, at the 768-d deployment's real shapes,
every step takes its chunk from the f32 slab in place and the rounding to bf16
happens inside the step's matmul. Two things hold that together and neither
does alone (index/tpu.py _scan_full, _TPU_SCAN_OPTIONS): the loop indexes the
whole slab (a static `store[:ext]` prefix is copied on every dispatch once the
slab is part full), and XLA's bf16 propagation is off for the program (it
narrows the whole slab at its source, outside the loop, at every batch of 8
and more). Nothing runs here, so this says nothing about answers or times;
tests/test_tpu_index.py holds the answers. The topology is described inside a
module-scoped fixture, never at import (on-chip-measurement guide, 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

CAP, DIM, K = 2 ** 20, 768, 10
TEMP_LIMIT = 64 * 2 ** 20   # the slab is 3.2 GB, a chunk of it 403 MB


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


def _slab_wide_bf16_converts(text: str) -> list:
    """`convert` instructions whose result is bf16 and holds a quarter of the
    slab's elements or more (the hoisted one is bf16[8,131072,768])."""
    found = []
    for m in re.finditer(r"= bf16\[([\d,]+)\]\S* convert\(", text):
        elems = 1
        for d in m.group(1).split(","):
            elems *= int(d)
        if elems >= CAP * DIM // 4:
            found.append(m.group(0))
    return found


@pytest.mark.parametrize("batch,rows,use_allow", [
    (1, 1_000_000, False), (8, 1_000_000, False), (256, 1_000_000, False),
    (1, 600_000, False), (8, 600_000, False), (256, 600_000, False),
    (256, 1_000_000, True),
])
def test_scan_program_has_no_slab_sized_temporary(one_chip, batch, rows,
                                                  use_allow):
    """cohere-768-cos's slab (2^20 x 768 f32), full and part full, at the
    widths Search and BatchSearch dispatch: the program the index picks for
    a TPU device (the platform of the slab's sharding decides, from a CPU
    process too)."""
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS
    from weaviate_tpu.index import tpu

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = tpu._search_full_fused.lower(
        S((CAP, DIM), jnp.float32), None, S((CAP,), jnp.bool_),
        S((), jnp.int32), S((batch, DIM), jnp.float32),
        S((CAP // 32,), jnp.uint32), S((CAP, 2), jnp.uint32),
        k=K, metric="cosine", use_allow=use_allow, exact=False,
        active_chunks=-(-rows // tpu._SCAN_CHUNK),
        rescore_r=min(max(4 * K, RESCORE_R_BUCKETS[0]),
                      RESCORE_R_BUCKETS[-1])).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    assert _slab_wide_bf16_converts(compiled.as_text()) == []
