"""cohere-768-cos-mesh4: the programs the deployment runs, compiled by the
real TPU compiler for a described v5e:2x2 at the configuration's own shapes
(rows over four chips, the capacity the index would grow to). Nothing runs:
this says what the compiler accepts and what a chip must hold, nothing about
answers or times."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.lib.spec import Spec

HBM_BYTES = int(15.75 * 2 ** 30)   # what a v5e chip's allocator offers
N_DEV, BATCH = 4, 256


@pytest.fixture(scope="module")
def cfg():
    return Spec().config("cohere-768-cos-mesh4")


@pytest.fixture(scope="module")
def n_loc(cfg):
    from weaviate_tpu.index.mesh import _pow2_at_least

    return _pow2_at_least(-(-int(cfg["rows"]) // N_DEV) + 1, 32)


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies

    from weaviate_tpu.parallel import mesh_search as ms
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(topo.devices[:N_DEV], (ms.SHARD_AXIS,))


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _shapes(mesh, n_loc, dim):
    from weaviate_tpu.parallel import mesh_search as ms

    cap = N_DEV * n_loc
    sh = lambda *rest: NamedSharding(mesh, P(ms.SHARD_AXIS, *rest))  # noqa: E731
    rep = NamedSharding(mesh, P())
    S = jax.ShapeDtypeStruct
    return {
        "store": S((cap, dim), jnp.float32, sharding=sh(None)),
        "norms": S((cap,), jnp.float32, sharding=sh()),
        "tombs": S((cap,), jnp.bool_, sharding=sh()),
        "counts": S((N_DEV,), jnp.int32, sharding=rep),
        "words": S((cap // 32,), jnp.uint32, sharding=sh()),
        "queries": S((BATCH, dim), jnp.float32, sharding=rep),
        "s2d": S((cap, 2), jnp.uint32, sharding=sh(None)),
        "chunks": S((N_DEV, 8192, dim), jnp.float32, sharding=sh(None, None)),
    }


def test_the_configuration_is_four_chips_of_one_slab_each(cfg, n_loc):
    assert cfg["chips"] == N_DEV
    assert cfg["class"]["vectorIndexConfig"]["meshDevices"] == N_DEV
    assert cfg["class"]["vectorIndexType"] == "hnsw_tpu_mesh"
    per_chip = -(-int(cfg["rows"]) // N_DEV)
    assert n_loc // 2 < per_chip + 1 <= n_loc
    # recovery holds the slab and one copy of it (the insert steps do not
    # donate): that has to fit a chip
    assert 2 * n_loc * int(cfg["dim"]) * 4 < HBM_BYTES


def test_gmin_is_refused_at_the_cells_shape_so_the_scan_serves(cfg, n_loc):
    from weaviate_tpu.ops import gmin_scan

    ncols = n_loc // gmin_scan.G
    active_g = -(-(-(-int(cfg["rows"]) // N_DEV)) // ncols)
    assert not gmin_scan.fits_vmem(BATCH, int(cfg["dim"]), ncols, active_g, 4)


def test_the_search_program_compiles_for_four_chips_and_fits(cfg, n_loc,
                                                             mesh):
    from weaviate_tpu.parallel import mesh_search as ms

    s = _shapes(mesh, n_loc, int(cfg["dim"]))
    compiled = ms.mesh_search_step.lower(
        s["store"], s["norms"], s["tombs"], s["counts"], s["words"],
        s["queries"], s["s2d"], k=int(cfg["k"]), metric=cfg["distance"],
        use_allow=False, use_norms=False, exact=False, fused=True,
        mesh=mesh).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes < HBM_BYTES
    # no slab-sized temporary: the f32 rows are read inside the loop, not
    # converted ahead of it as the one-chip program's are
    assert m.temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    assert "all-gather" in text and "tpu_custom_call" not in text


def test_the_insert_step_of_recovery_compiles_and_holds_two_slabs(cfg, n_loc,
                                                                  mesh):
    from weaviate_tpu.parallel import mesh_search as ms

    s = _shapes(mesh, n_loc, int(cfg["dim"]))
    compiled = ms.mesh_insert_step.lower(
        s["store"], s["norms"], s["chunks"], s["counts"], s["counts"],
        use_norms=False, mesh=mesh).compile()
    m = compiled.memory_analysis()
    slab = n_loc * int(cfg["dim"]) * 4
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert 2 * slab <= held < 2 * slab + (256 << 20)
