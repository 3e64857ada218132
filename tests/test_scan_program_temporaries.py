"""The scan step's programs make no slab-sized temporary: compiled by the
real TPU compiler for a described v5e, at the 768-d deployments' real shapes,
every step takes its chunk from the f32 slab in place and the rounding to bf16
happens inside the step's matmul. Two things hold that together and neither
does alone (ops/scan.py scan_topk, TPU_SCAN_OPTIONS): the loop indexes the
whole slab (a static `store[:ext]` prefix is copied on every dispatch once the
slab is part full), and XLA's bf16 propagation is off for the program (it
narrows the whole slab at its source, outside the loop, at every batch of 8
and more). The option binds to a top-level jit only, so each program that calls
the step carries it itself: the one-chip `_search_full_fused` and the mesh's
`mesh_search_step` (four chips, 2^19 rows a chip), both held here. Nothing
runs, so this says nothing about answers or times; tests/test_tpu_index.py and
tests/test_mesh_index.py hold the answers. The same compiled texts hold one
more thing since PR 41: no `gather` of the merged candidates' slots runs inside
the loop (ops/topk.py merge_top_k carries them through its sort). The topology
is described inside a module-scoped fixture, never at import
(on-chip-measurement guide, 2)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

CAP, DIM, K = 2 ** 20, 768, 10
TEMP_LIMIT = 64 * 2 ** 20   # the slab is 3.2 GB, a chunk of it 403 MB


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


def _slab_wide_bf16_converts(text: str, slab_rows: int = CAP) -> list:
    """`convert` instructions whose result is bf16 and holds a quarter of the
    slab's elements or more (the hoisted one is bf16[8,131072,768]; on the
    mesh, of a chip's slab, bf16[4,131072,768])."""
    found = []
    for m in re.finditer(r"= bf16\[([\d,]+)\]\S* convert\(", text):
        elems = 1
        for d in m.group(1).split(","):
            elems *= int(d)
        if elems >= slab_rows * DIM // 4:
            found.append(m.group(0))
    return found


def _one_chip_program(one_chip, program, batch, rows, use_allow, cap=CAP,
                      store=jnp.float32, candidates=False):
    """`program` (`_search_full_fused`, or one of its two jits) compiled for
    one described chip over a 768-d slab of `cap` slots (cohere-768-cos's
    unless the caller says), at the depth the index runs."""
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS
    from weaviate_tpu.index import tpu

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return program.lower(
        S((cap, DIM), store), None, S((cap,), jnp.bool_),
        S((), jnp.int32), S((batch, DIM), jnp.float32),
        S((cap // 32,), jnp.uint32), S((cap, 2), jnp.uint32),
        k=K, metric="cosine", use_allow=use_allow, exact=False,
        active_chunks=-(-rows // tpu._SCAN_CHUNK),
        rescore_r=min(max(4 * K, RESCORE_R_BUCKETS[0]),
                      RESCORE_R_BUCKETS[-1]),
        candidates=candidates).compile()


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
    from weaviate_tpu.index import tpu

    compiled = _one_chip_program(one_chip, tpu._search_full_fused, batch,
                                 rows, use_allow)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    assert _slab_wide_bf16_converts(compiled.as_text()) == []


def test_the_compiler_option_is_what_holds_the_one_chip_program(one_chip):
    """The same function jitted WITHOUT TPU_SCAN_OPTIONS (the program a CPU
    device gets), compiled for the chip at batch 256 over the full slab:
    bf16 propagation narrows the whole slab ahead of the loop."""
    from weaviate_tpu.index import tpu

    compiled = _one_chip_program(one_chip, tpu._search_full_fused._plain,
                                 256, 1_000_000, False)
    assert compiled.memory_analysis().temp_size_in_bytes > CAP * DIM * 2 // 2
    assert _slab_wide_bf16_converts(compiled.as_text()) != []


MESH_DEV, MESH_LOC, MESH_BATCH = 4, 2 ** 19, 256   # cohere-768-cos-mesh4


def _mesh_program(topo, program, **statics):
    """`program` (mesh_search_step, or one of its two jits) compiled for the
    four described chips at the mesh cell's shapes; the bytes are a chip's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from weaviate_tpu.parallel import mesh_search as ms

    mesh = Mesh(topo.devices[:MESH_DEV], (ms.SHARD_AXIS,))
    sharded = lambda *rest: NamedSharding(mesh, P(ms.SHARD_AXIS, *rest))  # noqa: E731
    rep = NamedSharding(mesh, P())
    S = jax.ShapeDtypeStruct
    cap = MESH_DEV * MESH_LOC
    return program.lower(
        S((cap, DIM), jnp.float32, sharding=sharded(None)),
        S((cap,), jnp.float32, sharding=sharded()),
        S((cap,), jnp.bool_, sharding=sharded()),
        S((MESH_DEV,), jnp.int32, sharding=rep),
        S((cap // 32,), jnp.uint32, sharding=sharded()),
        S((MESH_BATCH, DIM), jnp.float32, sharding=rep),
        S((cap, 2), jnp.uint32, sharding=sharded(None)),
        k=K, metric="cosine", use_norms=False, exact=False, fused=True,
        mesh=mesh, **statics).compile()


@pytest.mark.parametrize("use_allow,rescore_r", [
    (False, 40), (True, 40), (False, 0),
])
def test_mesh_scan_program_has_no_slab_sized_temporary(topo, use_allow,
                                                       rescore_r):
    """cohere-768-cos-mesh4's slab a chip (2^19 x 768 f32), batch 256, k 10:
    the program `mesh_search_step` picks for TPU devices (the platform of
    the store's sharding decides), at the depth the index runs (40), under a
    filter, and as the HIGHEST-precision scan that exactTopK keeps (0)."""
    from weaviate_tpu.parallel import mesh_search as ms

    compiled = _mesh_program(topo, ms.mesh_search_step, use_allow=use_allow,
                             rescore_r=rescore_r)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    text = compiled.as_text()
    assert _slab_wide_bf16_converts(text, MESH_LOC) == []
    assert "all-gather" in text
    # the name the benchmark finds the program by (scan_roofline.json)
    assert "HloModule jit_mesh_search_step" in text


def test_the_compiler_option_is_what_holds_the_mesh_program(topo):
    """The mesh's twin of the one-chip case above: without the option every
    chip's whole slab is narrowed ahead of the loop, an 806 MB temporary a
    chip a dispatch. The shared step does not bring the option; the program
    must."""
    from weaviate_tpu.parallel import mesh_search as ms

    compiled = _mesh_program(topo, ms.mesh_search_step._plain,
                             use_allow=False, rescore_r=40)
    assert compiled.memory_analysis().temp_size_in_bytes > \
        MESH_LOC * DIM * 2 // 2
    assert _slab_wide_bf16_converts(compiled.as_text(), MESH_LOC) != []


# -- cohere-768-cos-10m-share: a slab the chip holds once, not twice ----------

SHARE_CAP, SHARE_ROWS = 20 * 131072, 2_500_000      # 8.05 GB of 16.9


def _share_program(one_chip):
    from weaviate_tpu.index import tpu

    return _one_chip_program(one_chip, tpu._search_full_fused, 256,
                             SHARE_ROWS, False, cap=SHARE_CAP)


def test_the_share_scan_program_fits_beside_its_slab(one_chip):
    """cohere-768-cos-10m-share.batch256's program: 20 scan chunks over an
    8.05 GB slab, and no temporary worth naming beside it."""
    compiled = _share_program(one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    assert _slab_wide_bf16_converts(compiled.as_text(), SHARE_CAP) == []


# -- the loop's merge carries its slots: no gather a chunk ---------------------

def _computations(text: str) -> dict:
    """Compiled HLO text -> {computation: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _gathers_in_loops(text: str) -> list:
    """The result types of every `gather` that runs inside a `while`: in
    its body or in a computation the body calls (a fusion, a comparator), at
    any depth."""
    comps = _computations(text)
    todo = [m.group(1) for lines in comps.values() for line in lines
            if " while(" in line
            for m in [re.search(r"body=%([\w.\-]+)", line)] if m]
    assert todo, "the program has no while loop"
    seen, found = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) gather\(", line)
            if m:
                found.append(re.sub(r"\{.*", "", m.group(1)))
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
    return found


MERGED = {f"s32[256,{4 * K}]", f"s32[{256 * 4 * K}]"}


@pytest.mark.parametrize("program", ["share", "compressed", "mesh"])
def test_no_gather_of_the_merged_slots_is_left_in_the_scan_loop(
        topo, one_chip, program):
    """The cross-chunk merge of every step (ops/topk.py merge_top_k) moves
    the slots with their distances, through one sort. The form before it
    selected the distances and then read the slots by position, which the
    TPU compiler makes a `gather` with slice_sizes={1,1} from the
    s32[256,80] block to s32[256,40], in a fusion of the loop's body whose
    result is s32[10240]: 10,240 single look-ups, 82 us a step on a v5e, an
    eighth of cohere-768-cos-10m-share.batch256's program (the parent of
    PR 41 fails here with ['s32[256,40]']). The gathers that stay (the
    [B, R, D] rescore rows, the slot->doc words, the final k of R) run once
    a program, after the loop. Three programs: the share's, the compressed
    cell's (a bf16 slab, `candidates`) and a chip of the mesh's."""
    if program == "mesh":
        from weaviate_tpu.parallel import mesh_search as ms

        compiled = _mesh_program(topo, ms.mesh_search_step, use_allow=False,
                                 rescore_r=4 * K)
    elif program == "compressed":
        from weaviate_tpu.index import tpu

        compiled = _one_chip_program(
            one_chip, tpu._search_full_fused, 256, 2_000_000, False,
            cap=2 ** 21, store=jnp.bfloat16, candidates=True)
    else:
        compiled = _share_program(one_chip)
    text = compiled.as_text()
    assert [g for g in _gathers_in_loops(text) if g in MERGED] == []
    # what the parser can see: the program still gathers, outside the loop
    assert " gather(" in text


@pytest.mark.parametrize("kernel", ["_write_rows", "_write_slots",
                                    "_set_tombstones", "_write_doc_pairs"])
def test_a_donating_write_kernel_overwrites_every_array_it_writes(
        one_chip, kernel):
    """The in-place twins at the share's shapes: the compiler aliases every
    written array to its input (no second slab, no temporary), where the
    functional kernels' outputs are new arrays as large as their inputs."""
    from weaviate_tpu.index import tpu

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    slab = S((SHARE_CAP, DIM), jnp.float32)
    s2d, tombs = S((SHARE_CAP, 2), jnp.uint32), S((SHARE_CAP,), jnp.bool_)
    idx = S((128,), jnp.int32)
    args = {
        "_write_rows": (slab, S((8192, DIM), jnp.float32), S((), jnp.int32)),
        "_write_slots": (slab, None, s2d, tombs, idx,
                         S((128, DIM), jnp.float32), None,
                         S((128, 2), jnp.uint32), idx),
        "_set_tombstones": (tombs, idx),
        "_write_doc_pairs": (s2d, idx, S((128, 2), jnp.uint32)),
    }[kernel]
    plain = getattr(tpu, kernel)
    given = tpu._IN_PLACE[plain].lower(*args).compile().memory_analysis()
    made = plain.lower(*args).compile().memory_analysis()
    assert given.alias_size_in_bytes >= given.output_size_in_bytes - 1024
    assert given.temp_size_in_bytes < TEMP_LIMIT
    assert made.alias_size_in_bytes == 0
    assert made.output_size_in_bytes >= given.alias_size_in_bytes
