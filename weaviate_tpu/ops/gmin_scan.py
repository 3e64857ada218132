"""Fused distance + group-min Pallas kernel: the fast-scan half of the
flagship kNN path.

Why it exists: the lax.scan kernel in index/tpu.py materializes a
[B, chunk] float32 distance block in HBM every chunk and reads it back for
per-chunk selection — at SIFT1M serving shapes (B=16384, N=1M) that is
~137 GB of HBM round-trip per batch, an order of magnitude more traffic
than the store itself. This kernel never materializes distances: each grid
step computes a [QB, SCG] score tile in VMEM on the MXU and writes only its
min over G-member groups — an N/G-column summary (the ScaNN bottom-up
recipe, reference's AVX2 scan has no analog because CPUs don't pay this
memory tax).

Group layout is STRIDED, not contiguous: the store [cap, D] is viewed as
[G, cap/G, D] with zero data movement, so group c's members are slots
{c + g*(cap/G)}. Selection quality: at most k groups can contain the true
top-k, so keeping the top R >= k groups and exact-rescoring their R*G
members reproduces the true top-k UP TO two approximation sources — bf16
fast-scan ranking error and the approx_min_k group selection (the same
PartialReduce primitive the legacy scan uses per chunk, recall_target
0.99 here) — both absorbed in practice by the 2k..128 R slack; recall is
measured against exact ground truth every bench run, and `exactTopK`
config opts out of this path entirely.

Scoring is unified as  score = bias[slot] + alpha * (q . x[slot]):
  l2:     bias = ||x||^2 (+inf dead), alpha = -2   (rank-equal to l2)
  dot:    bias = 0 (+inf dead),       alpha = -1   (rank-equal to -dot)
  cosine: bias = 0 (+inf dead),       alpha = -1   (rows pre-normalized)
Dead slots (tombstoned / beyond n / filtered out) carry bias=+inf, which
survives the min and can never win selection — deletes and allowList
filters cost one elementwise vector, not a kernel variant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from weaviate_tpu.monitoring.metrics import record_device_fallback

G = 16          # group size (min columns per selected group)
_SCG = 512      # group-columns per grid step (VMEM upper bound; see plan_tiles)
_QB = 512       # query rows per grid step (upper bound)
_RESCORE_BLOCK = 2048  # query rows per rescore map step (bounds the gather)

# Mosaic's scoped-VMEM limit, passed to every pallas_call of the three scan
# kernels so it does not vary with the compiler's per-generation default
# (16 MiB on a v5e, whose core has 128 MiB of VMEM), and the budget the
# hand footprint models below plan against. The 4 MiB between them is
# headroom for what the models leave out. Mosaic's own allocation at the
# served shapes (b=256, d=128, active_g=16; libtpu 0.0.34, v5e, read from
# the compiler's scoped-allocation report): gmin f32 9.19 MiB against
# 10.0 modelled; pq_gmin M=32 C=256 7.51 against 6.34; pq4 M/2=16 5.59
# against 3.44. Over the limit is a compile error, so the plan is a hard
# gate, not a hint.
VMEM_LIMIT = 16 * 1024 * 1024
_VMEM_BUDGET = 12 * 1024 * 1024


def compiler_params():
    """Mosaic parameters shared by the scan kernels: the explicit VMEM
    limit, and a sequential grid (the PQ kernels carry their reconstructed
    tile in scratch across the inner query dimension)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def mosaic_g(ag: int, g: int = G) -> int:
    """Mosaic-legal live-group count: the bias input is a 2D [ag, scg]
    block, and Mosaic requires a 2D block's second-to-last dim to be
    8-divisible or equal to the array dim — interpret mode accepts ag=13,
    the real chip rejects it (found in the round-5 hardware session).
    Round up to the next multiple of 8, capped at g (equality is always
    legal). Padded slices carry inf bias, so they cost VMEM + FLOPs but
    never change results."""
    return min(g, -(-ag // 8) * 8)


def _tile_footprint(qb: int, scg: int, d: int, ag: int, store_bytes: int) -> int:
    """Estimated VMEM bytes for one grid step: double-buffered input blocks
    (query tile, [ag, scg, d] store slices, bias), double-buffered output,
    plus bf16 compute copies and the f32 accumulator."""
    inputs = qb * d * 4 + ag * scg * d * store_bytes + ag * scg * 4
    outputs = qb * scg * 4
    compute = qb * d * 2 + scg * d * 2 + qb * scg * 4
    return 2 * inputs + 2 * outputs + compute


def plan_tiles(b: int, d: int, ncols: int, ag: int,
               store_bytes: int = 4) -> tuple[int, int, int]:
    """-> (qb, scg, footprint_bytes): the largest power-of-two tile sizes
    whose VMEM footprint fits the budget. Wide vectors (d >= ~512 at f32)
    shrink the store tile first, then the query tile; callers must refuse
    the kernel when even the smallest tiling is over budget."""
    ag = mosaic_g(ag)  # footprint must price the padded slices the kernel loads
    qb = min(_QB, b)
    scg = min(_SCG, ncols)
    while scg > 128 and _tile_footprint(qb, scg, d, ag, store_bytes) > _VMEM_BUDGET:
        scg //= 2
    while qb > 64 and _tile_footprint(qb, scg, d, ag, store_bytes) > _VMEM_BUDGET:
        qb //= 2
    return qb, scg, _tile_footprint(qb, scg, d, ag, store_bytes)


def fits_vmem(b: int, d: int, ncols: int, ag: int, store_bytes: int = 4) -> bool:
    """Whether the kernel COMPILES at this shape. Not whether it should run:
    `kernel_serves` says that."""
    return plan_tiles(b, d, ncols, ag, store_bytes)[2] <= _VMEM_BUDGET


# The narrowest width at which the lax.scan program was measured no slower
# than the kernel, whatever a component weighs: fitted on a v5e at b = 256
# over nine (width, bytes a component) points (PERF.md section 6, PR 40 has
# the table). The kernel's time follows rows x width, the scan's the bytes
# plus a cost a row, so the width orders them and the bytes of a row do not:
# at 768 B a row the kernel wins as 192-d f32 and loses as 384-d bf16.
KERNEL_LOSES_FROM_DIM = 256


def kernel_serves(b: int, d: int, ncols: int, ag: int,
                  store_bytes: int = 4) -> bool:
    """Whether the group-min kernel is the program to run a full-store scan
    with: it compiles (`fits_vmem`) AND it is the faster of the two at this
    width. The one choice of both indexes (index/plan.py plan_search asks
    it for either). False is a choice, not a degradation: the
    caller runs ops/scan.py's program and builds nothing of the kernel's."""
    return (d < KERNEL_LOSES_FROM_DIM
            and fits_vmem(b, d, ncols, ag, store_bytes))


PROGRAM_GMIN, PROGRAM_SCAN = "gmin", "scan"


class ProgramCounts:
    """Full-store dispatches of one index by the program that ran them, and
    how many of the `scan` ones the kernel would have fitted and
    `kernel_serves` declined. Plain integers behind a leaf lock (four
    callers dispatch at once), kept whether or not the tracer is up."""

    __slots__ = ("_lock", "gmin", "scan", "declined_slower", "ivf_declined")

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.gmin = self.scan = self.declined_slower = 0
        # dispatches that had a partition layout and took a full-store
        # program by the bytes (index/plan.py `probed_reads_less`); beside
        # `as_dict`, whose three keys are the kernel's choice
        self.ivf_declined = 0

    def count(self, program: str) -> None:
        with self._lock:
            if program == PROGRAM_GMIN:
                self.gmin += 1
            else:
                self.scan += 1

    def declined(self) -> None:
        with self._lock:
            self.declined_slower += 1

    def declined_probe(self) -> None:
        with self._lock:
            self.ivf_declined += 1

    def kernel_serves(self, *shape) -> bool:
        """`kernel_serves(*shape)` as both indexes ask it: a no at a shape
        the kernel would have compiled for is counted as a decline."""
        if kernel_serves(*shape):
            return True
        if fits_vmem(*shape):
            self.declined()
        return False

    def as_dict(self) -> dict:
        return {PROGRAM_GMIN: self.gmin, PROGRAM_SCAN: self.scan,
                "declined_slower": self.declined_slower}


class KernelState:
    """Standalone holder of the per-shape validation state
    guarded_kernel_call drives — lets an index carry SEPARATE failure
    domains for different kernels (a Mosaic rejection of the PQ codes
    kernel must not disable the dense gmin path, and vice versa)."""

    __slots__ = ("_gmin_validated", "_gmin_shape_broken", "_gmin_broken")

    def __init__(self):
        self._gmin_validated: set = set()
        self._gmin_shape_broken: set = set()
        self._gmin_broken = False


def kernel_health(state) -> dict:
    """The ``health()["kernels"]`` entry of one failure domain (`state`
    carries the attributes guarded_kernel_call drives): how many compiled
    shapes completed a materialized search, how many Mosaic rejected, and
    the shape keys themselves. "No fallback counted" cannot prove a kernel
    ran — an ineligible shape counts nothing — so this is the positive
    proof: validated >= 1 and rejected == 0."""
    programs = getattr(state, "scan_programs", None)
    return {
        **({"dispatches": programs.as_dict()} if programs is not None else {}),
        "validated": len(state._gmin_validated),
        "rejected": len(state._gmin_shape_broken),
        "broken": bool(state._gmin_broken),
        "validated_shapes": sorted(
            (list(k) for k in state._gmin_validated), key=repr),
        "rejected_shapes": sorted(
            (list(k) for k in state._gmin_shape_broken), key=repr),
    }


def guarded_kernel_call(index, key, thunk, kernel_desc: str,
                        component: str = "ops.gmin_scan"):
    """Per-compiled-shape validation state machine, shared by the
    single-chip and mesh indexes so their fallback behavior cannot diverge.

    `index` carries `_gmin_validated` / `_gmin_shape_broken` (shape-key
    sets) and `_gmin_broken` (global flag). Policy: a failure on a NEW
    shape falls back for that shape only (first call per shape
    materializes, so runtime faults land here too); a failure on a shape
    that already served propagates (a real device fault must not silently
    halve throughput); three distinct pre-validation failures mark the
    whole path broken. -> the thunk's value (device-resident once the
    shape is validated, for pipelining), or None to use the fallback
    kernel."""
    import numpy as np

    if key in index._gmin_shape_broken:
        # count EVERY degraded dispatch, not just the first rejection — a
        # steady weaviate_device_fallback_total rate is what makes an index
        # quietly serving on the slow kernel dashboard-visible
        record_device_fallback(component, "degraded", log=False)
        return None
    try:
        out = thunk()
        if key not in index._gmin_validated:
            out = np.asarray(out)
    except Exception as e:  # noqa: BLE001 — see docstring
        if key in index._gmin_validated:
            raise
        import logging

        # the per-shape warnings below are already one-shot; the counter is
        # what makes a fleet-wide Mosaic regression visible on a dashboard
        record_device_fallback(component, "mosaic_reject", e, log=False)
        index._gmin_shape_broken.add(key)
        if not index._gmin_validated and len(index._gmin_shape_broken) >= 3:
            index._gmin_broken = True
            logging.getLogger(__name__).warning(
                "%s unavailable (%s: %s); using the fallback kernel for "
                "this index", kernel_desc, type(e).__name__, e)
        else:
            logging.getLogger(__name__).warning(
                "%s rejected shape %s (%s: %s); using the fallback kernel "
                "for this shape", kernel_desc, key, type(e).__name__, e)
        return None
    index._gmin_validated.add(key)
    return out


def _gmin_kernel(q_ref, s_ref, b_ref, o_ref, *, alpha: float, g: int):
    """One (store-tile, query-tile) step: min over g strided sub-tiles of
    bias + alpha * (q @ store_g.T), accumulated in VMEM."""

    qd = q_ref[...].astype(jnp.bfloat16)

    def body(gi, acc):
        qx = jnp.dot(qd, s_ref[gi].astype(jnp.bfloat16).T,
                     preferred_element_type=jnp.float32)
        return jnp.minimum(acc, b_ref[gi] + alpha * qx)

    acc0 = jnp.full(o_ref.shape, jnp.inf, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, g, body, acc0)


def group_min_scores(q, store3, bias2, alpha: float, *, active_g: int = G,
                     interpret: bool = False):
    """[B, D] queries x [G, ncols, D] store view -> [B, ncols] group-min
    scores. B % QB == 0 and ncols % SCG == 0 (callers pad; capacities are
    powers of two >= G*SCG).

    active_g bounds the member loop to ceil(n/ncols) slices: slots fill
    sequentially, so slices past the high-water mark are entirely dead —
    the BlockSpec loads only the live slices into VMEM and the matmul loop
    skips the dead tail (the legacy scan's active_chunks bound, here worth
    up to 2x after geometric growth)."""
    b, d = q.shape
    g, ncols, _ = store3.shape
    ag = mosaic_g(max(1, min(int(active_g), g)), g)
    qb, scg, _ = plan_tiles(b, d, ncols, ag, store3.dtype.itemsize)
    grid = (ncols // scg, b // qb)  # queries innermost: store tile loads once
    return pl.pallas_call(
        functools.partial(_gmin_kernel, alpha=alpha, g=ag),
        out_shape=jax.ShapeDtypeStruct((b, ncols), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, d), lambda i, j: (j, 0)),
            pl.BlockSpec((ag, scg, d), lambda i, j: (0, i, 0)),
            pl.BlockSpec((ag, scg), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((qb, scg), lambda i, j: (j, i)),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, store3, bias2)


@jax.jit
def build_rescore_blocks(store):
    """[cap, D] store -> [ncols, G*D] group-block layout: row `col` carries
    the G strided members of group `col` (slots col, ncols+col, ...)
    CONTIGUOUSLY, member-major. Why it exists: the candidate rescore gathers
    rg*G rows per query, and on TPU an HBM gather is descriptor-bound — rg*G
    scattered 512-byte rows per query (8.4M per 16384-batch at rg=32) was
    the measured e2e bottleneck of the fused path on real hardware (round-5
    chip session; the Pallas scan itself is ~µs-scale). Gathering from this
    layout needs only rg descriptors per query, each a contiguous G*D*4-byte
    slice (8 KB at D=128) — the ScaNN recipe of storing candidate blocks
    adjacently. The index caches this array per store generation (one 512 MB
    transpose per import flush at 1M x 128, amortized across every search)."""
    cap, d = store.shape
    ncols = cap // G
    return store.reshape(G, ncols, d).transpose(1, 0, 2).reshape(ncols, G * d)


@functools.partial(
    jax.jit,
    static_argnames=("use_allow", "k", "metric", "rg", "active_g", "interpret",
                     "with_slots"),
)
def search_gmin_fused(store, sq_norms, tombs, n, q, allow_words, s2d,
                      use_allow, k, metric, rg, active_g=G, interpret=False,
                      rescore_blk=None, with_slots=False):
    """The full-store search as one program: group-min fast scan -> top-RG
    groups -> exact rescore of RG*G members -> top-k (gmin_topk) -> doc
    ids. The matmul metrics' fast twin of index/tpu.py _search_full_fused.

    allow_words: packed uint32 allowList bitmap over slots (ignored unless
    use_allow). rescore_blk: optional build_rescore_blocks(store) output —
    when given, the candidate rescore reads contiguous group blocks instead
    of strided rows (16x fewer gather descriptors). s2d is the
    device-resident [capacity, 2] uint32 doc-id word table (index/tpu.py
    IndexSnapshot.slot_to_doc_dev) and the return is the FUSED [B, 3k]
    layout (ops/topk.translate_pack): final doc ids leave the device in the
    one packed fetch. with_slots: the [B, 4k] layout that keeps the slots
    (ops/topk.translate_pack_slots): the k columns of a compressed index's
    bf16 rows are candidates the host scores again."""
    from weaviate_tpu.ops.topk import translate_pack, translate_pack_slots

    top, idx = gmin_topk(store, sq_norms, tombs, n, q, allow_words, use_allow,
                         k, metric, rg, active_g, interpret, rescore_blk)
    return (translate_pack_slots if with_slots else translate_pack)(
        top, idx, s2d)


def gmin_topk(store, sq_norms, tombs, n, q, allow_words, use_allow,
              k, metric, rg, active_g=G, interpret=False, rescore_blk=None):
    """search_gmin_fused's traceable body -> ([B, k] dists, [B, k] slot idx,
    -1 for missing). Unjitted so it can run per-shard inside shard_map (the
    mesh kernel) as well as under the single-chip jit wrapper."""
    from weaviate_tpu.ops.topk import bitmap_to_mask

    cap, dim = store.shape
    ncols = cap // G
    b = q.shape[0]

    # dead-slot bias: +inf survives the group min and never wins selection
    slot = jnp.arange(cap)
    dead = jnp.logical_or(tombs, slot >= n)
    if use_allow:
        dead = jnp.logical_or(dead, jnp.logical_not(bitmap_to_mask(allow_words, cap)))
    if metric == "l2-squared":
        base = sq_norms
        alpha = -2.0
    else:  # dot / cosine (rows pre-normalized at insert for cosine)
        base = jnp.zeros((cap,), jnp.float32)
        alpha = -1.0
    bias = jnp.where(dead, jnp.inf, base)

    store3 = store.reshape(G, ncols, dim)
    bias2 = bias.reshape(G, ncols)
    gmin = group_min_scores(q, store3, bias2, alpha, active_g=active_g,
                            interpret=interpret)

    _, gidx = jax.lax.approx_min_k(gmin, rg, recall_target=0.99)

    # expand each kept group to its member slots and exact-rescore in query
    # blocks (bounds the [block, rg*G, D] gather in HBM). bias validity rides
    # the same block gather — jnp.take(bias, slots) would itself be rg*G
    # scalar gathers per query.
    from weaviate_tpu.ops.topk import rescore_distances

    offs = (jnp.arange(G) * ncols)[None, None, :]
    bias_blk = bias2.T  # [ncols, G]

    def rescore_block(args):
        qb_, gidx_ = args
        nb_ = qb_.shape[0]
        slots = (gidx_[:, :, None] + offs).reshape(nb_, rg * G)
        if rescore_blk is not None:
            cand = jnp.take(rescore_blk, gidx_, axis=0).reshape(
                nb_, rg, G, dim).reshape(nb_, rg * G, dim)
        else:
            cand = jnp.take(store, slots, axis=0)
        ed = rescore_distances(cand, qb_, metric)
        cand_bias = jnp.take(bias_blk, gidx_, axis=0).reshape(nb_, rg * G)
        ed = jnp.where(jnp.isinf(cand_bias), jnp.inf, ed)
        neg, pos = jax.lax.top_k(-ed, k)
        return -neg, jnp.take_along_axis(slots, pos, axis=1)

    if b > _RESCORE_BLOCK:
        # ceil-split with zero padding: bucketed batches are usually exact
        # multiples, but any b is legal here (the pad rows' results are
        # sliced off)
        nb = -(-b // _RESCORE_BLOCK)
        pad = nb * _RESCORE_BLOCK - b
        qp = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
        gp = jnp.pad(gidx, ((0, pad), (0, 0))) if pad else gidx
        top, idx = jax.lax.map(
            rescore_block,
            (qp.reshape(nb, _RESCORE_BLOCK, dim), gp.reshape(nb, _RESCORE_BLOCK, rg)),
        )
        top = top.reshape(nb * _RESCORE_BLOCK, k)[:b]
        idx = idx.reshape(nb * _RESCORE_BLOCK, k)[:b]
    else:
        top, idx = rescore_block((q, gidx))

    idx = jnp.where(jnp.isinf(top), -1, idx).astype(jnp.int32)
    return top, idx
