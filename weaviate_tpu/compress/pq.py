"""Product quantization: codebooks, encoders, and the asymmetric LUT kernel.

Reference: vector/ssdhelpers/product_quantization.go — segments x centroids
codebooks fit by KMeans (kmeans.go) or the distribution-based Tile scalar
encoder (tile_encoder.go); per-query asymmetric distances via a lazily
computed segment x centroid DistanceLookUpTable (product_quantization.go:30-75)
summed over a row's codes (LookUp :56).

TPU-first deltas:
- fit and encode are batched device programs (vmapped per-segment kmeans /
  one argmin matmul per segment) instead of scalar Go loops;
- the LUT scan is a jitted lax.scan over HBM chunks of the uint8 code
  matrix: per segment a vectorized table gather ([B, C] LUT rows indexed by
  a [chunk] code column) accumulated into the [B, chunk] distance block;
- search keeps a float rescoring pass (gather the top-R candidates' float
  vectors, exact distance, final top-k) so recall stays near-exact while the
  HBM-resident store shrinks 4-16x. The reference returns raw PQ distances;
  rescoring is the knob that buys back its recall loss.

Role in the index: PQ here is a *capacity* trade, not a speed trade — the
uint8 scan does M table-lookups per row on the VPU, while the uncompressed
path is one MXU matmul. Enable it when a shard outgrows HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.entities import vectorindex as vi

Array = jax.Array

_FIT_SAMPLE_MAX = 16384   # rows used to fit codebooks (kmeans.go samples too)
_KMEANS_ITERS = 10
_OPQ_ITERS = 6            # outer Procrustes alternations (OPQ-NP)
_OPQ_INNER_ITERS = 4      # kmeans depth per alternation (full depth at the end)
# encode streams the store through the device in fixed chunks; big chunks
# matter off-chip (each dispatch pays the full host<->device round trip)
_ENCODE_CHUNK = 65536


# -- kmeans (per-segment, on device) ----------------------------------------

def _kmeans_one_segment(data: Array, init: Array, iters: int) -> Array:
    """Lloyd iterations for one segment. data [N, ds], init [C, ds] -> [C, ds]."""
    n = data.shape[0]
    c = init.shape[0]

    def step(_, cent):
        # assign: [N, C] squared distances via the MXU
        xc = jnp.matmul(data, cent.T, preferred_element_type=jnp.float32)
        d = (
            jnp.sum(data**2, axis=1, keepdims=True)
            - 2.0 * xc
            + jnp.sum(cent**2, axis=1)[None, :]
        )
        assign = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(assign, c, dtype=jnp.float32)  # [N, C]
        counts = jnp.sum(onehot, axis=0)  # [C]
        sums = jnp.matmul(onehot.T, data, preferred_element_type=jnp.float32)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # empty clusters keep their previous centroid
        return jnp.where(counts[:, None] > 0, new, cent)

    return jax.lax.fori_loop(0, iters, step, init)


@functools.partial(jax.jit, static_argnames=("iters",))
def _kmeans_fit(data_seg: Array, init: Array, iters: int = _KMEANS_ITERS) -> Array:
    """data_seg [M, N, ds], init [M, C, ds] -> codebook [M, C, ds].
    lax.map keeps peak memory at one segment's [N, C] assignment matrix."""
    return jax.lax.map(
        lambda t: _kmeans_one_segment(t[0], t[1], iters), (data_seg, init))


# -- encode ------------------------------------------------------------------

@jax.jit
def _encode_chunk(chunk_seg: Array, codebook: Array) -> Array:
    """chunk_seg [M, chunk, ds] x codebook [M, C, ds] -> codes [chunk, M] int32.

    Nearest-centroid assignment per segment; ||x||^2 is constant per row so
    only the cross term + centroid norms decide the argmin."""

    def enc_one(t):
        data, cent = t
        xc = jnp.matmul(data, cent.T, preferred_element_type=jnp.float32)
        d = -2.0 * xc + jnp.sum(cent**2, axis=1)[None, :]
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    return jnp.transpose(jax.lax.map(enc_one, (chunk_seg, codebook)))  # [chunk, M]


# -- LUT ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric",))
def build_lut(q: Array, codebook: Array, metric: str) -> Array:
    """[B, D] queries x [M, C, ds] codebook -> LUT [B, M, C] float32.

    Additive decomposition per metric (LookUp sums segment contributions):
      l2:        ||q_m - c||^2
      dot:       -(q_m . c)
      cosine:    -(q_m . c)            (+1 constant applied by the caller)
      manhattan: sum |q_m - c|
    """
    b, d = q.shape
    m, c, ds = codebook.shape
    qs = q.reshape(b, m, ds).astype(jnp.float32)
    if metric == vi.DISTANCE_MANHATTAN:
        # [B, M, C, ds] broadcast — fine at LUT scale (B*M*C*ds = B*C*D)
        return jnp.sum(jnp.abs(qs[:, :, None, :] - codebook[None, :, :, :]), axis=-1)
    qc = jnp.einsum("bmd,mcd->bmc", qs, codebook.astype(jnp.float32))
    if metric in (vi.DISTANCE_DOT, vi.DISTANCE_COSINE):
        return -qc
    if metric == vi.DISTANCE_L2:
        qn = jnp.sum(qs**2, axis=-1)[:, :, None]
        cn = jnp.sum(codebook.astype(jnp.float32) ** 2, axis=-1)[None, :, :]
        return jnp.maximum(qn - 2.0 * qc + cn, 0.0)
    raise ValueError(f"metric {metric!r} has no additive PQ decomposition")


def lut_scan_block(codes_block: Array, lut: Array) -> Array:
    """codes_block [chunk, M] int — LUT [B, M, C] -> distances [B, chunk].

    The PQ hot loop (product_quantization.go:56-75 LookUp, vectorized): for
    each segment, gather the [B]-column of the LUT at each row's code and
    accumulate. Expressed as a fori over segments so the live buffer is one
    [B, chunk] accumulator plus one [B, C] table — VPU gathers from a
    VMEM-resident table, codes stream from HBM once.
    """
    b = lut.shape[0]
    m = codes_block.shape[1]
    chunk = codes_block.shape[0]

    def seg(i, acc):
        table = jax.lax.dynamic_index_in_dim(lut, i, axis=1, keepdims=False)  # [B, C]
        col = jax.lax.dynamic_index_in_dim(codes_block, i, axis=1, keepdims=False)  # [chunk]
        return acc + jnp.take(table, col, axis=1)  # [B, chunk]

    return jax.lax.fori_loop(0, m, seg, jnp.zeros((b, chunk), jnp.float32))


# -- 4-bit code packing ------------------------------------------------------

def pack_codes4(codes: np.ndarray) -> np.ndarray:
    """[N, M] 4-bit codes (values 0..15) -> [N, M//2] packed uint8.

    Byte j carries segment j in the LOW nibble and segment M//2 + j in the
    HIGH nibble, so unpacking is a lane-wise concat (codes = [lo | hi]) —
    no per-element interleave in either the Pallas kernel or the traceable
    LUT scan (ops/pq4.py), which keeps the unpack VPU-shaped."""
    codes = np.asarray(codes)
    n, m = codes.shape
    if m % 2:
        raise ValueError("pack_codes4 requires an even segment count")
    if codes.size and int(codes.max()) > 15:
        raise ValueError("pack_codes4 requires 4-bit codes (centroids <= 16)")
    mb = m // 2
    lo = codes[:, :mb].astype(np.uint8)
    hi = codes[:, mb:].astype(np.uint8)
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_codes4(packed: np.ndarray) -> np.ndarray:
    """[N, M//2] packed uint8 -> [N, M] 4-bit codes (pack_codes4 inverse)."""
    packed = np.asarray(packed, dtype=np.uint8)
    return np.concatenate([packed & 0xF, packed >> 4], axis=1)


# -- the quantizer -----------------------------------------------------------

class ProductQuantizer:
    """Codebook container + fit/encode (ProductQuantizer, ssdhelpers)."""

    def recon_sq_norms(self, codes) -> "np.ndarray":
        """||recon(code)||^2 per row: segments occupy disjoint dims, so the
        square norm is the sum of the chosen centroids' square norms —
        precomputable once per encode, feeding the reconstruction-matmul
        distance d = ||q||^2 - 2 q.recon + ||recon||^2."""
        import numpy as np

        cent_sq = (self.codebook.astype(np.float64) ** 2).sum(-1)  # [M, C]
        rows = np.asarray(codes, dtype=np.int64)                   # [n, M]
        return cent_sq[np.arange(self.segments)[None, :], rows].sum(1).astype(np.float32)

    def __init__(self, dim: int, segments: int, centroids: int, metric: str,
                 encoder: str = vi.PQ_ENCODER_KMEANS,
                 distribution: str = vi.PQ_DISTRIBUTION_LOG_NORMAL,
                 rotation: str = vi.PQ_ROTATION_NONE):
        if segments <= 0:
            segments = dim  # auto (= dims), pq_config.go default
        if dim % segments != 0:
            raise vi.ConfigValidationError(
                f"pq.segments ({segments}) must divide vector dims ({dim})")
        if centroids > 65536:
            raise vi.ConfigValidationError("pq.centroids must be <= 65536")
        if metric == vi.DISTANCE_HAMMING:
            # kmeans centroids are MEANS: exact-equality distance to a mean
            # counts ~every dim a mismatch, so every ADC distance collapses
            # to ~D — silently-useless ranking is worse than an error
            raise vi.ConfigValidationError("pq does not support hamming")
        if encoder == vi.PQ_ENCODER_TILE and dim != segments:
            raise vi.ConfigValidationError("tile encoder requires segments == dims")
        if rotation not in (vi.PQ_ROTATION_NONE, vi.PQ_ROTATION_OPQ):
            raise vi.ConfigValidationError(
                f"pq.rotation must be 'none' or 'opq', got {rotation!r}")
        if rotation == vi.PQ_ROTATION_OPQ:
            if metric == vi.DISTANCE_MANHATTAN:
                # L1 is not rotation-invariant: rotated-space ADC distances
                # would rank by a different geometry than the index serves
                raise vi.ConfigValidationError(
                    "pq.rotation 'opq' requires an l2/dot/cosine distance")
            if encoder == vi.PQ_ENCODER_TILE:
                raise vi.ConfigValidationError(
                    "pq.rotation 'opq' requires the kmeans encoder")
        self.dim = dim
        self.segments = segments
        self.centroids = centroids
        self.ds = dim // segments
        self.metric = metric
        self.encoder = encoder
        self.distribution = distribution
        self.rotation = rotation
        self.rotation_matrix: Optional[np.ndarray] = None  # [D, D] orthogonal
        self.code_dtype = np.uint8 if centroids <= 256 else np.uint16
        self.codebook: Optional[np.ndarray] = None  # [M, C, ds] float32
        self.trained_rows: Optional[int] = None  # rows the codebook fit on
        self._codebook_dev: Optional[Array] = None
        self._rot_dev: Optional[Array] = None

    # fit ---------------------------------------------------------------

    def fit(self, vectors: np.ndarray, seed: int = 0,
            rotation_matrix: Optional[np.ndarray] = None,
            sample_max: int = _FIT_SAMPLE_MAX) -> None:
        """Fit codebooks (and the OPQ rotation when configured). Passing
        ``rotation_matrix`` pins a PRE-FITTED orthogonal rotation instead of
        learning one — the 4-bit funnel quantizer reuses the 8-bit
        quantizer's OPQ rotation this way, so both ladders of the funnel
        rank in the SAME rotated space and the Procrustes alternation runs
        once per compress, not once per bit depth. At most `sample_max`
        rows fit (a uniform sample of more); `trained_rows` keeps how many
        did, and is persisted with the codebook."""
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.shape[0] > sample_max:
            rng = np.random.default_rng(seed)
            sel = rng.choice(vectors.shape[0], sample_max, replace=False)
            vectors = vectors[sel]
        self.trained_rows = int(vectors.shape[0])
        if rotation_matrix is not None:
            if self.encoder == vi.PQ_ENCODER_TILE:
                raise vi.ConfigValidationError(
                    "a preset rotation requires the kmeans encoder")
            self.rotation_matrix = np.asarray(rotation_matrix, np.float32)
            self.codebook = self._fit_kmeans(
                vectors @ self.rotation_matrix, seed)
        elif self.encoder == vi.PQ_ENCODER_TILE:
            self.codebook = self._fit_tile(vectors)
        elif self.rotation == vi.PQ_ROTATION_OPQ:
            self._fit_opq(vectors, seed)
        else:
            self.codebook = self._fit_kmeans(vectors, seed)
        self._codebook_dev = None
        self._rot_dev = None  # a re-fit replaces the rotation too

    def _fit_kmeans(self, vectors: np.ndarray, seed: int,
                    iters: int = _KMEANS_ITERS) -> np.ndarray:
        n = vectors.shape[0]
        m, c, ds = self.segments, self.centroids, self.ds
        data_seg = np.ascontiguousarray(
            vectors.reshape(n, m, ds).transpose(1, 0, 2))  # [M, N, ds]
        rng = np.random.default_rng(seed)
        # init from distinct sample rows per segment (kmeans.go random init)
        init = np.stack([seg[rng.choice(n, min(c, n), replace=False)]
                         for seg in data_seg])
        if init.shape[1] < c:  # fewer samples than centroids: tile them
            reps = -(-c // init.shape[1])
            init = np.tile(init, (1, reps, 1))[:, :c]
        cb = _kmeans_fit(jnp.asarray(data_seg), jnp.asarray(init), iters)
        return np.asarray(cb, dtype=np.float32)

    def _fit_opq(self, vectors: np.ndarray, seed: int) -> None:
        """OPQ-NP (Ge et al. 2013): alternate per-segment kmeans in the
        rotated space with a Procrustes update of the orthogonal rotation
        R = argmin ||XR - recon|| = U V^T from svd(X^T recon). The
        quantizer then lives entirely in the rotated space (codebook,
        codes, ADC distances — all rotation-invariant for the matmul
        metrics); decode() maps reconstructions back. On TPU the query-side
        cost is one [B, D] x [D, D] matmul folded into the jitted search.
        The reference has no analog — its PQ segments the raw dims."""
        x = vectors  # [N, D] fit sample
        r = np.eye(self.dim, dtype=np.float32)
        for _ in range(_OPQ_ITERS):
            xr = x @ r
            self.codebook = self._fit_kmeans(xr, seed, iters=_OPQ_INNER_ITERS)
            self._codebook_dev = None
            recon = self.decode_rotated(self.encode_rotated(xr))
            # Procrustes: [D, D] svd — trivial at vector dims
            u, _s, vt = np.linalg.svd(x.T @ recon)
            r = (u @ vt).astype(np.float32)
        self.rotation_matrix = r
        xr = x @ r
        self.codebook = self._fit_kmeans(xr, seed)  # final full-depth fit

    def _fit_tile(self, vectors: np.ndarray) -> np.ndarray:
        """Distribution-based scalar quantile encoder (tile_encoder.go): per
        dimension, fit a (log-)normal and place centroids at equal-probability
        quantile centers. Encoding then reuses the same nearest-centroid
        argmin as kmeans (exact for 1-d sorted centroids)."""
        c = self.centroids
        x = vectors  # [N, D], ds == 1 enforced in __init__
        if self.distribution == vi.PQ_DISTRIBUTION_LOG_NORMAL:
            # guard non-positive values the way a log-normal fit must
            shift = np.minimum(x.min(axis=0), 0.0) - 1e-6
            y = np.log(x - shift[None, :])
        else:
            shift = None
            y = x
        mu = y.mean(axis=0)  # [D]
        sigma = np.maximum(y.std(axis=0), 1e-9)
        p = (np.arange(c, dtype=np.float64) + 0.5) / c  # bin centers
        z = np.asarray(jax.scipy.special.erfinv(2.0 * p - 1.0)) * np.sqrt(2.0)
        cent = mu[:, None] + sigma[:, None] * z[None, :]  # [D, C]
        if shift is not None:
            cent = np.exp(cent) + shift[:, None]
        return cent[:, :, None].astype(np.float32)  # [M=D, C, ds=1]

    # encode ------------------------------------------------------------

    def _dev_codebook(self) -> Array:
        if self._codebook_dev is None:
            self._codebook_dev = jnp.asarray(self.codebook)
        return self._codebook_dev

    def rotation_dev(self) -> Array:
        """[D, D] device rotation for the jitted search paths — identity
        when no rotation is fitted, so callers apply it unconditionally
        (one tiny MXU matmul)."""
        if self._rot_dev is None:
            r = (self.rotation_matrix if self.rotation_matrix is not None
                 else np.eye(self.dim, dtype=np.float32))
            self._rot_dev = jnp.asarray(r)
        return self._rot_dev

    def encode(self, vectors: np.ndarray) -> np.ndarray:
        """[N, D] float32 -> [N, M] codes; rotates into the quantizer's
        space first when an OPQ rotation is fitted."""
        vectors = np.asarray(vectors, dtype=np.float32)
        if self.rotation_matrix is not None:
            vectors = vectors @ self.rotation_matrix
        return self.encode_rotated(vectors)

    def encode_rotated(self, vectors: np.ndarray) -> np.ndarray:
        """[N, D] ALREADY-ROTATED float32 -> [N, M] uint8/16 codes
        (Encode, :348)."""
        vectors = np.asarray(vectors, dtype=np.float32)
        n = vectors.shape[0]
        m, ds = self.segments, self.ds
        out = np.empty((n, m), dtype=self.code_dtype)
        cb = self._dev_codebook()
        # the per-segment [chunk, C] assignment matrix is the peak buffer:
        # cap it at ~1 GB so max centroids (65536) still fits device memory
        step = min(_ENCODE_CHUNK, max(4096, (1 << 28) // max(self.centroids, 1)))
        for off in range(0, n, step):
            end = min(off + step, n)
            blk = vectors[off:end].reshape(end - off, m, ds).transpose(1, 0, 2)
            codes = np.asarray(_encode_chunk(jnp.asarray(blk), cb))
            out[off:end] = codes.astype(self.code_dtype)
        return out

    def decode_rotated(self, codes: np.ndarray) -> np.ndarray:
        """[N, M] codes -> [N, D] reconstruction in the quantizer's
        (rotated) space — what the ADC distance paths compare against."""
        codes = np.asarray(codes)
        n, m = codes.shape
        recon = self.codebook[np.arange(m)[None, :], codes.astype(np.int64)]  # [N, M, ds]
        return recon.reshape(n, self.dim).astype(np.float32)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """[N, M] codes -> [N, D] reconstructed float32 in the ORIGINAL
        space (rotation undone — R is orthogonal, so inverse = transpose)."""
        recon = self.decode_rotated(codes)
        if self.rotation_matrix is not None:
            recon = recon @ self.rotation_matrix.T
        return recon

    # persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        extra = {}
        if self.rotation_matrix is not None:
            extra["rotation_matrix"] = self.rotation_matrix
        if self.trained_rows is not None:
            extra["trained_rows"] = self.trained_rows
        np.savez(
            path,
            codebook=self.codebook,
            dim=self.dim,
            segments=self.segments,
            centroids=self.centroids,
            metric=self.metric,
            encoder=self.encoder,
            distribution=self.distribution,
            rotation=self.rotation,
            **extra,
        )

    @classmethod
    def load(cls, path: str) -> "ProductQuantizer":
        z = np.load(path, allow_pickle=False)
        pq = cls(
            dim=int(z["dim"]),
            segments=int(z["segments"]),
            centroids=int(z["centroids"]),
            metric=str(z["metric"]),
            encoder=str(z["encoder"]),
            distribution=str(z["distribution"]),
            # pre-rotation files have no rotation key: default none
            rotation=str(z["rotation"]) if "rotation" in z else vi.PQ_ROTATION_NONE,
        )
        pq.codebook = z["codebook"].astype(np.float32)
        if "rotation_matrix" in z:
            pq.rotation_matrix = z["rotation_matrix"].astype(np.float32)
        if "trained_rows" in z:  # files from before it was kept have none
            pq.trained_rows = int(z["trained_rows"])
        return pq
