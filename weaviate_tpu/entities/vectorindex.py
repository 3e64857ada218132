"""Vector-index user configs and the index-type registry.

Reference: entities/vectorindex/hnsw/config.go:33-66 (UserConfig + defaults),
pq_config.go:21-26 (PQ defaults), config.go:69-71 (IndexType discriminator),
config.go:101 (ParseAndValidateConfig — the registration seam injected into the
schema manager at configure_api.go:228).

Index types:
- "hnsw"      — native C++ HNSW graph (CPU), commit-log persisted (parity index)
- "hnsw_tpu"  — the TPU-native index: HBM-resident store, batched device
                distance evaluation + masked top-k; exact for shards below
                `ivf_threshold`, IVF-partitioned above. Accepts the full hnsw
                config surface (ef etc. are tuning no-ops where exact).
- "flat"      — alias of hnsw_tpu with exact-only search
- "noop"      — null index for classes with skip=true (vector/noop)
"""

from __future__ import annotations

import threading

from dataclasses import dataclass, field
from typing import Callable, Optional


class ConfigValidationError(ValueError):
    pass


DISTANCE_COSINE = "cosine"
DISTANCE_DOT = "dot"
DISTANCE_L2 = "l2-squared"
DISTANCE_MANHATTAN = "manhattan"
DISTANCE_HAMMING = "hamming"

DISTANCES = (
    DISTANCE_COSINE,
    DISTANCE_DOT,
    DISTANCE_L2,
    DISTANCE_MANHATTAN,
    DISTANCE_HAMMING,
)

# defaults mirroring entities/vectorindex/hnsw/config.go:33-49
DEFAULT_MAX_CONNECTIONS = 64
DEFAULT_EF_CONSTRUCTION = 128
DEFAULT_EF = -1  # dynamic
DEFAULT_DYNAMIC_EF_MIN = 100
DEFAULT_DYNAMIC_EF_MAX = 500
DEFAULT_DYNAMIC_EF_FACTOR = 8
DEFAULT_CLEANUP_INTERVAL_SECONDS = 300
DEFAULT_VECTOR_CACHE_MAX_OBJECTS = 1_000_000_000_000
DEFAULT_FLAT_SEARCH_CUTOFF = 40_000

# PQ defaults (pq_config.go:21-26)
DEFAULT_PQ_CENTROIDS = 256
DEFAULT_PQ_TRAINING_LIMIT = 100_000  # the reference's documented default
PQ_ENCODER_KMEANS = "kmeans"
PQ_ENCODER_TILE = "tile"
PQ_DISTRIBUTION_LOG_NORMAL = "log-normal"
PQ_DISTRIBUTION_NORMAL = "normal"
# TPU extension: learned orthogonal rotation before quantization (OPQ)
PQ_ROTATION_NONE = "none"
PQ_ROTATION_OPQ = "opq"


@dataclass
class PQEncoderConfig:
    type: str = PQ_ENCODER_KMEANS
    distribution: str = PQ_DISTRIBUTION_LOG_NORMAL


@dataclass
class PQConfig:
    enabled: bool = False
    bit_compression: bool = False
    segments: int = 0  # 0 = auto (= dims)
    centroids: int = DEFAULT_PQ_CENTROIDS
    encoder: PQEncoderConfig = field(default_factory=PQEncoderConfig)
    # TPU extensions: exact float rescoring of the PQ top-R candidates
    # (buys back the reference's PQ recall loss; 0 = auto R)
    rescore: bool = True
    rescore_limit: int = 0
    # TPU extension: 'opq' fits an orthogonal rotation (OPQ-NP) that
    # decorrelates segments — big raw-ADC recall gains on clustered
    # data for the codes-only tier; query-side cost is one tiny matmul
    rotation: str = PQ_ROTATION_NONE
    # TPU extension: quantization ladder depth. 8 = the classic uint8
    # codes. 4 adds a nibble-packed 16-centroid sub-quantizer beside the
    # 8-bit codes and serves through the three-stage re-ranking funnel
    # (4-bit ADC scan -> 8-bit ADC rescore of top-C -> bf16/exact rescore
    # of top-c; ops/pq4.py) — half the scanned bytes per row at matched
    # recall through the funnel
    bits: int = 8
    # the reference's `trainingLimit`: at most this many rows fit the
    # codebook. A class that DECLARES pq compresses when its row count
    # reaches it (the documented procedure enables pq after that many
    # objects are imported); an explicit enable fits on a sample of at
    # most this many of the rows that are there
    training_limit: int = DEFAULT_PQ_TRAINING_LIMIT

    @classmethod
    def from_dict(cls, d: dict) -> "PQConfig":
        enc = d.get("encoder") or {}
        return cls(
            enabled=bool(d.get("enabled", False)),
            bit_compression=bool(d.get("bitCompression", False)),
            segments=int(d.get("segments", 0)),
            centroids=int(d.get("centroids", DEFAULT_PQ_CENTROIDS)),
            encoder=PQEncoderConfig(
                type=enc.get("type", PQ_ENCODER_KMEANS),
                distribution=enc.get("distribution", PQ_DISTRIBUTION_LOG_NORMAL),
            ),
            rescore=bool(d.get("rescore", True)),
            rescore_limit=int(d.get("rescoreLimit", 0)),
            rotation=str(d.get("rotation", PQ_ROTATION_NONE)),
            bits=int(d.get("bits", 8)),
            training_limit=int(d.get("trainingLimit",
                                     DEFAULT_PQ_TRAINING_LIMIT)),
        )

    def to_dict(self) -> dict:
        return {
            "enabled": self.enabled,
            "bitCompression": self.bit_compression,
            "segments": self.segments,
            "centroids": self.centroids,
            "encoder": {"type": self.encoder.type, "distribution": self.encoder.distribution},
            "rescore": self.rescore,
            "rescoreLimit": self.rescore_limit,
            "rotation": self.rotation,
            "bits": self.bits,
            "trainingLimit": self.training_limit,
        }


@dataclass
class HnswUserConfig:
    """UserConfig shared by "hnsw" and "hnsw_tpu" (config.go:52-66)."""

    index_type: str = "hnsw_tpu"
    skip: bool = False
    cleanup_interval_seconds: int = DEFAULT_CLEANUP_INTERVAL_SECONDS
    max_connections: int = DEFAULT_MAX_CONNECTIONS
    ef_construction: int = DEFAULT_EF_CONSTRUCTION
    ef: int = DEFAULT_EF
    dynamic_ef_min: int = DEFAULT_DYNAMIC_EF_MIN
    dynamic_ef_max: int = DEFAULT_DYNAMIC_EF_MAX
    dynamic_ef_factor: int = DEFAULT_DYNAMIC_EF_FACTOR
    vector_cache_max_objects: int = DEFAULT_VECTOR_CACHE_MAX_OBJECTS
    flat_search_cutoff: int = DEFAULT_FLAT_SEARCH_CUTOFF
    distance: str = DISTANCE_COSINE
    pq: PQConfig = field(default_factory=PQConfig)
    # hnsw_tpu extras
    ivf_threshold: int = 4_000_000   # above this shard size, switch exact → IVF
    ivf_nlist: int = 0               # 0 = auto (~sqrt(N) rounded to mult of 8)
    ivf_nprobe: int = 64
    query_batch_window_ms: float = 1.0  # cross-query batching window
    store_dtype: str = "float32"        # device store dtype: float32 | bfloat16
    exact_topk: bool = False            # force lax.top_k over approx_min_k
    mesh_devices: int = 0               # hnsw_tpu_mesh: chips to shard over (0 = all)

    def IndexType(self) -> str:  # discriminator parity (config.go:69-71)
        return self.index_type

    def distance_name(self) -> str:
        return self.distance

    def to_dict(self) -> dict:
        return {
            "skip": self.skip,
            "cleanupIntervalSeconds": self.cleanup_interval_seconds,
            "maxConnections": self.max_connections,
            "efConstruction": self.ef_construction,
            "ef": self.ef,
            "dynamicEfMin": self.dynamic_ef_min,
            "dynamicEfMax": self.dynamic_ef_max,
            "dynamicEfFactor": self.dynamic_ef_factor,
            "vectorCacheMaxObjects": self.vector_cache_max_objects,
            "flatSearchCutoff": self.flat_search_cutoff,
            "distance": self.distance,
            "pq": self.pq.to_dict(),
            "ivfThreshold": self.ivf_threshold,
            "ivfNlist": self.ivf_nlist,
            "ivfNprobe": self.ivf_nprobe,
            "queryBatchWindowMs": self.query_batch_window_ms,
            "storeDtype": self.store_dtype,
            "exactTopK": self.exact_topk,
            "meshDevices": self.mesh_devices,
        }

    @classmethod
    def from_dict(cls, d: Optional[dict], index_type: str = "hnsw_tpu") -> "HnswUserConfig":
        d = d or {}
        cfg = cls(
            index_type=index_type,
            skip=bool(d.get("skip", False)),
            cleanup_interval_seconds=int(d.get("cleanupIntervalSeconds", DEFAULT_CLEANUP_INTERVAL_SECONDS)),
            max_connections=int(d.get("maxConnections", DEFAULT_MAX_CONNECTIONS)),
            ef_construction=int(d.get("efConstruction", DEFAULT_EF_CONSTRUCTION)),
            ef=int(d.get("ef", DEFAULT_EF)),
            dynamic_ef_min=int(d.get("dynamicEfMin", DEFAULT_DYNAMIC_EF_MIN)),
            dynamic_ef_max=int(d.get("dynamicEfMax", DEFAULT_DYNAMIC_EF_MAX)),
            dynamic_ef_factor=int(d.get("dynamicEfFactor", DEFAULT_DYNAMIC_EF_FACTOR)),
            vector_cache_max_objects=int(d.get("vectorCacheMaxObjects", DEFAULT_VECTOR_CACHE_MAX_OBJECTS)),
            flat_search_cutoff=int(d.get("flatSearchCutoff", DEFAULT_FLAT_SEARCH_CUTOFF)),
            distance=d.get("distance", DISTANCE_COSINE),
            pq=PQConfig.from_dict(d.get("pq") or {}),
            ivf_threshold=int(d.get("ivfThreshold", 4_000_000)),
            ivf_nlist=int(d.get("ivfNlist", 0)),
            ivf_nprobe=int(d.get("ivfNprobe", 64)),
            query_batch_window_ms=float(d.get("queryBatchWindowMs", 1.0)),
            store_dtype=d.get("storeDtype", "float32"),
            exact_topk=bool(d.get("exactTopK", False)),
            mesh_devices=int(d.get("meshDevices", 0)),
        )
        cfg.validate()
        return cfg

    def validate(self) -> None:
        if self.distance not in DISTANCES:
            raise ConfigValidationError(
                f"invalid distance {self.distance!r}; must be one of {DISTANCES}"
            )
        if self.max_connections < 4:
            raise ConfigValidationError("maxConnections must be >= 4")
        if self.ef_construction < 4:
            raise ConfigValidationError("efConstruction must be >= 4")
        if self.ef != -1 and self.ef < 1:
            raise ConfigValidationError("ef must be -1 (dynamic) or >= 1")
        if self.store_dtype not in ("float32", "bfloat16"):
            raise ConfigValidationError(
                f"storeDtype must be 'float32' or 'bfloat16', got {self.store_dtype!r}"
            )
        if self.pq.enabled:
            if self.pq.centroids < 1 or self.pq.centroids > 65536:
                raise ConfigValidationError("pq.centroids must be in [1, 65536]")
            if self.pq.encoder.type not in (PQ_ENCODER_KMEANS, PQ_ENCODER_TILE):
                raise ConfigValidationError(f"invalid pq encoder {self.pq.encoder.type!r}")
            if self.pq.rotation not in (PQ_ROTATION_NONE, PQ_ROTATION_OPQ):
                raise ConfigValidationError(
                    f"invalid pq rotation {self.pq.rotation!r} (none|opq)")
            if self.pq.bits not in (4, 8):
                raise ConfigValidationError("pq.bits must be 4 or 8")
            if self.pq.training_limit < 1:
                raise ConfigValidationError(
                    "pq.trainingLimit must be at least 1")
            if self.pq.bits == 4:
                if self.distance not in (DISTANCE_L2, DISTANCE_DOT,
                                         DISTANCE_COSINE):
                    # the funnel's 4-bit scan and 8-bit rescore are both
                    # matmul-ADC formulations; manhattan's LUT tier has no
                    # 4-bit twin, and a config that silently served 8-bit
                    # would misreport its memory floor
                    raise ConfigValidationError(
                        "pq.bits=4 requires an l2-squared/dot/cosine distance")
                if self.pq.encoder.type != PQ_ENCODER_KMEANS:
                    raise ConfigValidationError(
                        "pq.bits=4 requires the kmeans encoder")
            if not self.pq.rescore:
                # Codes-only ADC over a flat scan has no graph to localize
                # candidates, so the quantizer's intrinsic error lands directly
                # on the result set (recall@10 ≈ 0.24 on the synthetic bench vs
                # ≈ 0.95+ rescored). Loud at config time; opting in stays legal.
                # Rate-limited: validate() runs on every config load/update
                # across every class, and a fleet restart would otherwise
                # emit one warning per shard. The degraded mode also stays
                # visible structurally — health() reports "rescore": false
                # in GET /debug/index.
                _warn_rescore_off()


_RESCORE_WARN_INTERVAL_S = 60.0
_rescore_warn_last = [0.0]  # module-level: one rate limit per process
_rescore_warn_lock = threading.Lock()


def _warn_rescore_off() -> None:
    import logging
    import time as _time

    with _rescore_warn_lock:
        now = _time.monotonic()
        if now - _rescore_warn_last[0] < _RESCORE_WARN_INTERVAL_S:
            return
        _rescore_warn_last[0] = now
    logging.getLogger(__name__).warning(
        "pq.rescore=false serves raw ADC distances with NO exact "
        "rescoring pass: expect a severe recall drop on flat scans "
        "(recall@10 ~0.24 vs ~0.95+ with rescoring on the synthetic "
        "bench). Set pq.rescore=true (default) unless you need the "
        "absolute memory floor; pq.rotation='opq' recovers part of "
        "the loss for codes-only serving."
    )


IMMUTABLE_FIELDS = (
    # reference: usecases/schema vector-index config update validation
    "max_connections",
    "ef_construction",
    "cleanup_interval_seconds",
    "distance",
)


def validate_config_update(old: HnswUserConfig, new: HnswUserConfig) -> None:
    """Hot-update validation (reference: hnsw/config_update.go — mutable: ef,
    dynamic-ef, flatSearchCutoff, vectorCacheMaxObjects, pq)."""
    for f in IMMUTABLE_FIELDS:
        if getattr(old, f) != getattr(new, f):
            raise ConfigValidationError(f"{f} is immutable: can't update vector index config")
    if old.pq.enabled and not new.pq.enabled:
        raise ConfigValidationError("pq is already enabled: can't disable")


_PARSERS: dict[str, Callable[[Optional[dict]], HnswUserConfig]] = {}
# modules register index types at import AND at runtime (plugin reload),
# while serving threads resolve configs concurrently — mutation takes the
# lock; lookups ride the GIL-atomic dict read
_parsers_lock = threading.Lock()


def register_index_type(name: str, parser: Callable[[Optional[dict]], HnswUserConfig]) -> None:
    with _parsers_lock:
        _PARSERS[name] = parser


def registered_index_types() -> list[str]:
    with _parsers_lock:
        return sorted(_PARSERS)


def parse_and_validate_config(index_type: str, cfg: Optional[dict]) -> HnswUserConfig:
    """The seam where index types register (config.go:101 / configure_api.go:228)."""
    parser = _PARSERS.get(index_type)
    if parser is None:
        raise ConfigValidationError(
            f"unknown vectorIndexType {index_type!r}; registered: {sorted(_PARSERS)}"
        )
    return parser(cfg)


register_index_type("hnsw", lambda d: HnswUserConfig.from_dict(d, "hnsw"))
register_index_type("hnsw_tpu", lambda d: HnswUserConfig.from_dict(d, "hnsw_tpu"))
register_index_type("hnsw_tpu_mesh", lambda d: HnswUserConfig.from_dict(d, "hnsw_tpu_mesh"))
register_index_type("flat", lambda d: HnswUserConfig.from_dict(d, "flat"))
register_index_type("noop", lambda d: HnswUserConfig.from_dict({**(d or {}), "skip": True}, "noop"))
