# weaviate-tpu server image (reference analog: /root/reference/Dockerfile —
# build stage compiles the native pieces, the runtime stage is minimal and
# 12-factor: all configuration through environment variables).
#
# Build:  docker build -t weaviate-tpu .
# Run:    docker run -p 8080:8080 -v wtpu-data:/var/lib/weaviate weaviate-tpu
# Ready:  curl localhost:8080/v1/.well-known/ready
#
# The default install is the CPU jax wheel so the image runs anywhere; on a
# TPU VM build with:  --build-arg JAX_EXTRA="jax[tpu]" (pulls libtpu).

###############################################################################
FROM python:3.12-slim AS server_builder
RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make && rm -rf /var/lib/apt/lists/*
WORKDIR /app
COPY native/ native/
COPY weaviate_tpu/ weaviate_tpu/
# compile the native engines (CPU HNSW graph, gRPC reply marshaller, LSM
# point gets, the compressed tier's float32 rescoring) into
# weaviate_tpu/_native — the runtime never needs a compiler. Portable
# baseline ISA: the image must run on any x86-64-v2 host, not just the
# build machine (-march=native would SIGILL elsewhere).
RUN ARCH_FLAGS="-march=x86-64-v2" sh native/build.sh

###############################################################################
FROM python:3.12-slim AS weaviate-tpu
RUN apt-get update && apt-get install -y --no-install-recommends \
        curl libgomp1 && rm -rf /var/lib/apt/lists/* \
    && useradd -r -u 10001 weaviate \
    && mkdir -p /var/lib/weaviate && chown weaviate /var/lib/weaviate
ARG JAX_EXTRA="jax[cpu]"
RUN pip install --no-cache-dir "${JAX_EXTRA}" numpy grpcio protobuf
WORKDIR /app
COPY --from=server_builder /app/weaviate_tpu/ weaviate_tpu/
USER weaviate
ENV PERSISTENCE_DATA_PATH=/var/lib/weaviate \
    QUERY_DEFAULTS_LIMIT=25 \
    DEFAULT_VECTORIZER_MODULE=none \
    PYTHONUNBUFFERED=1
EXPOSE 8080 50051 7946 7947 2112
VOLUME /var/lib/weaviate
HEALTHCHECK --interval=10s --timeout=3s --start-period=30s \
    CMD curl -sf http://localhost:8080/v1/.well-known/ready || exit 1
ENTRYPOINT ["python", "-m", "weaviate_tpu"]
