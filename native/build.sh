#!/bin/sh
# Build the native engines into weaviate_tpu/_native/.
# ARCH_FLAGS: -march=native for a host-local build (default); container
# images that may run on other CPUs set a portable baseline instead
# (the Dockerfile uses -march=x86-64-v2).
set -e
cd "$(dirname "$0")"
OUT_DIR="../weaviate_tpu/_native"
ARCH_FLAGS="${ARCH_FLAGS:--march=native}"
mkdir -p "$OUT_DIR"
g++ -O3 $ARCH_FLAGS -std=c++17 -fopenmp -shared -fPIC -o "$OUT_DIR/libhnsw.so" hnsw.cpp
echo "built $OUT_DIR/libhnsw.so"
g++ -O3 $ARCH_FLAGS -std=c++17 -shared -fPIC -o "$OUT_DIR/libreply.so" reply.cpp
echo "built $OUT_DIR/libreply.so"
g++ -O3 $ARCH_FLAGS -std=c++17 -shared -fPIC -o "$OUT_DIR/liblsmget.so" lsm_get.cpp
echo "built $OUT_DIR/liblsmget.so"
g++ -O3 $ARCH_FLAGS -std=c++17 -pthread -shared -fPIC -o "$OUT_DIR/librescore.so" rescore.cpp
echo "built $OUT_DIR/librescore.so"
