#!/bin/sh
# Build the native runtime: liborion_runtime.so (loader + tokenizer).
# Plain C ABI — loaded via ctypes (orion_tpu/runtime/__init__.py).
set -e
cd "$(dirname "$0")"
g++ -O3 -fPIC -shared -std=c++17 -pthread \
    loader.cc tokenizer.cc bpe.cc corpusgen.cc \
    -o liborion_runtime.so
echo "built $(pwd)/liborion_runtime.so"
