#!/usr/bin/env bash
# Build the native libraries. Each is also built on first use by
# predictionio_tpu/utils/native.py (event log, ALS packing, text prep).
set -euo pipefail
cd "$(dirname "$0")"
g++ -O3 -std=c++17 -shared -fPIC -o libpio_eventlog.so eventlog.cc
g++ -O3 -std=c++17 -shared -fPIC -o libpio_alspack.so alspack.cc
g++ -O3 -std=c++17 -shared -fPIC -o libpio_textpack.so textpack.cc
echo "built $(pwd)/libpio_eventlog.so, libpio_alspack.so and libpio_textpack.so"
