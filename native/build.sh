#!/usr/bin/env bash
# Build the native core and install the shared library into the Python
# package (brpc_tpu/_native/). Run from anywhere.
#
# Primary path: cmake+ninja (CMakeLists.txt is the source of truth).
# Fallback: a direct g++ build with the same flags, for containers that
# carry a compiler but no build system — same outputs, same install.
set -euo pipefail
cd "$(dirname "$0")"
mkdir -p build
# one build at a time per tree (xdist workers' first loads, chip_smoke.py,
# a developer): a waiter then finds nothing left to build
exec 9>build/.build.lock
flock 9

if command -v cmake >/dev/null 2>&1 && command -v ninja >/dev/null 2>&1; then
  bash forget_foreign_cache.sh build
  cmake -S . -B build -G Ninja -DCMAKE_BUILD_TYPE=Release >/dev/null
  ninja -C build
else
  # direct g++ fallback (mirrors CMakeLists.txt Release flags; the
  # source list lives ONCE in sources.lst so the shell builds can't
  # drift from each other)
  CXX="${CXX:-g++}"
  LIB_SRCS=$(grep -v '^#' sources.lst | tr '\n' ' ')
  FLAGS="-std=c++17 -O2 -g -DNDEBUG -fPIC -pthread"
  PJRT_INC="$(bash pjrt_include.sh)"
  PJRT_FLAGS=""
  if [[ -n "${PJRT_INC}" ]]; then
    PJRT_FLAGS="-I${PJRT_INC} -DTRPC_HAVE_PJRT_HEADER=1"
  fi
  # shellcheck disable=SC2086
  ${CXX} ${FLAGS} ${PJRT_FLAGS} -shared ${LIB_SRCS} \
    -o build/libbrpc_tpu_core.so -ldl
  if [[ -n "${PJRT_INC}" ]]; then
    ${CXX} -std=c++17 -O2 -g -DNDEBUG -fPIC -pthread -I"${PJRT_INC}" \
      -shared src/pjrt_fake.cc -o build/libpjrt_fake.so
  fi
  # shellcheck disable=SC2086
  ${CXX} ${FLAGS} ${PJRT_FLAGS} src/test_core.cc -o build/test_core \
    -Lbuild -lbrpc_tpu_core -Wl,-rpath,'$ORIGIN'
  # shellcheck disable=SC2086
  ${CXX} ${FLAGS} ${PJRT_FLAGS} src/test_stress.cc -o build/test_stress \
    -Lbuild -lbrpc_tpu_core -Wl,-rpath,'$ORIGIN'
fi
# atomic install: running processes keep their mapped copy (an in-place
# cp would rewrite the inode under them and crash mid-run test suites)
cp build/libbrpc_tpu_core.so ../brpc_tpu/_native/.libbrpc_tpu_core.so.tmp
mv ../brpc_tpu/_native/.libbrpc_tpu_core.so.tmp ../brpc_tpu/_native/libbrpc_tpu_core.so
if [[ -f build/libpjrt_fake.so ]]; then
  cp build/libpjrt_fake.so ../brpc_tpu/_native/.libpjrt_fake.so.tmp
  mv ../brpc_tpu/_native/.libpjrt_fake.so.tmp ../brpc_tpu/_native/libpjrt_fake.so
fi
if [[ "${1:-}" == "--test" ]]; then
  ./build/test_core
fi
echo "native core built -> brpc_tpu/_native/libbrpc_tpu_core.so"
