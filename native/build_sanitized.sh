#!/usr/bin/env bash
# Build the instrumented stress binary:
#   build_sanitized.sh <thread|address|undefined>
# -> native/build-{tsan|asan|ubsan}/test_stress, from the LIVE sources.
# The undefined flavor (ISSUE 10) runs with -fno-sanitize-recover=all:
# any UB (shift/overflow in crc32c/codec block math, misaligned loads,
# ...) aborts the scenario instead of silently wrapping.
#
# build_sanitized.sh <flavor> --sweep N [base-seed] additionally runs the
# seed sweep on the freshly built tree: N full gate runs, each under a
# distinct TRPC_SCHED_SEED (schedule perturbation; BENCH_NOTES.md
# "Schedule replay") — the on-demand hunt for schedule-dependent
# sanitizer aborts.
#
# Primary path: cmake -DSANITIZE=... + ninja (incremental).  Fallback for
# containers without a build system: direct g++ with the same flags, with
# a timestamp check standing in for incrementality.  Exit 3 means "no
# sanitizer toolchain/runtime here" (callers skip, not fail).
set -euo pipefail
cd "$(dirname "$0")"
flavor="${1:?usage: build_sanitized.sh <thread|address|undefined> \
[--sweep N [base]]}"
case "$flavor" in
  thread)    dir=build-tsan ;;
  address)   dir=build-asan ;;
  undefined) dir=build-ubsan ;;
  *) echo "flavor must be thread, address or undefined" >&2; exit 2 ;;
esac

run_sweep_if_asked() {
  if [[ "${2:-}" == "--sweep" ]]; then
    # forward N, optional base, and any trailing scenario filters
    # verbatim (test_stress parses the tail itself)
    : "${3:?--sweep needs N}"
    exec "$dir/test_stress" --sweep "${@:3}"
  fi
}

if command -v cmake >/dev/null 2>&1 && command -v ninja >/dev/null 2>&1; then
  bash forget_foreign_cache.sh "$dir"
  if [[ ! -f "$dir/build.ninja" ]]; then
    cmake -S . -B "$dir" -G Ninja -DSANITIZE="$flavor" >/dev/null || exit 3
  fi
  # ALWAYS run ninja: incremental, and a stale binary would test old code
  if ! out=$(ninja -C "$dir" test_stress 2>&1); then
    if grep -qE "cannot find -l(t|a|ub)san|lib(t|a|ub)san.*No such file" \
        <<<"$out"; then
      exit 3
    fi
    echo "$out" >&2
    exit 1
  fi
  run_sweep_if_asked "$@"
  exit 0
fi

# --- direct g++ fallback (mirrors CMakeLists.txt SANITIZE flags) -----------
CXX="${CXX:-g++}"
command -v "$CXX" >/dev/null 2>&1 || exit 3
if [[ "$flavor" == "thread" ]]; then
  # gcc < 12's libtsan cannot model the fiber-switch annotations
  # (__tsan_switch_to_fiber): measured on this container class, gcc-10
  # TSAN reports ~270 false "double lock"/"data race" warnings on the
  # UNMODIFIED seed's first butex scenario.  Require a toolchain whose
  # fiber support is usable, else report "no toolchain" (callers skip).
  if "$CXX" --version | head -1 | grep -qE ' (1[2-9]|[2-9][0-9])\.'; then
    :
  elif command -v clang++ >/dev/null 2>&1; then
    CXX=clang++
  else
    echo "thread sanitizer fallback needs g++>=12 or clang++ (gcc-10 \
libtsan false-positives on fiber switches)" >&2
    exit 3
  fi
fi
mkdir -p "$dir"
exe="$dir/test_stress"
# incrementality stand-in: rebuild only when any source is newer
if [[ -x "$exe" ]]; then
  newest=$(find src CMakeLists.txt -newer "$exe" -print -quit 2>/dev/null)
  if [[ -z "$newest" ]]; then
    run_sweep_if_asked "$@"
    exit 0
  fi
fi
# shared source list (see sources.lst) + the stress driver
SRCS="$(grep -v '^#' sources.lst | tr '\n' ' ') src/test_stress.cc"
FLAGS="-std=c++17 -fsanitize=$flavor -fno-omit-frame-pointer -O1 -g \
  -fPIC -pthread"
if [[ "$flavor" == "undefined" ]]; then
  # UB aborts the run (exit != 0) instead of printing-and-continuing —
  # the gate contract: fix the UB, never suppress it
  FLAGS+=" -fno-sanitize-recover=all"
fi
PJRT_INC="$(bash pjrt_include.sh)"  # shared probe: see pjrt_include.sh
PJRT_FLAGS=""
if [[ -n "${PJRT_INC}" ]]; then
  PJRT_FLAGS="-I${PJRT_INC} -DTRPC_HAVE_PJRT_HEADER=1"
fi
# shellcheck disable=SC2086
if ! out=$(${CXX} ${FLAGS} ${PJRT_FLAGS} ${SRCS} -o "$exe" -ldl 2>&1); then
  if grep -qE "cannot find -l(t|a|ub)san|lib(t|a|ub)san.*No such file" \
      <<<"$out"
  then
    exit 3
  fi
  echo "$out" >&2
  exit 1
fi
# the fake PJRT plugin next to the binary (the tpu/stream scenarios
# dlopen it; uninstrumented on purpose — it is the device under test's
# PEER, and the sanitizers only need to see our side)
if [[ -n "${PJRT_INC}" && ! -f "$dir/libpjrt_fake.so" ]]; then
  ${CXX} -std=c++17 -O1 -g -fPIC -pthread -I"${PJRT_INC}" \
    -shared src/pjrt_fake.cc -o "$dir/libpjrt_fake.so" || true
fi
run_sweep_if_asked "$@"
exit 0
