#!/usr/bin/env bash
# forget_foreign_cache.sh <build-dir>: a build tree configured for another
# source directory (one copied along with its checkout) makes cmake refuse
# at once ("CMakeCache.txt directory ... is different").  Drop its cache
# so the caller's cmake configures afresh; the directory itself stays (the
# inter-process build lock build.sh takes lives in native/build).
set -euo pipefail
cd "$(dirname "$0")"
cache="$1/CMakeCache.txt"
[[ -f "$cache" ]] || exit 0
home=$(sed -n 's/^CMAKE_HOME_DIRECTORY:INTERNAL=//p' "$cache")
if [[ "$(realpath -m "$home")" != "$(pwd -P)" ]]; then
  rm -rf "$cache" "$1/CMakeFiles" "$1/build.ninja"
fi
