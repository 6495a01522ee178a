#!/usr/bin/env bash
# Build the native engine (engine.cpp + fastcall.c) under a sanitizer
# and run the needs_native test lane against it.
#
#   tools/sanitize.sh              # ASan + UBSan (the default lane)
#   tools/sanitize.sh tsan         # ThreadSanitizer (mux/worker threads)
#   tools/sanitize.sh asan -k mux  # extra args forwarded to pytest
#
# BRPC_NATIVE_SANITIZE selects instrumented build flags and a distinct
# artifact name (_engine.<mode>-<key>.so) inside incubator_brpc_tpu/native;
# the sanitizer runtime must be LD_PRELOADed because stock CPython is
# not linked against it.  ASan leak checking is disabled: CPython's
# arena allocator holds blocks for the process lifetime and the lane
# is after memory-safety + UB, not interpreter leak noise.
#
# The lane excludes test_bench_smoke.py on purpose: its guards assert
# real performance floors, which instrumented builds cannot meet.
set -euo pipefail
cd "$(dirname "$0")/.."
MODE="${1:-asan}"
if [ "$#" -gt 0 ]; then shift; fi
case "$MODE" in
  asan)
    export ASAN_OPTIONS="${ASAN_OPTIONS:-detect_leaks=0:abort_on_error=1}"
    export UBSAN_OPTIONS="${UBSAN_OPTIONS:-print_stacktrace=1:halt_on_error=1}"
    ;;
  tsan)
    # exitcode=66: a clean pytest run still fails loudly if TSan saw
    # any report during the process.  tools/tsan.supp carries the one
    # glibc-owned suppression (_dl_deallocate_tls TLS reclamation);
    # engine code runs unsuppressed.
    SUPP="$PWD/tools/tsan.supp"
    export TSAN_OPTIONS="${TSAN_OPTIONS:-halt_on_error=0:exitcode=66:suppressions=$SUPP}"
    ;;
  *)
    echo "usage: $0 [asan|tsan] [pytest args...]" >&2
    exit 2
    ;;
esac
# single source of truth for runtime discovery: every required lib is
# existence-checked there, so a toolchain missing libubsan fails HERE
# instead of running a lane that silently lost its native coverage
PRELOAD="$(python -c "
from incubator_brpc_tpu import native
print(native.sanitizer_preload('$MODE') or '')")"
if [ -z "$PRELOAD" ]; then
  echo "sanitizer runtime(s) for '$MODE' not found in this toolchain" >&2
  exit 2
fi
export BRPC_NATIVE_SANITIZE="$MODE"
export LD_PRELOAD="$PRELOAD"
export JAX_PLATFORMS=cpu
exec python -m pytest \
  tests/test_native_engine.py \
  tests/test_native_multiproto.py \
  tests/test_fastpath_pool.py \
  tests/test_ring.py \
  tests/test_chaos.py \
  -q -m "not slow" -p no:cacheprovider "$@"
