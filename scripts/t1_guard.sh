#!/usr/bin/env bash
# t1_guard.sh — truncation guard around the tier-1 pytest run.
#
# A tier-1 run that is cut short — by the wall-clock budget (rc 124: the
# suite no longer fits the ROADMAP command's 870 s, ROADMAP.md D10) or
# by a process-fatal crash — ends with no summary line, the dot stream
# stops wherever it was cut, and a DOTS_PASSED count computed from the
# truncated log silently under-reports.  This wrapper:
#
#   1. collects the ordered test list (ids per file) up front;
#   2. runs the tier-1 suite once, teeing the log;
#   3. if the run TRUNCATED (no pytest summary line), maps the dot
#      stream back onto the collection order to find the file the crash
#      landed in, reruns THAT FILE AND EVERYTHING AFTER IT once, and
#      merges the dot counts: dots credited from run 1 are exactly the
#      outcomes of tests in files strictly before the crash file (the
#      crash file reruns whole, so none of its run-1 dots double-count);
#   4. emits the same DOTS_PASSED=<n> line the ROADMAP command does,
#      plus T1_GUARD=<clean|merged|truncated-twice> provenance.
#
# A second truncation is NOT retried (one rerun only — a guard, not a
# retry loop): the merged count so far is emitted with rc 139 so the
# flake stays visible instead of masquerading as green or red.
#
# The per-run budget is tunable: T1_BUDGET=<seconds> (default 870, the
# ROADMAP command's cap) applies to each of the two runs.
#
# Targeted reruns: T1_FILES is a space-separated allowlist of test
# files; when set (and no positional args are given) the guard runs
# exactly those files instead of the whole tier-1 sweep — the fast way
# to re-verify a specific area (e.g. the fleet fault tests) with the
# same truncation merge as the full run.
# T1_CACHE_OFF=1 additionally applies the MPI_TPU_DISABLE_COMPILE_CACHE
# kill switch to the FIRST run too (not just the rerun).
#
# Usage: scripts/t1_guard.sh            # the ROADMAP tier-1 invocation
#        scripts/t1_guard.sh tests/ -m 'not slow'   # custom args
#        T1_BUDGET=1200 scripts/t1_guard.sh         # grown suite
#        T1_FILES="tests/test_router.py tests/test_fault_injection.py" \
#            T1_CACHE_OFF=1 scripts/t1_guard.sh     # targeted, cache off
#        T1_FILES="tests/test_loadgen.py tests/test_bench.py" \
#            scripts/t1_guard.sh    # workload/goodput layer (loadgen is
#                                   # host-only: seconds, no jax dispatch)
#        T1_FILES="tests/test_paged_kernel.py tests/test_kv_quant.py" \
#            scripts/t1_guard.sh    # KV quantization + capacity-ladder
#                                   # layer: int8/int4 parity, error
#                                   # bounds, residual-lane + packing
#                                   # pins (test_paged_kernel) and the
#                                   # prefix/eviction/rollback/replay/
#                                   # host-tiering composition pins
#                                   # (test_kv_quant)
#        T1_FILES="tests/test_prefix_v2.py tests/test_serving.py" \
#            scripts/t1_guard.sh    # prefix sharing v2 smoke: gen-block
#                                   # insertion + partial tail copy +
#                                   # router hint (token identity, the
#                                   # refcount property test, knob
#                                   # coupling) next to the v1 cache,
#                                   # scheduler, and engine pins
#        T1_FILES="tests/test_mixed_batch.py tests/test_serving.py" \
#            scripts/t1_guard.sh    # mixed-batch smoke: fused-dispatch
#                                   # token identity (vs off and vs
#                                   # generate(), incl. eviction / int8
#                                   # / TP / replay), the zero-recompile
#                                   # pin, backlog + TTFT signals — next
#                                   # to the off-path engine pins it
#                                   # must leave byte-for-byte alone
#        T1_FILES="tests/test_tracing.py tests/test_analysis.py" \
#            scripts/t1_guard.sh    # tracing smoke: off-path token
#                                   # identity, span state machine,
#                                   # ring bound, Chrome JSON schema,
#                                   # breakdown-vs-stamp TTFT, failover
#                                   # span accumulation — plus the
#                                   # graft-lint knob/HOST-SYNC
#                                   # fixtures for --serve-trace

set -u
cd "$(dirname "$0")/.."

T1_BUDGET=${T1_BUDGET:-870}

# (No .jax_cache purge: under jaxlib 0.9.0 the round-trip canary in
# utils/cache.py DETECTS a box that cannot reload its own XLA:CPU AOT
# entries — this image is one, verdict "unsafe" — and leaves the CPU
# cache off there, so there is nothing to poison.)

# Pre-flight: the graft-lint static scan (docs/ANALYSIS.md) — the
# knob-bridge / recompile-hazard / host-sync / lock-discipline / names
# contracts are source properties, so a violation fails fast here
# instead of surfacing as a flaky runtime symptom mid-suite (or not at
# all).  Pure stdlib-ast work, ~a second.  T1_SKIP_LINT=1 opts out
# (e.g. when bisecting a runtime-only failure on a known-dirty tree).
if [ "${T1_SKIP_LINT:-0}" != "1" ]; then
    if ! env JAX_PLATFORMS=cpu python -m mpi_tensorflow_tpu.analysis; then
        echo "[t1_guard] graft-lint found new violations (above) — fix" \
             "or annotate them, or rerun with T1_SKIP_LINT=1"
        exit 1
    fi
fi

PYTEST_ARGS=("$@")
if [ ${#PYTEST_ARGS[@]} -eq 0 ]; then
    if [ -n "${T1_FILES:-}" ]; then
        # shellcheck disable=SC2206 — word splitting is the contract
        PYTEST_ARGS=(${T1_FILES} -m 'not slow')
    else
        PYTEST_ARGS=(tests/ -m 'not slow')
    fi
fi
COMMON=(-q --continue-on-collection-errors -p no:cacheprovider
        -p no:xdist -p no:randomly)
RUN_ENV=(env JAX_PLATFORMS=cpu)
if [ "${T1_CACHE_OFF:-0}" = "1" ]; then
    RUN_ENV+=(MPI_TPU_DISABLE_COMPILE_CACHE=1)
fi
LOG1=/tmp/_t1_guard_run1.log
LOG2=/tmp/_t1_guard_run2.log
COLLECT=/tmp/_t1_guard_collect.txt

# status-chars-per-line pattern: the -q progress stream (same regex the
# ROADMAP tier-1 command counts dots with)
PROGRESS_RE='^[.FEsx]+( *\[ *[0-9]+%\])?$'

summary_present() {
    # a completed pytest run always ends with a summary: under -q a bare
    # "N passed[, M failed]... in X.XXs" line (or "no tests ran"); the
    # decorated "==== ... ====" form appears with failures/-v
    grep -qaE '([0-9]+ (passed|failed|error|errors|skipped|xfailed|xpassed|deselected|warnings?)[, ].*in [0-9.]+s|[0-9]+ (passed|failed) in [0-9.]+s|no tests ran)' "$1"
}

dots_in() {
    grep -aE "$PROGRESS_RE" "$1" | tr -cd . | wc -c
}

# 1. ordered collection: "tests/test_x.py::TestC::test_y" per line
"${RUN_ENV[@]}" python -m pytest "${PYTEST_ARGS[@]}" "${COMMON[@]}" \
    --collect-only 2>/dev/null | grep -aE '^[^ ]+\.py::' > "$COLLECT" || true

# 2. the real run
"${RUN_ENV[@]}" timeout -k 10 "$T1_BUDGET" python -m pytest \
    "${PYTEST_ARGS[@]}" "${COMMON[@]}" 2>&1 | tee "$LOG1"
rc=${PIPESTATUS[0]}

if summary_present "$LOG1"; then
    echo "DOTS_PASSED=$(dots_in "$LOG1")"
    echo "T1_GUARD=clean"
    exit "$rc"
fi

echo "[t1_guard] no pytest summary line: run truncated (rc=$rc) — " \
     "rerunning the remaining files once"

# 3. locate the crash file from the truncated dot stream + collection
#    order, credit run-1 outcomes strictly before it, rerun the rest
readarray -t MERGE < <(python - "$COLLECT" "$LOG1" <<'EOF'
import re, sys

collect, log1 = sys.argv[1], sys.argv[2]
ids = [l.strip() for l in open(collect) if "::" in l]
files = []                      # ordered unique files
for tid in ids:
    f = tid.split("::", 1)[0]
    if not files or files[-1] != f:
        files.append(f)
stream = ""
pat = re.compile(r"^([.FEsx]+)( *\[ *\d+%\])?$")
# the crash usually garbles the FINAL progress line: completed-test
# chars then "Fatal Python error"/"Aborted" glued on with no newline —
# those chars are real outcomes and must not be dropped
garbled = re.compile(r"^([.FEsx]+)(?=Fatal Python error|Aborted)")
for line in open(log1, errors="replace"):
    line = line.rstrip("\n")
    m = pat.match(line)
    if m:
        stream += m.group(1)
        continue
    g = garbled.match(line)
    if g:
        stream += g.group(1)
k = len(stream)                 # tests with a recorded outcome
if not ids or k >= len(ids):
    # nothing collected, or every test reported yet no summary printed
    # (crash during teardown/summary): nothing left to rerun
    print(stream.count("."))
    print("1" if "F" in stream or "E" in stream else "0")
    sys.exit(0)
crash_file = ids[k].split("::", 1)[0]   # test k was in flight
n_before = sum(1 for t in ids if files.index(t.split("::", 1)[0])
               < files.index(crash_file))
credited = stream[:min(k, n_before)]
print(credited.count("."))
print("1" if "F" in credited or "E" in credited else "0")
print("\n".join(files[files.index(crash_file):]))
EOF
)
DOTS1=${MERGE[0]:-0}
RED1=${MERGE[1]:-0}
REMAIN=("${MERGE[@]:2}")

if [ ${#REMAIN[@]} -eq 0 ]; then
    echo "DOTS_PASSED=$DOTS1"
    echo "T1_GUARD=merged"
    [ "$RED1" = "1" ] && exit 1
    exit "$rc"
fi

# carry the original NON-PATH args (-m 'not slow', -k, ...) into the
# rerun: replacing the path args with the remaining files must not drop
# the selection filter, or the rerun would execute deselected tests and
# inflate the merged count
OPTS=()
for a in "${PYTEST_ARGS[@]}"; do
    [ -e "${a%%::*}" ] || OPTS+=("$a")
done

# rerun with the persistent compile cache OFF: if the truncation was an
# AOT entry aborting on reload (utils/cache.py same-host hazard), a
# rerun that reloads the same entry dies the same death.  Cold compiles
# for the remaining files are the price; slow beats fatal.
"${RUN_ENV[@]}" MPI_TPU_DISABLE_COMPILE_CACHE=1 timeout -k 10 "$T1_BUDGET" \
    python -m pytest "${REMAIN[@]}" "${OPTS[@]}" "${COMMON[@]}" \
    2>&1 | tee "$LOG2"
rc2=${PIPESTATUS[0]}

DOTS2=$(dots_in "$LOG2")
echo "DOTS_PASSED=$((DOTS1 + DOTS2))"
if ! summary_present "$LOG2"; then
    # truncated twice: emit what we know, stay loudly broken
    echo "T1_GUARD=truncated-twice"
    exit 139
fi
echo "T1_GUARD=merged"
if [ "$RED1" = "1" ]; then exit 1; fi
exit "$rc2"
