#!/bin/sh
# Full test suite — slow tests included — sharded across CPUs.
#
# The default `pytest tests/` path deselects slow-marked tests to stay fast
# (pytest.ini); this script is the complete gate: run it before landing
# changes to the parallel/runtime layers. ~18 min on an 8-core box.
#
# Static analysis runs FIRST: the dlint lint head (tools/dlint.py, also
# `python -m distributed_llama_tpu.analysis`) fails the gate on any finding
# not grandfathered in tools/dlint_baseline.txt — a new implicit sync or
# retrace trap stops the build before 18 minutes of tests do — the jaxpr
# contract head verifies the program-structure contracts (J001 for ALL
# THREE tp collective schemes, ref/fused/overlap; a collective added to
# the tp forward without its comm_stats term fails here), and the
# shardcheck head proves every (model, tp, scheme, dtype, kv-quant)
# config of the 84-config support matrix shards as declared and fits
# per-device HBM (J004/J005/J006 + budget + KV-PAGED/KV-QUANT). (The same
# contracts also run inside the suite, tests/test_jaxpr_contracts.py and
# tests/test_shardcheck_repo.py; tools/ probe scripts are outside the lint
# surface by design.)
#
# C++ static analysis rides along when the toolchain exists: clang-tidy
# over csrc/host.cpp (csrc/.clang-tidy) and an ASan/UBSan smoke run of
# every extern-C entry point (csrc/sanitize_main.cpp). Both skip cleanly
# on boxes without the tools — the Python suite never depends on them.
#
# Usage: tools/ci.sh [extra pytest args]
set -eu
cd "$(dirname "$0")/.."
# --all = dlint + jaxpr contracts (J002 now runs per cache LAYOUT:
# contiguous + paged donation both pinned) + the full 84-config shardcheck
# matrix re-run (which also pins the paged-pool footprint formula to the
# contiguous stripe at equal capacity — the KV-PAGED check — and the q8
# KV-quant column's byte formula + 2x capacity floor — KV-QUANT)
python -m distributed_llama_tpu.analysis --all
# Thread-safety gate (ISSUE 17): the --all run above already includes
# the threadcheck ownership lint (zero findings beyond the empty
# baseline); racecheck is its dynamic twin — the REAL cross-thread seam
# code (pool vs DCN adoption, uploader settle, ingest vs cancel sweep,
# ledger drain) driven through >= 100 deterministic interleavings per
# seam with the allocator-audit + ledger-conservation oracles after
# every schedule. The JSON row is archived next to the other artifacts.
mkdir -p tools/ci_artifacts
python tools/racecheck.py > tools/ci_artifacts/racecheck.json
# ... and the race gate must still CATCH a race: with drop-a-lock armed
# (page allocation split into the read/claim half-ops a dropped pool
# lock admits) the allocator audit must flag a schedule and exit 1
# EXACTLY — 2 is a usage error and would pass a naive non-zero check
set +e
python tools/racecheck.py --seam pool_adopt --inject drop-a-lock \
    > /dev/null 2>&1
droplock_rc=$?
set -e
if [ "$droplock_rc" -ne 1 ]; then
    echo "ci: racecheck did not flag the dropped pool lock" \
         "(exit $droplock_rc, expected 1)" >&2
    exit 1
fi
# ... and with reorder-inbox armed (the ingest inbox drained in reversed
# order) the FIFO admission-order oracle must flag it the same way
set +e
python tools/racecheck.py --seam ingest_sweep --inject reorder-inbox \
    > /dev/null 2>&1
reorder_rc=$?
set -e
if [ "$reorder_rc" -ne 1 ]; then
    echo "ci: racecheck did not flag the reordered ingest inbox" \
         "(exit $reorder_rc, expected 1)" >&2
    exit 1
fi
# Wire-contract gate (ISSUE 19): the --all run above already includes
# the wirecheck schema-drift head (zero findings beyond the empty
# baseline in tools/wirecheck_baseline.txt); the skew matrix is its
# dynamic twin — current code must round-trip its own golden corpus
# (tests/fixtures/wire/) byte-exactly AND read every legacy-era (N-1)
# sample: journal recovery, disagg handoff, pagewire CRC frames, fleet
# /health + /metrics parsing, flight-recorder bundles. The
# fingerprint-stamped JSON row is archived next to the other artifacts.
mkdir -p tools/ci_artifacts
python tools/wirecheck.py --json > tools/ci_artifacts/wirecheck.json
# ... and the corpus must REGENERATE byte-identically: a producer whose
# bytes drifted from the checked-in samples is a silent wire break
rm -rf tools/ci_artifacts/wire_regen
python tools/make_wire_corpus.py --out tools/ci_artifacts/wire_regen \
    > /dev/null
if ! diff -r tests/fixtures/wire tools/ci_artifacts/wire_regen \
        > /dev/null 2>&1; then
    echo "ci: wire corpus regeneration is not byte-identical —" \
         "a wire producer drifted (rerun tools/make_wire_corpus.py" \
         "and review the diff)" >&2
    exit 1
fi
rm -rf tools/ci_artifacts/wire_regen
# ... and the gate must still CATCH drift: with skew-reader armed (two
# legacy samples corrupted in memory before the real readers run) the
# matrix must exit 1 EXACTLY — 2 is a usage error and would pass a
# naive non-zero check vacuously
set +e
python tools/wirecheck.py --inject skew-reader > /dev/null 2>&1
skewreader_rc=$?
set -e
if [ "$skewreader_rc" -ne 1 ]; then
    echo "ci: wirecheck did not flag the corrupted legacy samples" \
         "(exit $skewreader_rc, expected 1)" >&2
    exit 1
fi
# ... and the STATIC head must catch a registry hole the same way: with
# journal.admit's 'cursor' field deleted from an in-memory copy of the
# wiremodel, the producer sites become unregistered-key writers and the
# lint must exit 1 EXACTLY
set +e
python tools/wirecheck.py --inject drop-registry-field > /dev/null 2>&1
dropfield_rc=$?
set -e
if [ "$dropfield_rc" -ne 1 ]; then
    echo "ci: wirecheck did not flag the deleted registry field" \
         "(exit $dropfield_rc, expected 1)" >&2
    exit 1
fi
# paged-vs-contiguous equivalence gate (ISSUE 6): paged decode must stay
# BITWISE equal to the contiguous cache and stream-invisible in the
# engine, and the shared-prompt radix path must actually share — fail
# fast here before the full suite (the same tests also run in tier-1)
python -m pytest tests/test_paging.py -q -p no:cacheprovider \
    -k "bitwise or streams_match or shared_system_prompt"
# paged flash-decode kernel gate (ISSUE 11): the Pallas page-table walk
# must agree with the XLA gather path at the documented flash tolerance
# on both hot shapes (decode + K-query verify), be BITWISE invariant to
# physical page placement, and the q8 page path must match its own XLA
# dequant fallback; the q8 engine streams must be deterministic across
# every scheduler and pinned stable on the CPU smoke model. The full
# tp x scheme x kv-quant routing grid is slow-marked (the fast suite
# keeps the single-chip routing cases) — include it here
python -m pytest tests/test_pallas_paged_attention.py -q \
    -p no:cacheprovider -m "slow or not slow"
# ... and the shardcheck KV-quant column must still CATCH a stale q8
# verdict: a matrix declaring a q8 config NOT to fit that fits must exit
# 1 EXACTLY (the PR 4 stale-matrix contract; 2 is a usage error and
# would pass a naive non-zero check vacuously)
mkdir -p tools/ci_artifacts
python -c "import json; json.dump([{'model': '7b', 'tp': 8, 'scheme': \
'fused', 'wtype': 'q40', 'expect_fits': False, 'kv_quant': 'q8'}], \
open('tools/ci_artifacts/stale_q8_matrix.json', 'w'))"
set +e
python tools/shardcheck.py --matrix tools/ci_artifacts/stale_q8_matrix.json \
    > /dev/null 2>&1
kvquant_rc=$?
set -e
if [ "$kvquant_rc" -ne 1 ]; then
    echo "ci: shardcheck did not flag the stale q8 matrix verdict" \
         "(exit $kvquant_rc, expected 1)" >&2
    exit 1
fi
# speculative losslessness gate (ISSUE 7): greedy spec-on token streams
# must be BITWISE the spec-off streams (across codecs, both tp schemes,
# paged cache) and rejected-suffix pages must return to the pool. The
# J001 verify-forward collective census per scheme runs in the --all
# contracts above — a collective added to the K-query verify dispatch
# without its comm_stats t_len term fails there.
python -m pytest tests/test_speculative.py -q -p no:cacheprovider \
    -k "bitwise or streams or rollback"
# KV-tiering gate (ISSUE 12): the continuous_bench tiering section on the
# CPU smoke model — prefix-hit prefill savings at a working set 10x the
# HBM page pool must hold within 20% of the all-HBM ceiling through the
# HBM->host->disk spill/promote churn (drop-on-evict baseline near zero),
# streams identical, three-tier audit clean (assertions inside the
# section); the row is archived next to the other artifacts
mkdir -p tools/ci_artifacts
python tools/continuous_bench.py --small --steps 12 --requests 3 \
    --block-steps 4 --no-paged-compare --no-spec-compare \
    --no-kv-quant-compare > tools/ci_artifacts/tiering_bench.json
# ... and the spill-storm chaos drill must pass healthy AND its seeded
# mutation must fail: with drop-on-demote armed (every write-behind
# demotion discards its payload) the drill must exit 1 EXACTLY — 2 is a
# usage error and would pass a naive non-zero check vacuously
python tools/loadcheck.py --drills-only --drills tier_spill_storm \
    --json > /dev/null
set +e
python tools/loadcheck.py --drills-only --drills tier_spill_storm \
    --inject drop-on-demote --json > /dev/null 2>&1
tier_rc=$?
set -e
if [ "$tier_rc" -ne 1 ]; then
    echo "ci: loadcheck did not flag the dropped tier demotion" \
         "(exit $tier_rc, expected 1)" >&2
    exit 1
fi
# Disaggregation gate (ISSUE 14): the virtual-clock two-pool sweep must
# show the disaggregated topology BEATING the colocated baseline on
# interactive-class SLO attainment at equal simulated hardware under the
# mixed interactive/batch trace (the fingerprinted row is archived), and
# the kill-mid-handoff drill must pass: decode pool killed mid-page-
# transfer, recovery via its journal bitwise vs the uninterrupted run,
# both pools' page audits clean
python tools/loadcheck.py --two-pool --sweep-only --json \
    > tools/ci_artifacts/two_pool.json
python tools/loadcheck.py --drills-only --drills kill_mid_handoff \
    --json > /dev/null
# ... and the gate must still CATCH wrong bytes on the wire: with
# drop-page-in-flight armed (every shipped page zeroed under a VALID
# CRC — corruption framing cannot see), the bitwise stream gate must
# exit 1 EXACTLY — 2 is a usage error and would pass a naive non-zero
# check vacuously
set +e
python tools/loadcheck.py --drills-only --drills kill_mid_handoff \
    --inject drop-page-in-flight --json > /dev/null 2>&1
disagg_rc=$?
set -e
if [ "$disagg_rc" -ne 1 ]; then
    echo "ci: loadcheck did not flag the dropped in-flight handoff page" \
         "(exit $disagg_rc, expected 1)" >&2
    exit 1
fi
# Token-budget scheduling gate (ISSUE 18): the virtual-clock budget
# comparison must show colocated engines with --dispatch-tokens closing
# the prefill-interference gap — best budget point reaching interactive
# attainment >= 0.90 at equal simulated hardware WITHOUT losing goodput
# to the separate-dispatch colocated baseline (the fingerprinted row is
# archived next to the two-pool one)
python tools/loadcheck.py --budget 8,12,16 --sweep-only --json \
    > tools/ci_artifacts/budget_sweep.json
# ... and the budget must be LOAD-BEARING: with overrun-budget armed
# (mixed prefill slices packed past the token budget), the overrun gate
# must exit 1 EXACTLY — 2 is a usage error and would pass a naive
# non-zero check vacuously
set +e
python tools/loadcheck.py --budget 8,12,16 --sweep-only \
    --inject overrun-budget --json > /dev/null 2>&1
budget_rc=$?
set -e
if [ "$budget_rc" -ne 1 ]; then
    echo "ci: loadcheck did not flag the overrun token budget" \
         "(exit $budget_rc, expected 1)" >&2
    exit 1
fi
# Distributed-tracing gate (ISSUE 15): the two-pool tracejoin drill —
# real DisaggPair over the TCP page channel — must stitch both pools'
# NDJSON exports into ONE valid Chrome trace (zero orphans, the handoff
# send/recv anchor pair present, >= 1 trace spanning both pools), and
# the watchdog leg must produce a flight-recorder bundle that
# tools/tracecheck.py validates (the crash-forensics artifact must never
# be discovered malformed mid-incident)
mkdir -p tools/ci_artifacts
python tools/tracejoin.py --drill \
    --chrome-out tools/ci_artifacts/twopool_trace.json \
    --flightrec-out tools/ci_artifacts/flightrec_bundle.json --json \
    > tools/ci_artifacts/tracejoin_drill.json
python tools/tracecheck.py tools/ci_artifacts/flightrec_bundle.json
# ... and the join gate must still CATCH a propagation break: with the
# seeded drop-traceparent mutation armed (the handoff loses its header
# at the seam), tracejoin must report orphan spans and exit 1 EXACTLY —
# 2 is a usage error and would pass a naive non-zero check vacuously
set +e
python tools/tracejoin.py --drill --inject drop-traceparent \
    > /dev/null 2>&1
tracejoin_rc=$?
set -e
if [ "$tracejoin_rc" -ne 1 ]; then
    echo "ci: tracejoin did not flag the dropped traceparent" \
         "(exit $tracejoin_rc, expected 1)" >&2
    exit 1
fi
# Fleet signal plane gate (ISSUE 15): the virtual-clock multi-replica
# rollup must be DETERMINISTIC — same seed => byte-identical row — and
# internally consistent (fleetcheck's own sum checks exit 1 on drift)
python tools/fleetcheck.py --sim 4 --seed 7 --json \
    > tools/ci_artifacts/fleetcheck_a.json
python tools/fleetcheck.py --sim 4 --seed 7 --json \
    > tools/ci_artifacts/fleetcheck_b.json
if ! cmp -s tools/ci_artifacts/fleetcheck_a.json \
        tools/ci_artifacts/fleetcheck_b.json; then
    echo "ci: fleetcheck --sim rows differ across identical seeds —" \
         "the rollup is not deterministic" >&2
    exit 1
fi
# Incident-detection gate (ISSUE 20): watchcheck replays the chaos
# faults on the virtual clock and holds the detection matrix — each
# fault raises EXACTLY its incident kind within the pinned tick budget,
# the healthy sweep raises none — and the fingerprint-stamped row
# (thresholds included, so a threshold drift shows in the artifact
# diff) must be byte-identical across runs of the same seed
python tools/watchcheck.py --json > tools/ci_artifacts/watchcheck.json
python tools/watchcheck.py --json > tools/ci_artifacts/watchcheck_b.json
if ! cmp -s tools/ci_artifacts/watchcheck.json \
        tools/ci_artifacts/watchcheck_b.json; then
    echo "ci: watchcheck rows differ across identical seeds —" \
         "incident detection is not deterministic" >&2
    exit 1
fi
rm -f tools/ci_artifacts/watchcheck_b.json
# ... and the gate must still CATCH a blind tower: with mute-detector
# armed (each fault scenario's expected detector muted), the faults go
# undetected and watchcheck must exit 1 EXACTLY — 2 is a usage error
# and would pass a naive non-zero check vacuously
set +e
python tools/watchcheck.py --inject mute-detector > /dev/null 2>&1
mute_rc=$?
set -e
if [ "$mute_rc" -ne 1 ]; then
    echo "ci: watchcheck did not flag the muted detectors" \
         "(exit $mute_rc, expected 1)" >&2
    exit 1
fi
# ... and a paging tower the same way: with jitter-thresholds armed
# (thresholds tightened to hair triggers) the healthy sweep must raise
# false incidents and exit 1 EXACTLY
set +e
python tools/watchcheck.py --inject jitter-thresholds > /dev/null 2>&1
jitter_rc=$?
set -e
if [ "$jitter_rc" -ne 1 ]; then
    echo "ci: watchcheck did not flag the jittered thresholds" \
         "(exit $jitter_rc, expected 1)" >&2
    exit 1
fi
# Accounting-plane gate (ISSUE 16): the request-ledger vs scheduler-
# census conservation equalities must hold EXACTLY on the virtual clock
# across every leg — healthy, speculative, cancel storm, kill-mid-decode
# recovery, the token-budget mixed engine (kind=mixed census rows,
# zero overruns), and the two-pool handoff seam (the fingerprinted row with
# per-class cost-per-token is archived next to the others)
python tools/costcheck.py --json > tools/ci_artifacts/costcheck.json
# ... and the gate must still CATCH cooked books: with the seeded
# double-count-dispatch mutation armed (every ledger charge billed twice
# while the census counts once), conservation must exit 1 EXACTLY — 2 is
# a usage error and would pass a naive non-zero check vacuously
set +e
python tools/costcheck.py --legs healthy --inject double-count-dispatch \
    --json > /dev/null 2>&1
costcheck_rc=$?
set -e
if [ "$costcheck_rc" -ne 1 ]; then
    echo "ci: costcheck did not flag the double-counted dispatch" \
         "(exit $costcheck_rc, expected 1)" >&2
    exit 1
fi
# ... and a swallowed ledger close (leak-ledger) must trip the
# open-ledger audit the same way
set +e
python tools/costcheck.py --legs healthy --inject leak-ledger \
    --json > /dev/null 2>&1
ledgerleak_rc=$?
set -e
if [ "$ledgerleak_rc" -ne 1 ]; then
    echo "ci: costcheck did not flag the leaked request ledger" \
         "(exit $ledgerleak_rc, expected 1)" >&2
    exit 1
fi
# SLO observatory gate (ISSUE 8) + crash-safety recovery gate (ISSUE 9):
# a small deterministic loadcheck run — the virtual-clock offered-load
# sweep held to the checked-in CPU goodput band
# (tools/loadcheck_baseline.json) plus the FULL chaos-drill suite:
# pool exhaustion, transient starvation, oversized prompts, disconnect,
# latency spikes, profiler-under-load, AND the recovery drills (journal
# WAL torn-tail/corruption contract, subprocess kill-mid-decode with
# bitwise stream-parity recovery, hung-dispatch watchdog trip,
# weight-stream disconnect+resume with CRC repair). Every drill asserts
# no leaked pages/slots, scrapeable metrics, and a still-admitting
# engine; the baseline's recovery_drills list makes a silently-skipped
# recovery drill a gate failure. The row is archived under
# tools/ci_artifacts/.
python tools/loadcheck.py --json > tools/ci_artifacts/loadcheck.json
# and the gate must still CATCH a fault: with the seeded
# leak-on-cancel mutation armed (a page deliberately dropped on every
# cancelled-request release) the disconnect drill must exit 1 EXACTLY —
# 2 is a usage error and would pass a naive non-zero check vacuously
set +e
python tools/loadcheck.py --drills-only --inject leak-on-cancel \
    --json > /dev/null 2>&1
loadcheck_rc=$?
set -e
if [ "$loadcheck_rc" -ne 1 ]; then
    echo "ci: loadcheck did not flag the seeded page leak" \
         "(exit $loadcheck_rc, expected 1)" >&2
    exit 1
fi
# ... and the RECOVERY gate must still catch a corrupt journal: with a
# byte smashed mid-file before recovery, loading must raise
# JournalCorruption and the kill-mid-decode drill must exit 1 EXACTLY —
# 2 is a usage error and would pass a naive non-zero check vacuously
set +e
python tools/loadcheck.py --drills-only --drills kill_mid_decode \
    --inject corrupt-journal --json > /dev/null 2>&1
recovery_rc=$?
set -e
if [ "$recovery_rc" -ne 1 ]; then
    echo "ci: loadcheck did not flag the corrupted request journal" \
         "(exit $recovery_rc, expected 1)" >&2
    exit 1
fi
if command -v clang-tidy >/dev/null 2>&1; then
    make -C csrc tidy
else
    echo "ci: clang-tidy not found — skipping csrc tidy"
fi
# probe: the compiler existing is not enough — the ASan/UBSan RUNTIME
# (libasan/libubsan) must link, or the make would abort the whole gate
san_probe="$(mktemp /tmp/dllama_san_probe.XXXXXX)"
if command -v "${CXX:-g++}" >/dev/null 2>&1 \
        && echo 'int main(){return 0;}' | "${CXX:-g++}" -x c++ - \
            -fsanitize=address,undefined -o "$san_probe" >/dev/null 2>&1; then
    rm -f "$san_probe"
    make -C csrc sanitize
else
    rm -f "$san_probe"
    echo "ci: no C++ toolchain with sanitizer runtime — skipping csrc" \
         "sanitizers"
fi
exec python -m pytest tests/ -q -n "${CI_SHARDS:-8}" \
    -m "slow or not slow" "$@"
