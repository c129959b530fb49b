#!/bin/bash
# Full TPU evidence chain, priority-ordered with per-step budgets.
#
# Called by tpu_canary.sh the moment a chip answers (or directly when one
# is already up). Each step is skipped if its artifact already proves the
# chip ran it (resumable: a 3-minute window with a chip captures step 1;
# the next window picks up at step 2). Steps, in priority order:
#
#   1. bench.py flagship          -> tpu_results/bench_tpu.json
#   2. bench.py fused-CE variant  -> tpu_results/bench_tpu_fused.json
#   3. bench.py GQA variant       -> tpu_results/bench_tpu_gqa.json
#   4. attention_bench.py         -> tpu_results/attention_tpu.jsonl
#      (first compiled-Mosaic validation of the flash GQA grids)
#   5. run_baselines.py           -> BASELINE.md TPU-measured section
#   6. decode_bench.py            -> tpu_results/decode_tpu.json
#
# Per-step wall budgets keep one dead step from starving the rest; the
# chain re-probes the chip between steps and exits early when it is gone
# so the canary loop can resume later. Commits happen after EVERY step
# (pathspec'd, under a flock) — a window that dies mid-chain still lands
# whatever it captured.
#
# Usage: scripts/tpu_capture_chain.sh [logfile]

set -u
cd "$(dirname "$0")/.."
mkdir -p tpu_results
log=${1:-tpu_results/chain.log}

note() { echo "chain[$(date -u +%T)] $*" >> "$log"; }

probe() {
  timeout 90 python -c "import jax; assert jax.devices()[0].platform=='tpu'" \
    >> "$log" 2>&1
}

commit_evidence() {
  (
    flock 9
    git add tpu_results BASELINE.md BASELINE.json 2>> "$log"
    git commit -m "$1" -- tpu_results BASELINE.md BASELINE.json >> "$log" 2>&1
  ) 9>.git/canary.lock
}

# A bench JSON only counts as chip evidence if it says so itself.
have_tpu_json() { [ -f "$1" ] && grep -q '"platform": "tpu"' "$1"; }

run_bench_variant() { # $1=outfile $2=budget $3=commit-msg, rest=env pairs
  local out=$1 budget=$2 msg=$3; shift 3
  if have_tpu_json "$out"; then note "skip $out (already chip-measured)"; return 0; fi
  probe || { note "chip gone before $out; stopping chain"; return 1; }
  note "running $out (budget ${budget}s)"
  env "$@" POLYAXON_BENCH_TIMEOUT=$((budget - 120)) \
    timeout "$budget" python bench.py > "$out.tmp" 2>> "$log"
  note "$out rc=$?"
  if grep -q '"platform": "tpu"' "$out.tmp" 2>/dev/null; then
    mv "$out.tmp" "$out"
    cat "$out" >> "$log"
    commit_evidence "$msg"
  else
    # never leave CPU numbers on disk under a _tpu filename
    note "$out fell back to cpu or failed; discarding"
    rm -f "$out.tmp"
    return 1
  fi
}

note "=== chain start ==="

run_bench_variant tpu_results/bench_tpu.json 1800 \
  "Record TPU flagship bench (canary chain)" \
  POLYAXON_BENCH_DUMMY=0 || exit 0

run_bench_variant tpu_results/bench_tpu_fused.json 1500 \
  "Record TPU fused-CE bench (canary chain)" \
  POLYAXON_BENCH_FUSED=1 || exit 0

run_bench_variant tpu_results/bench_tpu_gqa.json 1500 \
  "Record TPU GQA bench (canary chain)" \
  POLYAXON_BENCH_KV_HEADS=4 || exit 0

# success rows carry "mode" right after the backend; error rows don't —
# a sweep where every flash call failed must NOT count as chip evidence
flash_ok='"backend": "flash", "mode"'
attn=tpu_results/attention_tpu.jsonl

# Commit whatever chip-measured attention rows are on disk. The bench
# appends each line to $attn AS IT COMPLETES (--out, no .tmp indirection):
# round 5 lost a corrected flash-vs-XLA sweep because the window died
# before a final tmp->jsonl rename and the .tmp was gitignored. Partial
# evidence is evidence — the next window's run resumes past it.
commit_attention() {
  if grep -q '"platform": "tpu"' "$attn" 2>/dev/null; then
    commit_evidence "Record TPU attention backend bench (canary chain)"
  else
    # never leave CPU or all-error rows under a _tpu filename
    rm -f "$attn"
  fi
}

if [ ! -f "$attn" ] || ! grep -q "$flash_ok" "$attn"; then
  probe || { note "chip gone before attention bench"; exit 0; }
  note "running attention_bench (budget 1500s)"
  trap 'note "interrupted during attention bench"; commit_attention' INT TERM EXIT
  timeout 1500 python benchmarks/attention_bench.py --out "$attn" \
    >> "$log" 2>&1
  note "attention rc=$?"
  trap - INT TERM EXIT
  commit_attention
else
  note "skip attention bench (already captured)"
fi

if ! grep -q 'TPU-measured' BASELINE.md 2>/dev/null || \
   [ ! -f tpu_results/baselines_tpu.out ]; then
  probe || { note "chip gone before baselines"; exit 0; }
  note "running run_baselines --update-baseline (budget 4000s)"
  timeout 4000 python benchmarks/run_baselines.py --update-baseline \
    > tpu_results/baselines_tpu.out 2>> "$log"
  note "baselines rc=$?"
  commit_evidence "Record TPU-measured baselines (canary chain)"
else
  note "skip baselines (already captured)"
fi

if [ ! -f tpu_results/decode_tpu.json ] || \
   ! grep -q '"platform": "tpu"' tpu_results/decode_tpu.json; then
  probe || { note "chip gone before decode bench"; exit 0; }
  note "running decode_bench (budget 1500s)"
  timeout 1500 python benchmarks/decode_bench.py \
    > tpu_results/decode_tpu.json.tmp 2>> "$log"
  note "decode rc=$?"
  if grep -q '"platform": "tpu"' tpu_results/decode_tpu.json.tmp 2>/dev/null; then
    mv tpu_results/decode_tpu.json.tmp tpu_results/decode_tpu.json
    commit_evidence "Record TPU decode bench (canary chain)"
  else
    rm -f tpu_results/decode_tpu.json.tmp
  fi
else
  note "skip decode bench (already captured)"
fi

touch tpu_results/COMPLETE
commit_evidence "TPU evidence chain complete (canary chain)"
note "=== CHAIN-COMPLETE ==="
