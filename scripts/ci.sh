#!/usr/bin/env bash
# Smoke CI: tier-1 test suite + docs-consistency gate + the packed-wire
# perf benchmark + the population fleet smoke + the unified-driver /
# scaled-scheme smokes.
#
#     bash scripts/ci.sh
#
# The docs gate (scripts/check_docs.py) fails if a public
# repro.schemes symbol is missing from docs/ARCHITECTURE.md's API
# table. The wire bench writes benchmarks/results/BENCH_wire.json so
# the packed-wire speedup trajectory stays tracked run-over-run; the
# acceptance gate below exits nonzero if the packed path loses its
# >=3x advantage over the jitted per-leaf loop. The population fleet
# smoke (quick mode: a 2-client 1 FL + 1 SL fleet PLUS a
# fleet-dynamics case — uniform-k sampling with one deadline-dropped
# straggler) writes benchmarks/results/BENCH_population.json with
# per-round wall time + bits, and the gate checks the dropped clients
# billed zero. The fleet-engine smoke (benchmarks/fleet.py) pins the
# struct-of-arrays engine against the loop (bit-exact bills) and gates
# its >=5x per-round advantage at 10^3 clients. The robustness chaos smoke (benchmarks/robustness.py)
# sweeps FaultPlan outages x quorum on a bounded-ARQ fleet, kills each
# case at the midpoint, resumes from the crash-consistent snapshot,
# and fails unless every resumed run is bit-for-bit. The serving smoke
# (benchmarks/serve.py) runs continuous vs static batching AND chunked
# vs token-by-token prefill on a bounded-ARQ link and fails unless
# in-flight admission wins at every width, chunked prefill cuts TTFT
# p99 at every width, and the paged KV pool holds >=2x fewer resident
# columns than the dense reservation — all on a schedule-invariant,
# exactly-split (delivered + erased) radio bill. A second serve
# aot-warmup gate requires the persistent compile cache to collapse a
# warm process's prefill-bucket compile wall to <20% of the cold one.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1 pytest ==="
python -m pytest -x -q

echo "=== docs-consistency gate (schemes API vs docs/ARCHITECTURE.md) ==="
python scripts/check_docs.py

echo "=== packed-wire perf benchmark ==="
python -m benchmarks.run --only wire

echo "=== packed-wire acceptance gate (>=3x vs the seed eager loop) ==="
# gate on the seed per-leaf EAGER loop (the PR 1 claim, and what the
# benchmark's own acceptance row checks): the jitted-loop ratio is
# hardware-dependent — on a 1-core host both paths saturate the core
# and the margin collapses — so it is tracked in the JSON, not gated
python - <<'EOF'
import json, sys
res = json.load(open("benchmarks/results/BENCH_wire.json"))
fl = res["cases"]["fl_tinylstm_n3"]
print(f"fl_tinylstm_n3 packed speedup vs seed eager loop: "
      f"{fl['speedup_vs_per_leaf']:.2f}x "
      f"(vs jitted loop: {fl['speedup_vs_per_leaf_jit']:.2f}x, tracked)")
sys.exit(0 if fl["speedup_vs_per_leaf"] >= 3.0 else 1)
EOF

echo "=== population fleet smoke (sampling + straggler, BENCH_population.json) ==="
python -m benchmarks.population --quick
python - <<'EOF'
import json, sys
res = json.load(open("benchmarks/results/BENCH_population.json"))
rec = res["cases"]["smoke_1fl_1sl"]
wall = sum(rec["round_wall_s"]) / len(rec["round_wall_s"])
print(f"smoke_1fl_1sl: {len(rec['round_bits'])} rounds, "
      f"mean {wall:.2f}s/round, {rec['total_bits']:.0f} bits total")
ok = rec["total_bits"] > 0 and rec["final_accuracy"] > 0
dyn = res["cases"]["smoke_fleet_dynamics"]
dropped = [n for statuses in dyn["per_client_status"]
           for n, s in statuses.items() if s != "ok"]
zero_billed = all(
    bits[n] == 0.0
    for statuses, bits in zip(dyn["per_client_status"],
                              dyn["per_client_bits"])
    for n, s in statuses.items() if s != "ok")
print(f"smoke_fleet_dynamics: n_active per round {dyn['n_active']}, "
      f"{len(dropped)} dropped client-rounds, zero-billed={zero_billed}")
ok = ok and dyn["final_accuracy"] > 0 and len(dropped) > 0 and zero_billed
# the laggard never trains: deadline-dropped whenever sampled (rounds
# where the policy left it unsampled are legitimately "sampled_out"),
# and it must actually straggle at least once
ok = ok and all(s["laggard"] in ("straggler", "sampled_out")
                for s in dyn["per_client_status"])
ok = ok and any(s["laggard"] == "straggler"
                for s in dyn["per_client_status"])
sys.exit(0 if ok else 1)
EOF

echo "=== fleet-engine smoke (engine parity + scaling sweep, BENCH_fleet.json) ==="
# the struct-of-arrays fleet engine vs the per-client loop: bills must
# match bit-for-bit on every parity case, and the engine must keep a
# >=5x per-round advantage at 10^3 clients (steady state, post-compile)
python -m benchmarks.fleet --quick
python - <<'EOF'
import json, sys
res = json.load(open("benchmarks/results/BENCH_fleet.json"))
s = res["cases"]["scale_1000"]
print(f"fleet scale_1000: loop {s['loop_steady_wall_s']:.3f}s/round vs "
      f"fleet {s['fleet_steady_wall_s']:.3f}s/round -> "
      f"{s['speedup']:.1f}x (bills_match={res['bills_match']})")
ok = res["bills_match"] and res["speedup_at_1e3"] >= 5.0
# the bounded-ARQ chaos parity case really erased something
ok = ok and res["cases"]["parity_faulty_6"]["erased_bits"] > 0
sys.exit(0 if ok else 1)
EOF

echo "=== unified driver smoke (paper model + scaled arch through Experiment) ==="
# the paper's tiny FL through the unified launch driver (one comm cycle)
python -m repro.launch.train --arch paper-tinylstm --mode fl --steps 2 \
    --n-train 2048 --n-test 512
# a scaled arch, same driver, pod-FL scheme on the degraded test mesh
python -m repro.launch.train --arch qwen1.5-0.5b --reduced --mode fl \
    --steps 2 --batch 4 --seq 16 --local-steps 2 --n-users 2 --mesh test

echo "=== persistent compile-cache gate (2nd aot-warmup <20% of 1st) ==="
CACHE_DIR=$(mktemp -d)
SMOKE_ARGS="--arch qwen1.5-0.5b --reduced --mode fl --steps 2 --batch 4 \
    --seq 16 --local-steps 2 --n-users 2 --mesh test --aot-warmup"
W1=$(JAX_COMPILATION_CACHE_DIR="$CACHE_DIR" python -m repro.launch.train \
    $SMOKE_ARGS | grep -o 'aot_warmup_compile_wall_s=[0-9.]*' | cut -d= -f2)
W2=$(JAX_COMPILATION_CACHE_DIR="$CACHE_DIR" python -m repro.launch.train \
    $SMOKE_ARGS | grep -o 'aot_warmup_compile_wall_s=[0-9.]*' | cut -d= -f2)
rm -rf "$CACHE_DIR"
python - "$W1" "$W2" <<'EOF'
import sys
cold, warm = float(sys.argv[1]), float(sys.argv[2])
print(f"aot compile wall: cold {cold:.3f}s -> cache-warm {warm:.3f}s "
      f"({warm / max(cold, 1e-9):.1%})")
sys.exit(0 if warm < 0.2 * cold else 1)
EOF

echo "=== scaled-scheme benchmark (cl/fl/sl + FL steady-state closers, BENCH_scaled.json) ==="
python -m benchmarks.run --only scaled
python - <<'EOF'
import json, math, sys
res = json.load(open("benchmarks/results/BENCH_scaled.json"))
ok = True
for mode, rec in res["cases"].items():
    print(f"scaled {mode}: {len(rec['round_bits'])} cycles, "
          f"steady median {rec['steady_wall_s']:.2f}s "
          f"(p90 {rec['steady_p90_s']:.2f}s), "
          f"{rec['total_bits']:.0f} bits")
    ok = ok and math.isfinite(rec["final_loss"])
    ok = ok and len(rec["round_wall_s"]) >= 5   # >=4 post-compile cycles
# radio paradigms must bill per round; CL bills its init upload only
for fl_case in ("fl", "fl_barrier_q4", "fl_delayed_int4"):
    ok = ok and all(b > 0 for b in res["cases"][fl_case]["round_bits"])
ok = ok and all(b > 0 for b in res["cases"]["sl"]["round_bits"])
ok = ok and res["cases"]["cl"]["init_bits"] > 0
ok = ok and all(b == 0 for b in res["cases"]["cl"]["round_bits"])
# FL steady-state gate: the delayed+int4 stack must beat the PINNED
# PR 5 barrier steady wall (baseline_pr5_fl_steady_s, recorded at
# commit 4f84a5a) by >=2x, at EQUAL total on-air bits to the live
# barrier-Q4 baseline (float32 wire bills quant_bits=4, int4 bills
# its 4-bit container — same bill), without regressing vs the live
# barrier (which also gained the recompile fix)
d = res["cases"]["fl_delayed_int4"]
b4 = res["cases"]["fl_barrier_q4"]
speed = res["baseline_pr5_fl_steady_s"] / max(d["steady_wall_s"], 1e-9)
print(f"scaled fl_delayed_int4: {speed:.1f}x vs PR5 baseline "
      f"({res['baseline_pr5_fl_steady_s']}s), live barrier_q4 "
      f"{b4['steady_wall_s']:.2f}s")
ok = ok and speed >= 2.0
ok = ok and d["round_bits"] == b4["round_bits"]
ok = ok and d["steady_wall_s"] <= 1.25 * b4["steady_wall_s"]
sys.exit(0 if ok else 1)
EOF

echo "=== serving smoke (continuous vs static, BENCH_serve.json) ==="
python -m benchmarks.run --only serve
python - <<'EOF'
import json, sys
res = json.load(open("benchmarks/results/BENCH_serve.json"))
ok = True
for case, rec in res["cases"].items():
    c, s = rec["continuous"], rec["static"]
    print(f"serve {case}: continuous {c['cycles']} cycles "
          f"({c['tokens_per_cycle']:.2f} tok/cyc, p99 "
          f"{c['p99_latency_cycles']:.0f}) vs static {s['cycles']} "
          f"({s['tokens_per_cycle']:.2f} tok/cyc, p99 "
          f"{s['p99_latency_cycles']:.0f}) -> "
          f"{rec['speedup_cycles']:.2f}x | ttft p99 "
          f"{c['p99_ttft_cycles']:.0f} cycles | "
          f"{c['bits']:.0f} bits ({c['erased_bits']:.0f} erased)")
    # the tentpole claim: in-flight admission beats the barrier at
    # mixed lengths, on the SAME schedule-invariant radio bill
    ok = ok and rec["speedup_cycles"] > 1.0
    ok = ok and c["bits"] == s["bits"]
    for d in (c, s):
        ok = ok and abs(d["delivered_bits"] + d["erased_bits"]
                        - d["bits"]) < 1e-6
# the bounded-ARQ link actually erased something somewhere
ok = ok and any(rec["continuous"]["erased_bits"] > 0
                for rec in res["cases"].values())
# paged KV: >=2x fewer resident KV columns than a per-slot reservation
# on the long-prompt mix
pk = res["paged_kv"]
print(f"serve paged_kv: per-slot {pk['slot_reserved_cols']} cols vs "
      f"paged peak {pk['paged_peak_cols']} -> "
      f"{pk['capacity_factor']:.2f}x")
ok = ok and pk["capacity_factor"] >= 2.0
sys.exit(0 if ok else 1)
EOF

echo "=== serve aot-warmup compile-cache gate (2nd run <20% of 1st) ==="
# decode step + every prefill bucket AOT-compile before admission; the
# persistent cache must collapse the second process's compile wall
CACHE_DIR=$(mktemp -d)
SERVE_ARGS="--arch qwen1.5-0.5b --reduced --batch 4 --prompt-len 48 \
    --new-tokens 4 --aot-warmup"
V1=$(JAX_COMPILATION_CACHE_DIR="$CACHE_DIR" python -m repro.launch.serve \
    $SERVE_ARGS | grep -o 'aot_warmup_compile_wall_s=[0-9.]*' | cut -d= -f2)
V2=$(JAX_COMPILATION_CACHE_DIR="$CACHE_DIR" python -m repro.launch.serve \
    $SERVE_ARGS | grep -o 'aot_warmup_compile_wall_s=[0-9.]*' | cut -d= -f2)
rm -rf "$CACHE_DIR"
python - "$V1" "$V2" <<'EOF'
import sys
cold, warm = float(sys.argv[1]), float(sys.argv[2])
print(f"serve aot compile wall: cold {cold:.3f}s -> cache-warm "
      f"{warm:.3f}s ({warm / max(cold, 1e-9):.1%})")
sys.exit(0 if warm < 0.2 * cold else 1)
EOF

echo "=== robustness chaos smoke (outage x quorum sweep + kill-and-resume, BENCH_robustness.json) ==="
python -m benchmarks.run --only robustness
python - <<'EOF'
import json, sys
res = json.load(open("benchmarks/results/BENCH_robustness.json"))
ok = True
for case, rec in res["cases"].items():
    print(f"robustness {case}: acc {rec['final_accuracy']:.3f}, "
          f"{rec['total_bits']:.0f} bits ({rec['erased_bits']:.0f} erased), "
          f"quorum met {rec['quorum_met_frac']:.0%}, "
          f"resume bit-for-bit: {rec['resume_bit_for_bit']}")
    # the chaos gate: every case's kill-at-midpoint + resume run must
    # reproduce the uninterrupted trajectory and billing bit-for-bit
    ok = ok and rec["resume_bit_for_bit"]
    ok = ok and 0.0 <= rec["erased_bits"] <= rec["total_bits"]
# faults were actually injected somewhere in the sweep
ok = ok and any(rec["erased_bits"] > 0 for rec in res["cases"].values())
sys.exit(0 if ok else 1)
EOF
