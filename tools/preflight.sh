#!/usr/bin/env bash
# Pre-snapshot gate: run before EVERY end-of-round / milestone commit.
# Aborts (non-zero exit) unless the full suite is green AND the multichip
# dryrun compiles+executes. Usage:  bash tools/preflight.sh
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== preflight: pytest =="
python -m pytest tests/ -q -x

echo "== preflight: proglint (static verifier over serialized program +"
echo "   INFERENCE_PASSES under verify_passes + memory profile/budget gate) =="
python tools/proglint.py --memory --selftest

echo "== preflight: serve_bench (ragged-packing parity + padding-waste"
echo "   bound, AOT-cache cold/warm restart, ServingFleet HBM admission) =="
python tools/serve_bench.py --selftest

echo "== preflight: decode bench (paged KV-cache engine: continuous"
echo "   batching token parity vs the per-request greedy loop, AOT"
echo "   warm-restart 0 fresh compiles, cache-block admission reject"
echo "   with 0 compiles + parity under pool churn, device-chained"
echo "   decode w/ seeded-sampling determinism, cross-request prefix"
echo "   cache suffix-only prefill, chunked prefill interleave) =="
python tools/decode_bench.py --selftest

echo "== preflight: observability probe (telemetry JSONL schema, MFU in"
echo "   (0,1] within 10% of the analytic model, flight bundle on induced"
echo "   NaN, perfetto timeline merge) =="
python tools/obs_probe.py --selftest

echo "== preflight: kernel A/B probe (pallas flag ladder: flash attention"
echo "   + fused LN, CPU-safe interpret-mode leg, JSON artifact) =="
python tools/kernel_ab.py --selftest

echo "== preflight: pallas kernel census (TPU cross-lowering: flash attn"
echo "   incl. ring inner step, fused LayerNorm, dequant-accumulate all"
echo "   present as tpu_custom_calls; interpret-mode parity bounds) =="
python tools/verify_lowering.py --selftest

echo "== preflight: chaos probe (self-healing drills: NaN step skipped"
echo "   bitwise + scale backoff/regrow, skip-budget abort -> replayed"
echo "   bit-exactly, watchdog stall stacks + false-positive bound,"
echo "   serving worker fatal hardening, checkpoint readback verify)"
python tools/chaos_probe.py --selftest

echo "== preflight: launch audit probe (static SPMD launch proofs: all six"
echo "   divergence classes caught with 0 compiles + 0 live collectives,"
echo "   clean pipelined audit, two-process rendezvous drill aborts both"
echo "   ranks exit 43 naming the op -> LAUNCH_AUDIT_r24.json) =="
python tools/launch_probe.py --selftest

echo "== preflight: reshard probe (elastic restore: dp8/ZeRO-3 BERT-tiny"
echo "   checkpoint onto dp4/dp16 + tp2->tp1 flip, planned==executed wire"
echo "   bytes, parity <=1e-6, 0 compiles on rejected candidates) =="
python tools/reshard_probe.py --selftest

echo "== preflight: pipeline probe (dp2.pp2 + pp4 BERT-tiny schedule grid"
echo "   {1f1b, interleaved v2, zero-bubble} parity <=1e-6, census idle =="
echo "   simulator bubble ticks exactly, pipe-axis weight sharding (state"
echo "   bytes / pipe, pp4->pp2 resharded restore), the (data,fsdp,tp,pipe,"
echo "   remat) x schedule search with 0 compiles + remat budget"
echo "   flip -> PIPE_SEARCH_r21.json) =="
python tools/pipe_probe.py --selftest

echo "== preflight: spec audit probe (differential op_spec proof: clean"
echo "   ladder shape/flops/mem + dp8 wire reconciled, seeded infer"
echo "   corruption anchored as spec-drift-shape) =="
python tools/spec_audit_probe.py --selftest

echo "== preflight: auto-shard plan probe (dp8 BERT-tiny tp2: >=6 configs"
echo "   priced, winner min-EXPOSED-comm among budget-fitting, ties to"
echo "   fewer wire bytes, 0 compiles) =="
python tools/plan_probe.py --selftest

echo "== preflight: MoE expert-parallel probe (dp8 MoE BERT-tiny: planner"
echo "   expert rows priced, budget rejects every dense row, winner dp2.ep4"
echo "   with 0 compiles; expert all_to_all wire census fp32/bf16/int8"
echo "   int8 >=3.5x; MoE decode greedy parity + AOT warm restart 0 fresh"
echo "   compiles -> MOE_SEARCH_r23.json) =="
python tools/moe_probe.py --selftest

echo "== preflight: overlap census (dp8 BERT ready-order grad sync: >=4"
echo "   interleaved collectives each preceding later backward compute,"
echo "   loss bit-parity vs the tail-fused path) =="
python tools/verify_multichip_lowering.py --overlap

echo "== preflight: quant wire-compression census (dp8 BERT bucketed grad"
echo "   sync: int8 >=3.5x fp32 / >=1.9x bf16 ring-model wire bytes) =="
python tools/verify_multichip_lowering.py --selftest

echo "== preflight: ZeRO-3 fsdp census (fsdp8 BERT-tiny: resident param"
echo "   bytes /8, windowed all-gathers + reduce_scatter transposes) =="
python tools/verify_multichip_lowering.py --fsdp

echo "== preflight: dryrun_multichip(8) =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== preflight: chip_smoke dry run (every leg of the chip script at"
echo "   tiny width on the CPU; the real run needs the chip) =="
python chip_smoke.py --cpu-dry-run

echo "== preflight: entry() compile-check =="
JAX_PLATFORMS=cpu python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn).lower(*args).compile()
print("entry() compiles OK")
EOF

echo "PREFLIGHT OK"
