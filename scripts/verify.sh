#!/usr/bin/env bash
# Tier-1 gate: static contracts (lint + kernel + jaxpr seam checks) + full
# correctness suite.
#
# Usage:  scripts/verify.sh [--lint|--fast] [extra pytest args]
#
#   --lint     run ONLY the static-contract checker
#              (python -m repro.analysis.check) — AST lint over
#              src/ benchmarks/ examples/ tests/, the Pallas kernel
#              contracts (repro.analysis.kernelcheck: semaphore balance,
#              DMA/slot races, ring arithmetic, tile coverage, VMEM
#              budgets — every kernel x both ring directions), plus the
#              jaxpr seam contracts for every config x both residual
#              layouts.  No pytest; finishes in well under a minute.
#   --fast     skip the multi-device subprocess sweeps (tests marked
#              ``multidev`` — everything that spawns a fresh python with
#              forced host devices).  Quick iteration tier; the FULL suite
#              remains the default and the PR gate.
#
# The static checker replaced the old grep-lint gates: the standing source
# rules (compat-import, private-backend, removed-wrapper, raw-collective,
# bare-shard-map, stale-allow) are AST checks in repro.analysis.lint, the
# in-kernel DMA/semaphore/ring/coverage/budget protocol is verified on
# abstract per-rank grid traces in repro.analysis.kernelcheck, and the seam
# invariants (collective census with ring provenance, partial-cotangent
# completion, layout coherence) are verified on ABSTRACT jaxpr traces in
# repro.analysis.seamcheck — no devices, no execution.
#
# Runs on CPU CI machines (no TPU): kernels execute in Pallas interpret mode
# (REPRO_PALLAS_INTERPRET=1).  Every PR must pass this before review.
set -euo pipefail
cd "$(dirname "$0")/.."

LINT_ONLY=0
FAST=0
if [[ "${1:-}" == "--lint" ]]; then
  LINT_ONLY=1
  shift
elif [[ "${1:-}" == "--fast" ]]; then
  FAST=1
  shift
fi

export REPRO_PALLAS_INTERPRET="${REPRO_PALLAS_INTERPRET:-1}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "$LINT_ONLY" == 1 ]]; then
  echo "== static contracts (repro.analysis.check: lint + kernel + seam invariants) =="
  python -m repro.analysis.check "$@"
  exit 0
fi

echo "== static contracts (repro.analysis.check: lint + kernel + seam invariants) =="
python -m repro.analysis.check

echo "== MoE a2a seam: census provenance on both transports =="
# runs in EVERY lane (incl. --fast): abstractly trace one MoE config's
# train step under the barrier and ring a2a transports and demand the EP
# exchange shows up seam-tagged — the all_to_all census blind spot stays
# closed even when the multi-device sweeps are skipped.
python - <<'EOF'
from repro.analysis import seamcheck
from repro.configs.base import ParallelConfig, get_smoke_config
from repro.tuning.plans import PlanSet, SeamPlan

cfg = get_smoke_config("deepseek_v3_671b")
par = ParallelConfig(tp=4, dp=1)
for layout in ("seq", "hidden"):
    for a2a_mode in ("xla", "decomposed"):
        plans = PlanSet.uniform("decomposed").override(
            "moe_a2a", SeamPlan(mode=a2a_mode)).with_scatter_axis(layout)
        colls = seamcheck.collect_collectives(
            seamcheck.trace_train(cfg, par, plans))
        a2a = [c for c in colls if c.prim == "all_to_all"]
        assert all(c.seam_tagged for c in a2a), \
            [c.describe() for c in a2a if not c.seam_tagged]
        if layout == "seq" and a2a_mode == "xla":
            assert a2a, "barrier plan must trace all_to_all dispatch/combine"
        if layout == "seq" and a2a_mode == "decomposed":
            assert not a2a, "ring plan must decompose the a2a into ppermute"
            assert any(c.prim == "ppermute" and "seam_moe" in c.scope
                       for c in colls), "no seam_moe ppermute ring traced"
print("moe a2a census ok: both layouts x both transports")
EOF

echo "== tier-1 test suite =="
if [[ "$FAST" == 1 ]]; then
  # the serving regressions run FIRST in the fast lane: they guard the
  # continuous-batching cache-corruption bugs (per-slot positions, batched
  # prefill admission) and fail in seconds when the serving path breaks.
  python -m pytest -x -q tests/test_serving_regression.py
  python -m pytest -x -q -m "not multidev" --ignore=tests/test_serving_regression.py "$@"
else
  python -m pytest -x -q "$@"
fi

echo "== serving smoke bench (BENCH_serving.json well-formedness) =="
# open-loop Poisson traffic through the paged runtime: TTFT / per-token
# percentiles must be finite, the paged pool must beat the dense-cache
# footprint, and overlap modes must not change outputs
python benchmarks/serving.py --smoke
python - <<'EOF'
import json
import math
doc = json.load(open("experiments/BENCH_serving.json"))
assert doc["arrival_rate_rps"] > 0, "smoke bench must run open-loop traffic"
assert doc["slo_ttft_s"] > 0, doc
rows = doc["modes"]
assert len(rows) >= 2, f"need >= 2 overlap modes, got {len(rows)}"
assert any(r.get("wire_dtype") for r in rows), \
    "serving bench must include a quantized-wire lane"
for r in rows:
    assert r["tokens_per_s"] > 0 and r["new_tokens"] > 0, r
    # chunked admission: at least one chunk dispatch per request, never a
    # per-token decode loop (<= ceil(max_seq / chunk) chunks per request)
    assert r["requests"] <= r["prefill_dispatches"], r
    assert r["prefill_dispatches"] < r["requests"] * doc["max_seq"], r
    for key in ("ttft_s", "per_token_s"):
        stats = r[key]
        assert {"mean", "p50", "p95", "p99"} <= set(stats), (key, stats)
        assert all(math.isfinite(v) and v >= 0 for v in stats.values()), \
            (key, stats)
        assert stats["p50"] <= stats["p95"] <= stats["p99"], (key, stats)
    assert 0 <= r["slo"]["attainment"] <= 1, r["slo"]
    pool = r["pool"]
    assert 0 < pool["blocks_in_use_peak"] < pool["dense_equiv_blocks"], \
        f"paged pool must beat the dense-cache footprint: {pool}"
    if r.get("wire_dtype"):
        # lossy wire: outputs may drift at tp > 1; at tp = 1 every seam
        # takes the single-shard fallback so nothing rides the wire
        assert "outputs_match_fp_wire" in r, r["mode"]
        if doc["tp"] == 1:
            assert r["outputs_match_fp_wire"], \
                "tp=1 has no wire transport — outputs must match"
    else:
        assert r["outputs_match_reference"], \
            f"overlap mode {r['mode']} changed serving outputs"
print("BENCH_serving.json ok:",
      ", ".join(f"{r['mode']}={r['tokens_per_s']:.0f} tok/s "
                f"ttft_p99={r['ttft_s']['p99'] * 1e3:.1f}ms" for r in rows))
EOF

echo "== BENCH_tuning.json scatter_axis sweep rows =="
python - <<'EOF'
import json
doc = json.load(open("experiments/BENCH_tuning.json"))
rows = doc.get("layout", {}).get("scatter_axis", [])
assert rows, "BENCH_tuning.json has no scatter_axis sweep rows"
axes = {r["scatter_axis"] for r in rows}
assert axes == {"seq", "hidden"}, axes
for r in rows:
    assert {"m", "overall_s", "act_bytes", "comm_bytes"} <= set(r), r
by_m = {}
for r in rows:
    by_m.setdefault(r["m"], {})[r["scatter_axis"]] = r
for m, pair in by_m.items():
    seq, hid = pair["seq"], pair["hidden"]
    assert abs(seq["comm_bytes"] - hid["comm_bytes"]) < 1e-6 * max(
        seq["comm_bytes"], 1.0), (m, "layer-pair comm volume must be "
                                  "layout-invariant")
    assert seq["act_bytes"] < hid["act_bytes"], (m, "seq must reduce "
                                                 "activation residency")
print(f"BENCH_tuning.json scatter_axis sweep ok: {len(rows)} rows")
EOF
echo "== BENCH_tuning.json MoE a2a rows =="
python - <<'EOF'
import json
doc = json.load(open("experiments/BENCH_tuning.json"))
chunks = doc.get("moe", {}).get("a2a_chunks", [])
assert chunks, "BENCH_tuning.json has no a2a chunk-sweep rows"
assert len({r["comm_chunks"] for r in chunks}) >= 3, chunks
for r in chunks:
    assert {"m", "n", "k", "overall_s", "comm_bytes"} <= set(r), r
    assert r["comm_bytes"] > 0, r
a2a_seams = [s for s in doc["seams"] if s["seam"] == "moe_a2a"]
assert a2a_seams, "no moe_a2a planner row in BENCH_tuning.json"
modes = {c["mode"] for c in a2a_seams[0]["candidates"]}
assert {"xla", "decomposed"} <= modes, modes
print(f"BENCH_tuning.json moe a2a ok: {len(chunks)} chunk rows, "
      f"pick={a2a_seams[0]['plan']['mode']}")
EOF
echo "== BENCH_tuning.json static tile-budget pruning rows =="
python - <<'EOF'
import json
from repro.analysis.kernelcheck import tile_budget_ok
doc = json.load(open("experiments/BENCH_tuning.json"))
assert doc["seams"], "no planner rows in BENCH_tuning.json"
for s in doc["seams"]:
    # every planner row reports how many flux tilings the static VMEM
    # budget rejected before pricing, and no surviving candidate carries
    # an infeasible tiling (autotune never times what kernelcheck rejects)
    assert "pruned" in s, f"seam row missing pruned count: {s['seam']}"
    assert s["pruned"] >= 0, s
    for c in s["candidates"]:
        if c["mode"] == "flux" and c.get("blocks"):
            assert tile_budget_ok(s["kind"], tuple(c["blocks"])), \
                (s["seam"], c["blocks"], "infeasible tiling in the table")
print(f"BENCH_tuning.json pruning ok: {len(doc['seams'])} seam rows, "
      f"pruned={[s['pruned'] for s in doc['seams']]}")
EOF
echo "== BENCH_tuning.json wire-precision sweep rows =="
python - <<'EOF'
import json
doc = json.load(open("experiments/BENCH_tuning.json"))
wire = doc.get("wire", {})
seams = wire.get("seams", [])
assert seams, "BENCH_tuning.json has no wire-precision sweep rows"
budget = wire["max_logit_rmse"]
assert budget > 0, wire
kinds = {s["kind"] for s in seams}
assert {"ag", "rs", "ar", "a2a"} <= kinds, kinds
for s in seams:
    dtypes = {r["wire_dtype"] for r in s["rows"]}
    assert None in dtypes and "int8" in dtypes, (s["seam"], dtypes)
    for r in s["rows"]:
        # every row: bytes on the wire, a time estimate, and its
        # deviation vs the accuracy budget
        assert r["comm_bytes"] >= 0, (s["seam"], r)
        assert (r["measured_s"] or r["predicted_s"]) > 0, (s["seam"], r)
        assert r["logit_rmse"] >= 0, (s["seam"], r)
        assert r["within_budget"] == (r["logit_rmse"] <= budget), \
            (s["seam"], r, "within_budget disagrees with the budget")
        if r["wire_dtype"] is None:
            assert r["logit_rmse"] == 0.0, (s["seam"], r)
    # the CHOSEN plan never violates its accuracy budget
    assert s["plan"]["logit_rmse"] <= budget, (s["seam"], s["plan"])
    # quantized rows shrink bytes-on-wire vs the fp wire of the same mode
    for r in s["rows"]:
        if r["wire_dtype"] is None or r["comm_bytes"] == 0:
            continue
        fp = [f for f in s["rows"] if f["wire_dtype"] is None
              and f["mode"] == r["mode"]
              and f["comm_chunks"] == r["comm_chunks"]
              and f["reverse"] == r["reverse"]
              and f["scatter_axis"] == r["scatter_axis"]]
        assert fp and r["comm_bytes"] < fp[0]["comm_bytes"], (s["seam"], r)
assert wire["any_quantized_win"], \
    "no seam shows an in-budget low-precision wire beating the fp wire"
picks = {s["seam"]: (s["plan"]["mode"], s["plan"]["wire_dtype"])
         for s in seams}
print(f"BENCH_tuning.json wire sweep ok: {len(seams)} seams, "
      f"budget={budget}, picks={picks}")
EOF
