#!/usr/bin/env bash
# CI smoke, split into named stages so the pipeline can matrix them and a
# failed gate names its stage:
#
#   scripts/ci_smoke.sh [stage...]      # default: all stages, in order
#
#   lint      ruff check (skipped with a notice when ruff is absent)
#   tests     tier-1 pytest suite
#   ops       bench_ops backend sweep + batched-Pallas-vs-dense parity gate
#             (<= 1e-4 relative) + real 8-device-mesh parity + bench_ops
#             wall-clock regression gate vs benchmarks/baselines +
#             compensated-f32 parity vs the f64 oracle (<= 1e-6)
#   delta     delta-ingest gates (delta-vs-rebuild loss parity <= 1e-9,
#             delta beats full re-ingest) + deprecation-warning-clean run
#   service   serve_coresets self-check + 2s closed-loop loadgen per wire
#             encoding + binary-beats-JSON registration gate + bench_service
#             regression gate
#   coalesce  cross-request query coalescing gate: 16 concurrent same-signal
#             loss queries must fuse into <= 4 scoring dispatches with
#             per-request losses <= 1e-9 off the uncoalesced path
#   trace     end-to-end tracing gate: traceparent propagation, span
#             taxonomy (http -> scheduler wait -> linked fused dispatch ->
#             ops.dispatch), >= 80% root coverage, shared fused-trace
#             linking under a concurrent burst, valid Chrome export
#   stream    re-anchor + v2 streaming gate: disjoint-delta ingest serves
#             subsequent requests with zero rebuilds and bitwise parity,
#             >= 4 MB compress streams in >= 4 chunks identical to the v1
#             body; then the delta-mix/stream probes + their wall-clock,
#             miss-rate and encode-peak regression gates
#   cluster   distributed serving plane gate: 1 coordinator + 3 subprocess
#             workers, bitwise fingerprint parity vs the single-host build,
#             loss parity <= 1e-9, worker-kill -> degraded (200s, same
#             bytes) -> same-port rejoin; then the cluster loadgen smoke +
#             its wall-clock regression gate
#   qos       admission control / multi-tenant QoS gate: under 4x overload
#             with one hot tenant, admitted requests never 504, the hot
#             tenant is capped within +-20% of its weighted share, cold
#             p95 <= 2x unloaded; then the overload probe + its regression
#             gate (admit decision < 50us, 503 round-trip wall-clock)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# runtime hygiene (mirrors scripts/run.sh): persist jit compilations across
# stage processes — every stage re-imports jax, and recompiles of the same
# kernels otherwise dominate smoke wall time
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"

stage_lint() {
  echo "== lint (ruff) =="
  if command -v ruff >/dev/null 2>&1; then
    ruff check .
  else
    echo "[ci_smoke] ruff not installed: lint stage skipped"
  fi
}

stage_tests() {
  echo "== tier-1 tests =="
  python -m pytest -q
}

stage_ops() {
  echo "== bench_ops backend sweep (numpy vs xla vs pallas-interpret) =="
  python -m benchmarks.bench_ops --fast

  echo "== batched-Pallas vs dense dispatched-path parity gate =="
  python - <<'EOF'
import json, pathlib, sys
p = pathlib.Path("benchmarks/results/bench_ops.json")
res = json.loads(p.read_text())
rel = res["parity"]["batched_pallas_vs_dense_rel"]
print(f"[ci_smoke] batched pallas vs dense: rel={rel:.2e} "
      f"(blocks={res['parity']['coreset_blocks']}, "
      f"T={res['parity']['trees']}, K={res['parity']['leaves']})")
if rel > 1e-4:
    sys.exit(f"[ci_smoke] FAIL: batched kernel off dense path by {rel:.2e} > 1e-4")
EOF

  echo "== mesh-sharded batched fitting loss (8 devices, forced host mesh) =="
  # the parity logic lives once, in the test (it spawns its own subprocess
  # with XLA_FLAGS); this step just runs it by name so a smoke log shows it
  python -m pytest -q tests/test_ops.py -k mesh_sharded

  echo "== bench_ops wall-clock + compensated-parity regression gate =="
  # the gate re-measures failing rows itself (per-row min over runs):
  # micro-timings are load-sensitive and one sample proves nothing
  python scripts/check_bench_regression.py ops
}

stage_delta() {
  echo "== delta-ingest gates: rebuild parity <= 1e-9, delta beats full rebuild =="
  python - <<'EOF'
import json, pathlib, sys
p = pathlib.Path("benchmarks/results/bench_ops.json")
if not p.exists():
    sys.exit("[ci_smoke] FAIL: run the ops stage first (bench_ops.json missing)")
res = json.loads(p.read_text())
d = res["ingest_delta"]
print(f"[ci_smoke] delta ingest {d['band_rows']}x{d['m']} into "
      f"{d['n']}x{d['m']}: delta={d['delta_ms']:.1f}ms "
      f"rebuild={d['rebuild_ms']:.1f}ms (speedup {d['speedup']:.2f}x), "
      f"loss parity rel={d['loss_parity_rel']:.2e}")
if d["loss_parity_rel"] > 1e-9:
    sys.exit(f"[ci_smoke] FAIL: delta-built coreset off from-scratch build "
             f"by {d['loss_parity_rel']:.2e} > 1e-9")
if d["delta_ms"] >= d["rebuild_ms"]:
    sys.exit("[ci_smoke] FAIL: delta ingest is not faster than full rebuild")
EOF

  echo "== deprecation-warning-clean (coreset_loss_many shim fully migrated) =="
  # explicitly-named files bypass conftest's hypothesis-absent collect-ignore,
  # so mirror its guard here: drop the property-test module on bare containers
  python - <<'EOF'
import subprocess, sys
mods = ["tests/test_ops.py", "tests/test_streaming.py",
        "tests/test_ingest_delta.py"]
try:
    import hypothesis  # noqa: F401
    mods.insert(0, "tests/test_fitting_loss.py")
except ModuleNotFoundError:
    print("[ci_smoke] hypothesis absent: -W error run skips "
          "tests/test_fitting_loss.py (collect-ignored in tier-1 too)")
sys.exit(subprocess.call(
    [sys.executable, "-m", "pytest", "-q", "-W", "error::DeprecationWarning",
     *mods]))
EOF
}

stage_service() {
  echo "== serve_coresets smoke (concurrent SDK clients, both encodings) =="
  python -m repro.launch.serve_coresets --smoke

  echo "== bench_service loadgen smoke (2s, json encoding) =="
  python benchmarks/bench_service.py --smoke --encoding json

  echo "== bench_service loadgen smoke (2s, binary encoding) =="
  python benchmarks/bench_service.py --smoke --encoding binary

  echo "== binary-vs-json registration gate =="
  python - <<'EOF'
import json, pathlib, sys
p = pathlib.Path("benchmarks/results/bench_service.json")
res = json.loads(p.read_text())
missing = [m for m in ("json", "binary") if m not in res]
if missing:
    sys.exit(f"[ci_smoke] bench_service.json missing mode(s): {missing}")
j, b = res["json"]["register_seconds"], res["binary"]["register_seconds"]
nm = res["binary"]["register_nm"]
print(f"[ci_smoke] register {nm[0]}x{nm[1]}: json={1e3*j:.1f}ms "
      f"binary={1e3*b:.1f}ms (speedup {j/max(b,1e-9):.2f}x)")
if b >= j:
    sys.exit("[ci_smoke] FAIL: binary registration is not faster than JSON")
EOF

  echo "== bench_service wall-clock regression gate =="
  python scripts/check_bench_regression.py service
}

stage_coalesce() {
  echo "== cross-request query coalescing gate =="
  python scripts/coalesce_gate.py
}

stage_trace() {
  echo "== end-to-end tracing gate =="
  python scripts/trace_gate.py

  echo "== trace-retrieval race regression (20x repeat) =="
  # this test raced trace finalization (root span ends AFTER the response is
  # written) and only failed under load; Tracer.get now bounded-waits on a
  # condition variable.  Repeat it to keep the race from regressing silently
  for _ in $(seq 20); do
    python -m pytest -q -x tests/test_service.py \
      -k "trace_retrieval" >/dev/null \
      || { echo "[ci_smoke] FAIL: trace retrieval raced finalization"; exit 1; }
  done
  echo "[ci_smoke] 20/20 trace-retrieval repeats clean"
}

stage_stream() {
  echo "== cache re-anchor + v2 streaming gate =="
  python scripts/stream_gate.py

  echo "== bench_service delta-mix probe (2s) =="
  python benchmarks/bench_service.py --smoke --delta-mix 0.3

  echo "== bench_service stream probe =="
  python benchmarks/bench_service.py --smoke --stream

  echo "== stream wall-clock / miss-rate / encode-peak regression gate =="
  python scripts/check_bench_regression.py stream
}

stage_cluster() {
  echo "== distributed serving plane gate (1 coordinator + 3 workers) =="
  python scripts/cluster_gate.py

  echo "== bench_service cluster loadgen smoke (2s) =="
  python benchmarks/bench_service.py --smoke --cluster

  echo "== bench_service cluster wall-clock regression gate =="
  python scripts/check_bench_regression.py cluster
}

stage_qos() {
  echo "== admission control / multi-tenant QoS overload gate =="
  python scripts/overload_gate.py --smoke

  echo "== bench_service overload probe (admit-decision us + 503 cost) =="
  python benchmarks/bench_service.py --smoke --overload

  echo "== qos regression gate (admit < 50us, 503 round-trip wall-clock) =="
  python scripts/check_bench_regression.py qos
}

ALL_STAGES=(lint tests ops delta service coalesce trace stream cluster qos)
# bash 3.2 (macOS) treats an empty array as unbound under set -u, so pick
# the default stage list off $# instead of the array length
if [ $# -eq 0 ]; then
  STAGES=("${ALL_STAGES[@]}")
else
  STAGES=("$@")
fi

for stage in "${STAGES[@]}"; do
  case "$stage" in
    lint|tests|ops|delta|service|coalesce|trace|stream|cluster|qos) "stage_${stage}" ;;
    *) echo "[ci_smoke] unknown stage '${stage}' (known: ${ALL_STAGES[*]})" >&2
       exit 2 ;;
  esac
done

echo "== ci_smoke PASS (${STAGES[*]}) =="
