#!/usr/bin/env bash
# CI: native build + ruff + orlint + emulator smokes + full test suite.
# Mirrors the reference's CI shape (build deps, compile, ctest) for this
# repo: make -C native, lint, pytest on the virtual 8-device CPU mesh.
#
# The tree-scraping doc lints that used to live here as bash/python
# heredocs (perf markers, decision.rebuild.*, flood/program counters,
# queue/ctrl/watchdog/spark counters) are now orlint rule OR007 backed
# by the central name registry (openr_tpu/monitor/names.py); the task
# hygiene, determinism and queue-seam contracts are OR001..OR006. See
# docs/Linting.md.
set -euo pipefail
cd "$(dirname "$0")"

# smoke lanes tee their scratch logs HERE, never into the worktree (a
# stray trace_smoke.err at the repo root prompted this): /tmp scratch
# survives the run for diagnosis and can't pollute git status
SMOKE_LOG_DIR="${SMOKE_LOG_DIR:-/tmp/openr-ci-logs}"
mkdir -p "$SMOKE_LOG_DIR"
smoke_log() {  # usage: some_lane 2> >(smoke_log <name>)
    tee "$SMOKE_LOG_DIR/$1.err" >&2
}

echo "== native build =="
make -C native

echo "== ruff =="
if ! command -v ruff >/dev/null 2>&1; then
    echo "ERROR: ruff is not installed — the lint lane is mandatory."
    echo "Install it (pip install ruff); the rule set is pinned in"
    echo "pyproject.toml [tool.ruff]. CI must not silently skip lint."
    exit 1
fi
ruff check openr_tpu tests benchmarks tools

echo "== orlint (project AST lint; registry<->docs parity via OR007) =="
python -m tools.orlint openr_tpu tests benchmarks

echo "== orlint smoke (known-bad fixture must trip every rule) =="
set +e
smoke_out=$(python -m tools.orlint \
    tests/fixtures/orlint/decision/known_bad.py --no-baseline 2>&1)
smoke_rc=$?
set -e
if [ "$smoke_rc" -ne 1 ]; then
    echo "expected the known-bad fixture to produce findings (rc=1)," \
         "got rc=$smoke_rc"
    echo "$smoke_out"
    exit 1
fi
for code in OR001 OR002 OR003 OR004 OR005 OR006 OR007 OR008 OR009 \
            OR010 OR011 OR012 OR013 OR014 OR015; do
    if ! printf '%s\n' "$smoke_out" | grep -q " $code "; then
        echo "orlint smoke: rule $code produced no finding on the" \
             "known-bad fixture (rule deleted or broken?)"
        echo "$smoke_out"
        exit 1
    fi
done
# the legal evolution move must stay silent: the fixture's AppendedMsg
# adds a DEFAULTED trailing field, which OR015 must NOT flag
if printf '%s\n' "$smoke_out" | grep -q "AppendedMsg"; then
    echo "orlint smoke: OR015 flagged AppendedMsg — a defaulted" \
         "trailing append is the LEGAL evolution move and must pass"
    echo "$smoke_out"
    exit 1
fi
echo "ok: known-bad fixture trips all 15 rules (legal append silent)"

echo "== wire-schema lock (extracted schema vs committed lock + goldens) =="
# the schema-lock lane (docs/Wire.md "Schema evolution"): re-extract
# the wire/persist schema from source, fail on ANY drift vs
# openr_tpu/types/wire_schema.lock.json (breaking drift additionally
# trips orlint OR015 above; benign drift means the committed lock text
# is stale — regenerate with `python -m tools.orlint.wireschema
# --write`), verify the lock covers 100% of serde-registered types,
# and verify the golden-frame corpus exists and regenerates
# byte-identically for the current lock version
JAX_PLATFORMS=cpu python -m tools.orlint.wireschema --check

echo "== topo-churn smoke (fixed seed, warm-start counter + parity gate) =="
# the topology-delta acceptance gate (docs/Decision.md): single-link
# metric changes on a 320-node grid must take the warm-start path
# (decision.rebuild.topo_delta, zero full area solves) and stay
# byte-equal to from-scratch compute_rib — bench_churn --smoke exits 1
# on any counter or parity violation, and (compile ledger,
# monitor/compile_ledger.py) on ANY post-warmup XLA compile: steady
# state under churn must be pure jit-cache hits (docs/Linting.md
# OR008-OR010)
JAX_PLATFORMS=cpu python benchmarks/bench_churn.py \
    --topo-churn --nodes 320 --topo-rounds 30 --smoke --backend cpu \
    2> >(smoke_log topo_churn_smoke)

echo "== prefix-churn smoke (scoped-path counters + compile ledger gate) =="
# the prefix-only rebuild path under the same zero-steady-state-
# recompile gate: every churn round must be decision.rebuild.
# prefix_only with zero SPF solves and zero post-warmup compiles
JAX_PLATFORMS=cpu python benchmarks/bench_churn.py \
    --prefix-churn --nodes 80 --prefix-rounds 40 --smoke --backend cpu \
    2> >(smoke_log prefix_churn_smoke)

echo "== work-ledger smoke (delta-proportionality attribution gates) =="
# the steady-state work ledger gate (docs/Monitor.md "Work ledger"):
# the full dataflow — two-area decision, real delta FIB, real ABR
# redistribution — under prefix AND topo churn must show
# work.fib.ratio pinned at 1, work.election.ratio bounded, the two
# formerly-O(routes) walks (cross-area merge, RIB redistribution)
# holding their ISSUE 17 delta-native bounds (ratios <= 8,
# oroutes_share ~0 of the full-table budget), zero post-warmup XLA
# compiles, and no delta-proportional stage — merge and redistribute
# now included — breaching k*delta+floor in any steady round —
# bench_churn --work-bench --smoke exits 1 on any of those
JAX_PLATFORMS=cpu python benchmarks/bench_churn.py \
    --work-bench --nodes 36 --work-prefixes 2000 --work-rounds 12 \
    --work-mode both --smoke --backend cpu \
    2> >(smoke_log work_ledger_smoke)

echo "== 100k-prefix data-plane smoke (vectorized election + delta FIB) =="
# the million-prefix pipeline at CI scale: one 100k-prefix rung through
# solve → batched election → RIB → group-aware diff → delta FIB
# programming; exits 1 unless byte-parity vs the scalar oracle holds,
# routes/sec beats the per-prefix scalar loop >= 5x on this host, zero
# post-warmup XLA compiles landed (PR 7 ledger), and the idle FIB
# program pass scanned zero routes (the O(1) delta-book contract)
JAX_PLATFORMS=cpu python benchmarks/bench_prefix_scale.py --smoke \
    --prefixes 100000 --nodes 512 2> >(smoke_log prefix_scale_smoke)

echo "== flood-throughput smoke (binary wire vs JSON baseline) =="
# the wire-format acceptance gate (docs/Wire.md): on a small emulated
# grid, BOTH codecs run the same seeded churn + flap + anti-entropy
# workload and bench_churn --smoke exits 1 unless the binary path is
# active (serialize-once counter-asserted: flood_encodes < floods_sent),
# delta full_sync noop probes were served with zero keys shipped,
# floods/sec >= the JSON baseline, bytes/flood is reduced >= 2x, and
# the emulator invariant checker stayed clean on both codecs
JAX_PLATFORMS=cpu python benchmarks/bench_churn.py \
    --flood-bench --flood-side 4 --flood-events 120 --flood-flaps 2 \
    --smoke --backend cpu 2> >(smoke_log flood_bench_smoke)

echo "== flood-trace smoke (hop-span waterfall + overhead gate) =="
# the cluster observability gate (docs/Monitor.md "Flood tracing"): on
# a small emulated grid, sampled cross-node flood traces must complete
# end-to-end across >= 3 hops, every completed span's named-stage
# waterfall must telescope to its total (>= 95% attributed), and
# sampled tracing's isolated wire cost must stay < 5%: span bytes as
# a share of flood bytes, AND wire-seam ns-per-byte vs the untraced
# binary baseline (1-in-16 sampling, 2 interleaved pairs, per-arm MIN
# — the pure-CPU seam measure only ever gains time from contention;
# per-FLOOD time is reported but conflates coalescing batch shape
# with codec cost, so it is not the gate)
JAX_PLATFORMS=cpu python benchmarks/bench_churn.py \
    --flood-trace --flood-trace-every 16 --flood-repeats 2 \
    --flood-side 4 --flood-events 120 --flood-flaps 1 \
    --smoke --backend cpu 2> >(smoke_log trace_smoke)

echo "== device-telemetry smoke (kernel cost ledger + ctrl export) =="
# the device telemetry gate (docs/Monitor.md "Device telemetry"): on
# the CPU backend every canonical jitted kernel entry point (split RIB
# solve, batched split kernel, sharded split over a 2x2
# mesh, device election, KSP) must own a captured
# cost_analysis/memory_analysis row, a live node must serve them
# through ctrl get_device_telemetry with HBM gauges explicitly
# degraded, and re-running everything post-warmup must add ZERO XLA
# compiles — the capture path itself is compile-ledger gated
JAX_PLATFORMS=cpu python benchmarks/bench_device_telemetry.py --smoke \
    2> >(smoke_log device_telemetry_smoke)

echo "== bench-history sentinel (warn-only) =="
# flags >25% drift of the newest BENCH_HISTORY.jsonl row's headline
# metrics vs the median of prior same-fingerprint runs
# (benchmarks/history.py). Warn-only by design: bench variance on
# burstable CI hosts is real, so the lane reports, never blocks
JAX_PLATFORMS=cpu python benchmarks/history.py --check || true

echo "== serde micro-bench (encode/decode ns per Publication) =="
JAX_PLATFORMS=cpu python benchmarks/bench_serde.py --iters 500

echo "== soak smoke (fixed seed, 2 rounds, 9-node grid) =="
# the tier-1-safe slice of the long-horizon soak: storms + background
# prefix churn + all five invariant classes + memory watermark, with
# the seed+round replay hint on any failure (docs/Emulator.md)
JAX_PLATFORMS=cpu python -m openr_tpu.emulator --soak \
    --topo grid --nodes 9 --seed 7 --rounds 2

echo "== multi-process cluster smoke (real sockets, real crashes) =="
# the process-boundary gate (docs/Emulator.md "Multi-process
# clusters"): a 16-node fat-tree where every node is its own OS
# process speaking real UDP spark discovery and TCP kvstore flooding,
# observed only over per-process ctrl RPC. A ToR is SIGKILLed and
# restarted (new ephemeral ports — the Spark GR re-handshake path),
# the fabric is partitioned into halves and healed, and after each
# fault the full cross-process invariant suite must come back clean
# (kvstore digest convergence, FIB-vs-oracle parity, no stuck
# backoff/queues, counter sanity, per-process work-ledger ratios) with
# ZERO post-warmup XLA compiles counter-asserted via ctrl on every
# surviving process. Flight-recorder rings are gathered over ctrl into
# a dump dir on any violation; the replay seed is embedded in the
# failure message. exits 1 on any of those
rm -rf "$SMOKE_LOG_DIR/proc-smoke"
JAX_PLATFORMS=cpu python benchmarks/bench_cluster.py --smoke \
    --workdir "$SMOKE_LOG_DIR/proc-smoke" --keep \
    2> >(smoke_log proc_cluster_smoke)

echo "== crash-recovery smoke (journaled warm boot under torn write) =="
# the crash-consistent persistence gate (docs/Persist.md): journal
# append/replay micro-bench (row into the BENCH_HISTORY sentinel),
# then a 16-node multi-process pod with persistence on — durable book
# digests snapshotted at quiescence, a torn write armed and fed doomed
# churn, GR announced, the victim SIGKILLed mid-churn and re-exec'd.
# exits 1 unless the full cross-process invariant suite passes, the
# recovered books are byte-identical to the pre-crash snapshot with
# zero withdrawal window observed by survivors, the torn frame was
# found and truncated at boot, boot reconciliation stayed delta-
# proportional (work.persist_replay bound), and zero steady-state XLA
# compiles landed across the whole cycle
rm -rf "$SMOKE_LOG_DIR/persist-smoke"
JAX_PLATFORMS=cpu python benchmarks/bench_persist.py --smoke \
    --workdir "$SMOKE_LOG_DIR/persist-smoke" --keep \
    2> >(smoke_log persist_smoke)

echo "== pytest tier-1 (not slow) =="
# the fast lane the PR driver gates on — observability (test_perf),
# CLI/ctrl export, dirty-scoped rebuild parity (test_rebuild_scoped),
# the chaos soak matrix (test_chaos: three fixed-seed storms x both
# solvers — this subsumes the old inline chaos smoke), the orlint
# self-tests (test_orlint: per-rule fixtures + shipped-baseline zero-
# stale check) and the task-hygiene regressions (test_task_hygiene).
# tests/conftest.py runs every loop in asyncio DEBUG mode and fails
# any test that leaks pending tasks or never-retrieved exceptions.
python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors

echo "== pytest slow lane =="
# exit 5 = nothing collected (no slow-marked tests yet) — not a failure
python -m pytest tests/ -q -m 'slow' || [ $? -eq 5 ]

echo "== driver contract =="
python __graft_entry__.py 8

echo "CI OK"
