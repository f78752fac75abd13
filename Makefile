# gordo-components-tpu build/test targets
# (reference parity: the upstream Makefile's test/docker targets,
# SURVEY.md §2 "packaging/CI" — adapted to the TPU-native stack)

PYTHON ?= python
IMAGE_PREFIX ?= gordo-components-tpu
TAG ?= latest

.PHONY: test test-fast chaos chaos-deadline slo rebalance stream wire replay saturate mesh fleet history gameday heat qos seqperf hotloop perf-guard trace-demo slo-demo rebalance-demo stream-demo wire-demo replay-demo saturate-demo mesh-demo fleet-demo incident-demo gameday-demo capacity-demo qos-demo images builder-image server-image watchman-image clean

test:
	$(PYTHON) -m pytest tests/ -q

# skip the slowest integration suites for a quick signal
test-fast:
	$(PYTHON) -m pytest tests/ -q -x \
		--ignore=tests/test_fleet_chunks.py \
		--ignore=tests/test_checkpoint.py

# fault-injection lane: drive every registered faultpoint through the
# public HTTP/build APIs and assert the documented degraded state
# (tests/test_chaos.py; the standing regression harness for robustness)
chaos:
	$(PYTHON) -m pytest tests/ -q -m chaos

# deadline lane: latency faults + short request budgets through the
# public HTTP API — proves expired requests 504 WITHOUT device dispatch,
# the retry budget caps re-offers <1.1x, and hedges win against a slow
# replica (tests/test_deadline.py)
chaos-deadline:
	$(PYTHON) -m pytest tests/test_deadline.py -q -m chaos

# SLO lane: goodput accounting + burn-rate engine — the chaos
# acceptance (goodput drops / burn rises under latency faults with
# tight deadlines), the no-drift contract between /slo, /stats, and the
# registry, and the ledger's <=5% enabled / ~0% disabled overhead guard
# (tests/test_goodput.py)
slo:
	$(PYTHON) -m pytest tests/ -q -m slo --continue-on-collection-errors

# rebalance lane: the placement control plane — deterministic LPT
# planner, zero-downtime generation swap (incl. the bank.swap chaos
# rollback), the hot-workload >=2x skew-cut acceptance with zero non-200s
# under concurrent load, watchman rollup consistency across a generation
# change, and the <=5% load-tracking hot-loop guard
# (tests/test_placement.py + the reload no-5xx regression)
rebalance:
	$(PYTHON) -m pytest tests/ -q -m rebalance --continue-on-collection-errors
	$(PYTHON) -m pytest tests/test_reload.py -q -k zero_non_200

# streaming lane: the ingestion & online adaptation plane — window
# buffers/watermarks/late-row accounting, drift detection flagging
# exactly the shifted members, the recalibrate/refit -> zero-downtime
# generation swap acceptance (zero non-200s under concurrent load, FP
# rate drops), the stream.ingest/stream.refit chaos rollbacks, and the
# GORDO_STREAM=0 default-off contract (tests/test_streaming.py)
stream:
	$(PYTHON) -m pytest tests/ -q -m stream --continue-on-collection-errors

# replay lane: the time-compressed backtest harness — the clock seam
# (staleness/SLO/scrape aging on an injected timeline), duplicate-
# delivery dedup, provider chunk-invariance, and every incident class
# in replay/scenarios.py driven through the real ingest -> drift ->
# recalibrate/refit -> hot-swap path at >=100x with verdict bounds
# asserted (tests/test_replay.py; threshold/EWMA/refit knobs are tuned
# against THIS lane, not vibes)
replay:
	$(PYTHON) -m pytest tests/ -q -m replay --continue-on-collection-errors

# wire lane: the binary tensor data plane — frame codec round-trips
# (dtype/shape/endianness, truncated/oversized/malformed -> 400 with
# reason), JSON-vs-tensor bitwise score parity through the live app
# (incl. 410 quarantine, 504 deadline, chaos bank.score faults on the
# binary path), client auto-negotiation + foreign-server downgrade, the
# per-encoding metric rows, and tensor ingest (tests/test_wire.py)
wire:
	$(PYTHON) -m pytest tests/ -q -m wire --continue-on-collection-errors

# saturation lane: the serving-plane saturation stack — multi-worker
# pool (shared state, per-worker engines, SO_REUSEPORT + acceptor
# fallback, cross-loop reload), the uds/shm zero-copy transports with
# cross-transport bitwise parity + the shm error surface, the client's
# transport negotiation ladder with graceful tcp fallback, and push
# mode's long-poll/backpressure/default-off contracts
# (tests/test_saturate.py + the parity legs in tests/test_wire.py)
saturate:
	$(PYTHON) -m pytest tests/ -q -m saturate --continue-on-collection-errors

# mesh lane: the multi-host serving plane — mesh bootstrap/partition,
# watchman's versioned routing table (ETag polling, health stamps),
# cross-replica member migration with zero non-200s under load (the
# acquire -> route -> release sequence over both banks' hot-swaps),
# routing edge cases (no owner -> 404 with reason, dual owner ->
# bitwise-identical answers, empty fleet), the client's partition-aware
# fan-out + stale-table reroute + health-gated hedging, and the fleet
# placement tier's planner gates (tests/test_mesh.py; multi-process
# coverage lives in the perfguard leg + tools/mesh_demo.py)
mesh:
	$(PYTHON) -m pytest tests/ -q -m mesh --continue-on-collection-errors

# fleet lane: the declarative fleet compiler — compile-only golden-DAG
# determinism (YAML in -> byte-identical DAG JSON out), content-digest
# incremental staleness, spec/canary validation, the canary judge's
# verdict edges (zero-traffic hold, burn/goodput rollback), and the
# live-server execution legs: end-to-end build -> place -> canary ->
# promote with zero data-plane non-200s, SLO fast-burn auto-rollback,
# the workflow.canary chaos rollback, and incremental re-run asserted
# by step-key digests (tests/test_fleet_compiler.py)
fleet:
	$(PYTHON) -m pytest tests/ -q -m fleet --continue-on-collection-errors

# history lane: the fleet flight recorder — retained metric history
# (tiered rings, counter-delta rates, strict memory bound), the
# structured event timeline (every state transition, ring-bounded),
# watchman incident correlation (burn episodes x fleet events ->
# GET /incidents), the canary history-window judge (single polls can
# neither promote nor roll back), and the fleet /slo last-good
# staleness contract (tests/test_history.py)
history:
	$(PYTHON) -m pytest tests/ -q -m history --continue-on-collection-errors

# game-day lane: mesh-scale chaos drills — the scenario catalog + judge
# verdict edges, the harness's subprocess env contract (mesh identity /
# per-replica GORDO_FAULTS isolation), the compiler's fleet.gameday.gate
# -> gameday/fleet pre-promotion step (failed gate blocks promote), and
# the slow legs: real N-subprocess meshes + a live watchman SIGKILLed /
# partitioned / slowed on purpose, every failure judged end-to-end by
# the SLO/incident stack (tests/test_gameday.py + the gate legs in
# tests/test_fleet_compiler.py; the full 6-scenario catalog also runs
# via `make gameday-demo`)
gameday:
	$(PYTHON) -m pytest tests/ -q -m gameday --continue-on-collection-errors

# heat lane: the access-heat & device-cost observatory — decayed
# per-member heat math (decay identity, tiers, eviction, steady state),
# the skewed-load acceptance (4 hot members at 8x rank hottest on
# GET /heat, watchman rollup byte-for-byte), per-bucket FLOPs/MFU
# attribution on GET /costs for every live bucket (mixed dense/LSTM
# archs), analytic-FLOPs-vs-XLA cost_analysis cross-check, the metric
# cardinality guard (GORDO_METRIC_MAX_SERIES), heat surviving /reload
# swaps, and the <=5% hot-loop overhead guard (tests/test_heat_cost.py)
heat:
	$(PYTHON) -m pytest tests/ -q -m heat --continue-on-collection-errors

# QoS lane: the multi-tenant fairness stack — request classification
# (headers + __meta__ sidecar, alias/sanitize/cardinality rules), the
# per-tenant token buckets and the three admission rules (tenant_rate /
# queue_pressure / goodput_burn, each with an honest Retry-After), the
# weighted-fair queue's starvation bound + class-aware deadline order,
# per-class metric plumbing end to end (render -> parse -> watchman
# rollup, unknown tenants collapsed to `other`), and the noisy-neighbor
# acceptance on BOTH the JSON and binary tensor paths: a best_effort
# flood at 5x capacity must leave interactive goodput >=0.95, land
# >=90% of sheds on the flooding class, and never 429 the interactive
# probe (tests/test_qos.py)
qos:
	$(PYTHON) -m pytest tests/ -q -m qos --continue-on-collection-errors

# hot-loop overhead lane: every disabled-instrumentation guard in one
# named check (metrics recording, disarmed faultpoints, tracing) — a
# regression that makes "off" cost >5% on the serving loop fails HERE,
# not buried in the full run
hotloop:
	$(PYTHON) -m pytest tests/ -q -m hotloop --continue-on-collection-errors

# sequence fast-path lane: time-major vs legacy parity (gang epoch,
# end-to-end fleet incl. the heterogeneous 8-shard leg, bank scoring),
# interpret-mode fused recurrent-step kernel bands, width-autotune
# persistence round-trip, width-cap dispatch splitting, gang-scheduled
# build vs serial, and the time-major>=legacy perf guard
# (tests/test_seq_fastpath.py)
seqperf:
	$(PYTHON) -m pytest tests/ -q -m seqperf --continue-on-collection-errors

# perf-guard lane: every hot-loop overhead guard PLUS the pipelined-vs-
# serial parity+no-slower check (tests/test_bank_pipeline.py) PLUS the
# banked-kernel legs (tests/test_banked_kernel.py parity sweep and
# tests/test_bank_quantized.py fused-kernel>=XLA-at-equal-dtype) PLUS
# the tensor-path>=JSON-path wire guard (tests/test_wire.py) PLUS the
# saturation guards (tests/test_saturate.py: multi-worker >= single
# under mixed load, uds >= tcp) PLUS the mesh fan-out guard
# (tests/test_mesh.py: partition-aware routed client >= single-URL on a
# real 2-process mesh; the parallel-win bound asserts only on
# multi-core hosts) — the scoring pipeline must never regress below the
# serial path it replaced, the fused kernel below the XLA epilogue, the
# binary data plane below the JSON path it bypasses, the local
# transports below the TCP stack they bypass, or the routing path below
# naive broadcast
perf-guard:
	$(PYTHON) -m pytest tests/ -q -m "hotloop or perfguard" --continue-on-collection-errors

# short serve loop with tracing at sample=1.0; prints the top-3 slow
# traces with their per-stage breakdown (tools/trace_demo.py)
trace-demo:
	$(PYTHON) tools/trace_demo.py

# short mixed-deadline serve loop; prints the goodput ledger and the
# SLO burn-rate table (tools/slo_demo.py)
slo-demo:
	$(PYTHON) tools/slo_demo.py

# deliberately skewed fleet on an 8-shard virtual mesh -> plan -> swap;
# prints shard skew before/after and the flip pause (tools/rebalance_demo.py)
rebalance-demo:
	$(PYTHON) tools/rebalance_demo.py

# live-stream loop on a small fleet: inject a mean-shift drift -> watch
# detection flag exactly the shifted members -> recalibrate (and refit)
# through the zero-downtime swap -> FP rate drops; prints one JSON doc
# (tools/stream_demo.py)
stream-demo:
	$(PYTHON) tools/stream_demo.py

# posts the same batch as JSON, parquet, and framed tensor bodies and
# prints rows/s + bytes/row side by side (tools/wire_demo.py)
wire-demo:
	$(PYTHON) tools/wire_demo.py

# drives the same scoring batch over tcp, uds, and the shm ring through
# the real multi-worker pool (parity-gated) and prints per-transport
# rows/s + bytes/row, the in-process ceiling, the end-to-end gap ratio,
# and push-mode windows/s (tools/saturate_demo.py)
saturate-demo:
	$(PYTHON) tools/saturate_demo.py

# backtests the standard incident library through the real adaptive
# loop at 100-1000x and prints the per-scenario verdict table +
# one JSON doc (tools/replay_demo.py)
replay-demo:
	$(PYTHON) tools/replay_demo.py

# true multi-process mesh: 2 partitioned server processes + a live
# watchman routing table; prints single-vs-mesh rows/s (with cpu_count —
# the parallel win needs real cores), fan-out per replica, and a live
# cross-replica migration's zero-non-200 verdict (tools/mesh_demo.py)
mesh-demo:
	$(PYTHON) tools/mesh_demo.py

# compiles a fleet spec to the typed DAG, executes it end to end against
# a live in-process server (build gangs -> place -> canary -> promote
# under scoring traffic), then edits one machine and re-runs to show the
# incremental recompile ratio; prints one JSON doc (tools/fleet_demo.py)
fleet-demo:
	$(PYTHON) tools/fleet_demo.py

# game-day drill for the fleet flight recorder: injects scoring errors
# (quarantine) + a queue stall vs tight deadlines (SLO burn) under live
# load, recovers, then asks a real watchman /incidents for the
# correlated fault -> burn -> quarantine -> recovery timeline; prints
# one JSON doc (tools/incident_demo.py)
incident-demo:
	$(PYTHON) tools/incident_demo.py

# breaks a real multi-process mesh on purpose: boots N server
# subprocesses + a live watchman per scenario shape, runs the full
# game-day catalog (SIGKILL crash/restart, watchman partition,
# migration storm, gray failure, thundering herd, correlated drift)
# under sustained scoring load, and prints the per-scenario verdict
# table + one JSON doc (tools/gameday_demo.py)
gameday-demo:
	$(PYTHON) tools/gameday_demo.py

# capacity advisor on a live skewed fleet: drives 4x-hot traffic over a
# mixed dense/LSTM bank, reads GET /heat + GET /costs + bank capacity,
# and prints the advisor tables (tier split, per-bucket MFU league,
# projected members per HBM budget per dtype) + one JSON doc
# (tools/capacity_demo.py)
capacity-demo:
	$(PYTHON) tools/capacity_demo.py

# best_effort flood vs a steady interactive probe through the real
# serving stack (admission + weighted-fair engine + per-class SLO);
# prints the per-class fairness table (admitted/shed, WFQ dequeues,
# per-tenant goodput + burn) + one JSON doc (tools/qos_demo.py)
qos-demo:
	$(PYTHON) tools/qos_demo.py

images: builder-image server-image watchman-image

builder-image:
	docker build -f Dockerfile-ModelBuilder -t $(IMAGE_PREFIX)/builder:$(TAG) .

server-image:
	docker build -f Dockerfile-ModelServer -t $(IMAGE_PREFIX)/server:$(TAG) .

watchman-image:
	docker build -f Dockerfile-Watchman -t $(IMAGE_PREFIX)/watchman:$(TAG) .

clean:
	rm -rf build dist *.egg-info .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
