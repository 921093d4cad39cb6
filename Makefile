# Test targets. Tier-1 (the CI gate) runs the whole suite minus
# @pytest.mark.slow stress cases; the qos-smoke target runs the serving
# QoS fault-injection suite in isolation (fast feedback while tuning
# admission/deadline/hedge knobs — see docs/QOS.md); ingest-smoke pushes
# a small CSV through `cli.py import` against an in-process server and
# exercises the routed-import suite (docs/INGEST.md); serving-smoke
# gates the host-path fast lane — keep-alive reuse via the
# connection-count oracle, and /internal/query-batch returning
# byte-identical results vs per-query dispatch (docs/OPERATIONS.md);
# sync-smoke gates the anti-entropy/resize fast path — batched-manifest
# repair byte-identical to the per-fragment path, the ≤2-RTT diff
# oracle, compression negotiation, and pacer bounds. bench-sync runs the
# seeded-divergence repair benchmark (control RTTs, wall, wire bytes).
# durability-smoke gates the write-path durability subsystem — group
# commit batching, torn-tail fuzz, the SIGKILL crash-recovery oracle
# (group + per-op modes), and the backup/restore round trip;
# bench-durability measures group vs per-op write QPS at 25% write
# fraction plus the crash and restore oracles (docs/OPERATIONS.md).

PYTEST := env JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider

.PHONY: test test-slow qos-smoke ingest-smoke serving-smoke sync-smoke \
	durability-smoke obs-smoke cost-smoke chaos-smoke scrub-smoke \
	mp-smoke multitenant-smoke mesh-smoke autopilot-smoke bench-ingest \
	bench-serving bench-sync bench-durability bench-tracing \
	bench-profiling bench-chaos bench-scrub bench-mp bench-multitenant \
	bench-mesh bench-mesh-quantized bench-autopilot cdc-smoke bench-cdc \
	elastic-smoke bench-elastic hostpath-smoke bench-hostpath \
	ingest-kernel-smoke

test:
	$(PYTEST) tests/ -m "not slow"

test-slow:
	$(PYTEST) tests/ -m slow

qos-smoke:
	$(PYTEST) tests/test_qos.py -m "not slow"

ingest-smoke:
	$(PYTEST) tests/test_ingest.py -m "not slow"

serving-smoke:
	$(PYTEST) tests/test_fastlane.py -m "not slow"

sync-smoke:
	$(PYTEST) tests/test_sync_fastpath.py -m "not slow"

durability-smoke:
	$(PYTEST) tests/test_durability.py -m "not slow"

# obs-smoke: start a node, run a traced query, assert /debug/traces
# renders the span tree, /debug/queries shows-then-clears, and /metrics
# is stock-Prometheus parseable; the stage site's four sinks, the named
# programs and trace-report (docs/OBSERVABILITY.md)
obs-smoke:
	$(PYTEST) tests/test_tracing.py tests/test_stage_tracing.py -m "not slow"

# cost-smoke: the query cost plane — PQL PROFILE single-node + 3-node
# stitching, /debug/tenants accounting, /debug/heatmap skew ranking,
# SLO burn-rate flips, knob roundtrips, and the stats quantile edge
# cases (docs/OBSERVABILITY.md)
cost-smoke:
	$(PYTEST) tests/test_cost.py tests/test_stats_quantiles.py -m "not slow"

# chaos-smoke: the partition-tolerance gate — fault-plane semantics,
# symmetric/asymmetric partition scenarios (minority read-only
# degradation, corroborated death, epoch fencing, rejoin) and one
# seeded chaos schedule through the four oracles
# (docs/OPERATIONS.md failure model)
chaos-smoke:
	$(PYTEST) tests/test_faults.py tests/test_partition.py -m "not slow"

# scrub-smoke: the storage-integrity gate — checksum sidecars +
# verified loads, quarantine at open, every-offset corruption fuzz,
# scrubber detection / read-repair / self-heal, ENOSPC degraded mode
# with auto-recovery, epoch-file hardening, restore read-back verify,
# and the CLI check verb (docs/OPERATIONS.md integrity runbook)
scrub-smoke:
	$(PYTEST) tests/test_integrity.py -m "not slow"

# mp-smoke: the multi-process serving tier — shm-ring framing/fuzz/
# backpressure/reclaim units, the end-to-end worker+owner contract
# (byte-identical responses, WAL ACK barrier under owner SIGKILL,
# tenant/trace attribution over the ring, degraded shedding, worker
# respawn, owner-restart re-handshake, single-process fallback), and
# one kill-a-worker chaos schedule (docs/OPERATIONS.md deployment
# shapes)
mp-smoke:
	$(PYTEST) tests/test_shmring.py tests/test_mpserve.py -m "not slow"

# multitenant-smoke: the skewed-traffic actuators — result-cache unit
# semantics (per-field invalidation, the fill-race version fence,
# heat-weighted eviction), read-your-writes through the HTTP cache path
# (sequential, concurrent, and across mp-serving workers' rings),
# PROFILE/ledger satellites, /debug/rescache + heatmap tier view,
# tiering demote/promote/hysteresis/pacing, and knob roundtrips
# (docs/OPERATIONS.md skewed traffic)
multitenant-smoke:
	$(PYTEST) tests/test_multitenant.py -m "not slow"

# mesh-smoke: the hierarchical reduction plane — byte-identical results
# vs single-device across mesh sizes 1/2/4/8 incl. 2-D groups x shards
# factorizations at non-divisible shard counts, the narrowed-lane wire
# model + PROFILE reduceBytes, the roaring row-frame roundtrip, the
# quantized candidate-ranking lane (error-bound/window properties +
# verify_quantized byte-identity + wire counters), the MULTICHIP record
# schema + hardened trace parse, the experimental-fallback multi-mesh
# serialization guard, and the query_raw vs cache-hit envelope mirror
# contract (docs/OPERATIONS.md multi-chip mesh)
mesh-smoke:
	$(PYTEST) tests/test_mesh_reduction.py tests/test_envelope_contract.py \
		tests/test_multichip_schema.py -m "not slow"

# autopilot-smoke: the placement plane — planner properties (uniform ⇒
# zero moves, hot-spot drain, dwell freezing), placement-table fencing/
# persistence/fallback byte-identity vs the hash ring, the end-to-end
# forced-move resize, and the knob-parity contract across every config
# surface (TOML / env / snake / kebab / generated template)
autopilot-smoke:
	$(PYTEST) tests/test_autopilot.py tests/test_config_parity.py \
		-m "not slow"

# cdc-smoke: the CDC backbone — WAL tail cursor semantics (resume,
# rotation survival, segment-GC pinning, 410 on truncation AND on
# unknown-cursor restart detection), frame codec torn-frame fuzz,
# follower attach/apply/resync convergence, the staleness QoS header,
# and restore --as-of point-in-time bit-exactness
cdc-smoke:
	$(PYTEST) tests/test_cdc.py -m "not slow"

# elastic-smoke: the membership plane — graceful drain state machine
# (shed-writes latch, cursor handoff, clean leave, coordinator-failover
# resume), heat-ordered byte-verified join warm-up, the range-keyed
# placement table (byte-identity fallback, mixed-version gossip,
# persistence round-trip), sub-shard split/merge planning, and the
# autopilot/drain mutual-exclusion contract (docs/OPERATIONS.md
# elastic operations)
elastic-smoke:
	$(PYTEST) tests/test_elastic.py tests/test_placement_ranges.py \
		-m "not slow"

# hostpath-smoke: the vectorized roaring kernel layer — byte-identity
# property tests (random + adversarial + corruption-fuzz fragments) for
# every kernel vs the per-container reference walks, PROFILE
# container-scan accounting parity, and the static lint that keeps
# per-container python loops out of the rewired host paths
# (docs/OPERATIONS.md host-path kernels)
hostpath-smoke:
	$(PYTEST) tests/test_roaring_kernels.py tests/test_hostpath_lint.py \
		-m "not slow"
	env JAX_PLATFORMS=cpu python scripts/check_hostpath_loops.py

# ingest-kernel-smoke: the write-path fast lane — byte-identity
# property/fuzz tests for the whole-batch merge kernels vs the retired
# per-container loop (randomized + adversarial batches, mutex/BSI merge
# rules, batched membership probes, WAL-replay equivalence), plus the
# host-path lint over the write-side consumer modules
# (docs/OPERATIONS.md write-path fast lane)
ingest-kernel-smoke:
	$(PYTEST) tests/test_merge_kernels.py tests/test_hostpath_lint.py \
		-m "not slow"
	env JAX_PLATFORMS=cpu python scripts/check_hostpath_loops.py

bench-ingest:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs ingest

bench-serving:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs serving

bench-sync:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs sync

bench-durability:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs durability

bench-tracing:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs tracing

# overhead gate for the query cost plane: profile-off <= 1%,
# profile-on <= 10% vs the bare fast-lane plateau
bench-profiling:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs profiling

# >=20 randomized partition/kill/heal schedules against a 3-node
# cluster under mixed read+write load, gated on the four
# partition-safety oracles (zero lost acked writes, no non-quorum
# deletion, <=1 coordinator per epoch, byte-identical replicas)
bench-chaos:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs chaos

# multi-process serving scaling gate: single-process fast-lane plateau
# vs 1/2/4 SO_REUSEPORT-worker plateaus (subprocess clients, best-of-3
# interleaved), byte-identical responses across shapes, ring round-trip
# quantiles, and the kill-a-worker chaos schedule
bench-mp:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs mp_serving

# host-path gate: the three rewired roaring host paths (row decode,
# scrub digesting, sync manifest diff) timed against in-bench copies of
# the retired per-container loops — byte-identical and >= 2x each —
# plus the Executor.submit host-cost number
bench-hostpath:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs hostpath

# storage-integrity gate: scrubber serving overhead >= 0.97x off,
# detection-latency bound, the corruption-heal + ENOSPC oracles, and
# randomized storage-fault chaos schedules
bench-scrub:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs scrub

# skewed-traffic gate: 120 indexes under Zipf traffic with QoS quotas
# active — hot-tenant p99 within 1.3x the single-index plateau, bounded
# cold-tenant tail, >50% result-cache hit rate on the Zipf hot set,
# read-your-writes through the cache path (single-process + mp-serving),
# and a heat-driven demote/promote cycle with zero serving errors
bench-multitenant:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs multitenant

# multi-chip reduction-plane gate: per-mesh-size (2/4/8, 2-D
# factorizations) subprocesses over the canonical 20 dryrun shapes —
# byte-identical vs the dense 1-D path, >=4x reduction-lane wire-byte
# reduction on Row/TopN, a measured quantized-ranking net wire
# reduction with byte-identical results (verify_quantized), and
# model-vs-measured wire reconciliation (or a structured skip on
# CPU-only hosts); records written to MULTICHIP_r07.json, shape pinned
# by scripts/check_multichip_schema.py
bench-mesh:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs mesh
	python scripts/check_multichip_schema.py

# just the quantized-ranking leg of the gate, per mesh size: the 8-bit
# lane's byte-identity certification + wire delta without the full
# record rewrite (docs/OPERATIONS.md quantized candidate ranking)
bench-mesh-quantized:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python bench_suite.py --mesh-inner 2
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python bench_suite.py --mesh-inner 4
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python bench_suite.py --mesh-inner 8

# autopilot placement-plane gate: a 3-process cluster under
# hot-spotted Zipf traffic — tail p99 recovers to <=1.5x the
# uniform-placement p99 with zero client errors and zero lost acked
# writes, autopilot-active chaos schedules trip none of the five
# oracles, and the kill-switch-off control cluster stays byte-identical
# to hash placement
bench-autopilot:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs autopilot

# CDC backbone gate: chaos schedules with a live out-of-cluster mirror
# (byte-identical to n0 after heal, restarts driving the
# unknown-cursor 410 → resync path), subprocess follower read scaling
# >= 1.7x primary-alone with staleness p99 under the 1 s budget, the
# X-Pilosa-Max-Staleness gate live, and every WAL seq between two
# backup generations restoring bit-exactly via restore --as-of
bench-cdc:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs cdc

# elastic membership gate: scripted 3->5->3 grow/shrink under live Zipf
# traffic with a ledgered writer (zero lost acked writes, p99
# continuity vs the steady-state plateau), a hot single shard recovered
# by a sub-shard range split spreading reads across >=2 owners, and
# chaos schedules that kill/partition mid-drain without tripping any
# oracle
bench-elastic:
	env JAX_PLATFORMS=cpu python bench_suite.py --configs elastic
