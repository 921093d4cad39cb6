# Test targets. Tier-1 (the CI gate) runs the whole suite minus
# @pytest.mark.slow stress cases and holds correctness only; speed is
# measured on the chip by benchmarks/run.py (docs/TESTING.md
# "Benchmark"). The qos-smoke target runs the serving
# QoS fault-injection suite in isolation (fast feedback while tuning
# admission/deadline/hedge knobs — see docs/QOS.md); ingest-smoke pushes
# a small CSV through `cli.py import` against an in-process server and
# exercises the routed-import suite (docs/INGEST.md); serving-smoke
# gates the host-path fast lane — keep-alive reuse via the
# connection-count oracle, and /internal/query-batch returning
# byte-identical results vs per-query dispatch (docs/OPERATIONS.md);
# sync-smoke gates the anti-entropy/resize fast path — batched-manifest
# repair byte-identical to the per-fragment path, the ≤2-RTT diff
# oracle, compression negotiation, and pacer bounds.
# durability-smoke gates the write-path durability subsystem — group
# commit batching, torn-tail fuzz, the SIGKILL crash-recovery oracle
# (group + per-op modes), and the backup/restore round trip
# (docs/OPERATIONS.md).

PYTEST := env JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider

.PHONY: test test-slow qos-smoke ingest-smoke serving-smoke sync-smoke \
	durability-smoke obs-smoke cost-smoke chaos-smoke scrub-smoke \
	mp-smoke multitenant-smoke mesh-smoke autopilot-smoke cdc-smoke \
	elastic-smoke hostpath-smoke ingest-kernel-smoke

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
# programs and trace-report, the second clock and the CPU by thread role
# (docs/OBSERVABILITY.md)
obs-smoke:
	$(PYTEST) tests/test_tracing.py tests/test_stage_tracing.py \
		tests/test_stage_cpu.py -m "not slow"

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

# mesh-smoke: the mesh reduction — byte-identical results vs
# single-device across mesh sizes 1/2/4/8 at non-divisible shard
# counts, the dist_reduce_* byte count + PROFILE reduceBytes, and the
# query_raw vs cache-hit envelope mirror contract
# (docs/OPERATIONS.md multi-chip mesh)
mesh-smoke:
	$(PYTEST) tests/test_mesh_reduction.py tests/test_envelope_contract.py \
		-m "not slow"

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
	$(PYTEST) tests/test_roaring_kernels.py tests/test_row_leaf_decode.py \
		tests/test_hostpath_lint.py -m "not slow"
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
