"""Server lifecycle: composition + tickers.

Reference: server.go (SURVEY.md §2 #20) — functional options compose the
holder, cluster, listeners, and background tickers (anti-entropy,
diagnostics, stats flush). Here ServerConfig plays the role of the option
set (populated from TOML/env/flags by pilosa_tpu.cli — SURVEY.md §5.6),
and tickers are daemon threads.
"""

from __future__ import annotations

import threading

from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import make_http_server
from pilosa_tpu.storage import Holder
from pilosa_tpu.utils.logger import new_standard_logger


class ServerConfig:
    def __init__(
        self,
        data_dir: str = "~/.pilosa_tpu",
        bind: str = "localhost",
        port: int = 10101,
        anti_entropy_interval: float = 600.0,
        replica_n: int = 1,
        verbose: bool = False,
        device_budget_bytes: int | None = None,
        name: str = "",
        advertise: str = "",
        seeds: list[str] | None = None,
        heartbeat_interval: float = 5.0,
        heartbeat_timeout: float = 2.0,
        use_mesh: bool | None = None,
        trace_sample_rate: float = 0.0,
        trace_log_dir: str = "",
        diagnostics_endpoint: str = "",
        statsd: str = "",
        long_query_time: float = 0.0,
        max_writes_per_request: int = 5000,
        ingest_workers: int = 1,
        tls_certificate: str = "",
        tls_key: str = "",
        tls_skip_verify: bool = False,
        qos_max_inflight: int = 0,
        qos_tenant_inflight: int = 0,
        qos_default_deadline: float = 0.0,
        qos_hedge_delay: float = 0.25,
        qos_hedge_budget: float = 0.05,
        qos_breaker_threshold: int = 5,
        qos_breaker_cooldown: float = 5.0,
        client_pool_size: int = 8,
        remote_batch: bool = True,
        sync_workers: int = 8,
        repair_max_bytes_per_sec: int = 0,
        repair_max_inflight: int = 0,
        repair_compression: bool = True,
        durability_mode: str = "group",
        group_commit_max_ms: float = 2.0,
        group_commit_max_ops: int = 256,
        slow_query_ring: int = 100,
        heat_half_life: float = 300.0,
        slo_objectives: list[str] | None = None,
        slo_windows: list[str] | None = None,
        verify_on_load: bool = True,
        scrub_interval: float = 0.0,
        scrub_max_bytes_per_sec: int = 0,
        serving_workers: int = 0,
        ring_slots: int = 1024,
        ring_slot_bytes: int = 65536,
        result_cache_bytes: int = 0,
        residency_promote_interval: float = 0.0,
        residency_promote_heat: float = 4.0,
        residency_demote_heat: float = 1.0,
        residency_host_tier_bytes: int = 1 << 30,
        autopilot_enabled: bool = False,
        autopilot_interval: float = 30.0,
        autopilot_heat_budget: float = 1.5,
        autopilot_max_moves: int = 4,
        autopilot_min_dwell: float = 0.0,
        autopilot_split_threshold: float = 0.0,
        autopilot_split_ways: int = 2,
        cdc_enabled: bool = False,
        cdc_max_retention_bytes: int = 64 << 20,
        cdc_poll_interval: float = 0.05,
        cdc_max_batch_bytes: int = 1 << 20,
        cdc_follow: str = "",
        cdc_staleness_budget: float = 1.0,
    ):
        self.data_dir = data_dir
        self.bind = bind
        self.port = port
        self.anti_entropy_interval = anti_entropy_interval
        self.replica_n = replica_n
        self.verbose = verbose
        self.device_budget_bytes = device_budget_bytes
        self.name = name
        self.advertise = advertise
        self.seeds = seeds or []
        self.heartbeat_interval = heartbeat_interval
        # Tight dedicated timeout for liveness probes (heartbeat, quorum
        # checks, death corroboration): a hung peer must not stall the
        # loop that detects every OTHER failure (docs/OPERATIONS.md
        # failure model).
        self.heartbeat_timeout = float(heartbeat_timeout)
        if self.heartbeat_timeout <= 0:
            raise ValueError(
                f"invalid heartbeat-timeout {heartbeat_timeout!r} "
                "(want > 0)"
            )
        self.use_mesh = use_mesh  # None = auto (mesh when >1 device)
        # Distributed tracing (docs/OBSERVABILITY.md): `trace-sample-rate`
        # sets probabilistic sampling of the span tree (0 = off; the
        # stage counters are always on). `trace-log-dir` is where POST
        # /debug/trace-device writes live JAX profiler captures
        # (default: <data-dir>/jax-traces).
        self.trace_sample_rate = float(trace_sample_rate)
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(
                f"invalid trace-sample-rate {trace_sample_rate!r} "
                "(want 0.0..1.0)"
            )
        self.trace_log_dir = trace_log_dir
        self.diagnostics_endpoint = diagnostics_endpoint
        self.statsd = statsd
        self.long_query_time = long_query_time
        self.max_writes_per_request = max_writes_per_request
        # bounded pool width for applying one import's independent local
        # shard groups (docs/INGEST.md); 1 = serial apply
        self.ingest_workers = ingest_workers
        self.tls_certificate = tls_certificate
        self.tls_key = tls_key
        self.tls_skip_verify = tls_skip_verify
        # Serving QoS (docs/QOS.md): admission gate (0 = unlimited),
        # server-default request deadline (0 = none), hedged replica
        # reads (initial delay before the p95 tracker warms up; budget as
        # a fraction of primary reads), per-node circuit breakers.
        self.qos_max_inflight = qos_max_inflight
        self.qos_tenant_inflight = qos_tenant_inflight
        self.qos_default_deadline = qos_default_deadline
        self.qos_hedge_delay = qos_hedge_delay
        self.qos_hedge_budget = qos_hedge_budget
        self.qos_breaker_threshold = qos_breaker_threshold
        self.qos_breaker_cooldown = qos_breaker_cooldown
        # Serving fast lane (docs/OPERATIONS.md): keep-alive connections
        # retained per peer by the internal client's pool, and whether
        # same-node remote sub-queries group-commit onto
        # /internal/query-batch.
        self.client_pool_size = client_pool_size
        self.remote_batch = remote_batch
        # Anti-entropy / resize data plane (docs/OPERATIONS.md): pipeline
        # width for the fragment diff/fetch/apply pass, token-bucket
        # pacing of repair transfers (bytes/sec; 0 = unpaced), inflight
        # transfer cap (0 = unbounded), and zlib Content-Encoding on
        # fragment/delta payloads.
        self.sync_workers = sync_workers
        self.repair_max_bytes_per_sec = repair_max_bytes_per_sec
        self.repair_max_inflight = repair_max_inflight
        self.repair_compression = repair_compression
        # Write-path durability (docs/OPERATIONS.md): how an acked
        # write reaches disk — `group` (one fsync per commit group, the
        # default), `per-op` (fsync per write), or `flush-only` (the
        # round-5 behavior: OS buffer only). The group knobs bound how
        # long a record may wait for its group's fsync and how large a
        # group may grow.
        from pilosa_tpu.storage.wal import DURABILITY_MODES

        if durability_mode not in DURABILITY_MODES:
            raise ValueError(
                f"invalid durability-mode {durability_mode!r} "
                f"(want one of {', '.join(DURABILITY_MODES)})"
            )
        self.durability_mode = durability_mode
        self.group_commit_max_ms = float(group_commit_max_ms)
        self.group_commit_max_ops = int(group_commit_max_ops)
        # Query cost plane (docs/OBSERVABILITY.md): slow-query ring
        # capacity behind /debug/queries/slow (the threshold is
        # long-query-time above), per-shard heat decay half-life, and
        # declarative SLO objectives with their burn-rate windows.
        # Objectives validate at CONFIG time (a typo'd spec must fail
        # startup, not silently never alert) — same policy as
        # trace-sample-rate.
        self.slow_query_ring = int(slow_query_ring)
        if self.slow_query_ring < 1:
            raise ValueError(
                f"invalid slow-query-ring {slow_query_ring!r} (want >= 1)"
            )
        self.heat_half_life = float(heat_half_life)
        if self.heat_half_life <= 0:
            raise ValueError(
                f"invalid heat-half-life {heat_half_life!r} (want > 0)"
            )
        self.slo_objectives = list(slo_objectives or [])
        self.slo_windows = list(slo_windows or [])
        # Storage integrity plane (docs/OPERATIONS.md integrity
        # runbook): verify-on-load checks fragment snapshots against
        # their checksum sidecars at open (corrupt files quarantine
        # instead of serving); scrub-interval > 0 runs the background
        # scrubber that re-verifies owned fragments' DISK bytes on a
        # scrub-max-bytes-per-sec token-bucket budget and read-repairs
        # rot from healthy replicas.
        self.verify_on_load = _parse_bool(verify_on_load)
        self.scrub_interval = float(scrub_interval)
        if self.scrub_interval < 0:
            raise ValueError(
                f"invalid scrub-interval {scrub_interval!r} (want >= 0)"
            )
        self.scrub_max_bytes_per_sec = int(scrub_max_bytes_per_sec)
        # Multi-process serving tier (docs/OPERATIONS.md deployment
        # shapes): serving-workers > 0 runs N SO_REUSEPORT worker
        # processes fronting this (device-owner) process over
        # per-worker shared-memory rings; 0 = classic single-process.
        # ring-slots/ring-slot-bytes size each direction of a worker's
        # ring pair (fixed-slot, so memory is slots x bytes, bounded).
        from pilosa_tpu.serving.mpserve import MAX_WORKERS

        self.serving_workers = int(serving_workers)
        if not 0 <= self.serving_workers <= MAX_WORKERS:
            raise ValueError(
                f"invalid serving-workers {serving_workers!r} "
                f"(want 0..{MAX_WORKERS})"
            )
        self.ring_slots = int(ring_slots)
        if self.ring_slots < 2:
            raise ValueError(
                f"invalid ring-slots {ring_slots!r} (want >= 2)"
            )
        self.ring_slot_bytes = int(ring_slot_bytes)
        if self.ring_slot_bytes < 256:
            raise ValueError(
                f"invalid ring-slot-bytes {ring_slot_bytes!r} "
                "(want >= 256)"
            )
        # Skewed-traffic actuators (docs/OPERATIONS.md skewed traffic):
        # the write-invalidated result cache (bytes of pre-serialized
        # hot responses; 0 = off) and the heat-driven residency tiering
        # worker (promote/demote pass interval; 0 = off) with its
        # hysteresis thresholds — promote must sit above demote or
        # borderline shards would thrash host<->device every pass.
        self.result_cache_bytes = int(result_cache_bytes)
        if self.result_cache_bytes < 0:
            raise ValueError(
                f"invalid result-cache-bytes {result_cache_bytes!r} "
                "(want >= 0)"
            )
        self.residency_promote_interval = float(residency_promote_interval)
        if self.residency_promote_interval < 0:
            raise ValueError(
                "invalid residency-promote-interval "
                f"{residency_promote_interval!r} (want >= 0)"
            )
        self.residency_promote_heat = float(residency_promote_heat)
        self.residency_demote_heat = float(residency_demote_heat)
        if self.residency_demote_heat < 0:
            raise ValueError(
                f"invalid residency-demote-heat {residency_demote_heat!r} "
                "(want >= 0)"
            )
        if self.residency_promote_heat <= self.residency_demote_heat:
            raise ValueError(
                f"residency-promote-heat {residency_promote_heat!r} must "
                f"exceed residency-demote-heat {residency_demote_heat!r} "
                "(the gap IS the hysteresis dead band)"
            )
        self.residency_host_tier_bytes = int(residency_host_tier_bytes)
        if self.residency_host_tier_bytes < 0:
            raise ValueError(
                "invalid residency-host-tier-bytes "
                f"{residency_host_tier_bytes!r} (want >= 0)"
            )
        # Autopilot placement plane (docs/OPERATIONS.md autopilot):
        # the kill switch is OFF by default — with it off no placement
        # overrides are ever minted and shard placement stays
        # byte-identical to the pure hash ring. heat-budget is a
        # multiple of the mean per-node heat (> 1; the planner acts on
        # nodes above it, and the gap between mean and budget IS the
        # hysteresis dead band); max-moves bounds one pass (further
        # shaped down by the repair pacer); min-dwell is the post-move
        # immunity window (0 = auto: two intervals).
        self.autopilot_enabled = _parse_bool(autopilot_enabled)
        self.autopilot_interval = float(autopilot_interval)
        if self.autopilot_interval <= 0:
            raise ValueError(
                f"invalid autopilot-interval {autopilot_interval!r} "
                "(want > 0; use autopilot-enabled=false to turn the "
                "planner off)"
            )
        self.autopilot_heat_budget = float(autopilot_heat_budget)
        if self.autopilot_heat_budget <= 1.0:
            raise ValueError(
                f"invalid autopilot-heat-budget {autopilot_heat_budget!r} "
                "(want > 1.0: the margin over mean node heat IS the "
                "hysteresis dead band)"
            )
        self.autopilot_max_moves = int(autopilot_max_moves)
        if self.autopilot_max_moves < 1:
            raise ValueError(
                f"invalid autopilot-max-moves {autopilot_max_moves!r} "
                "(want >= 1)"
            )
        self.autopilot_min_dwell = float(autopilot_min_dwell)
        if self.autopilot_min_dwell < 0:
            raise ValueError(
                f"invalid autopilot-min-dwell {autopilot_min_dwell!r} "
                "(want >= 0; 0 = two intervals)"
            )
        # Elastic sub-shard split/merge (docs/OPERATIONS.md elastic
        # operations): a shard hotter than split-threshold x mean node
        # load is split into split-ways column ranges spread across
        # nodes; 0 disables the splitter (whole-shard placement only).
        self.autopilot_split_threshold = float(autopilot_split_threshold)
        if self.autopilot_split_threshold < 0:
            raise ValueError(
                f"invalid autopilot-split-threshold "
                f"{autopilot_split_threshold!r} (want >= 0; 0 disables "
                "sub-shard splits)"
            )
        self.autopilot_split_ways = int(autopilot_split_ways)
        if self.autopilot_split_ways < 2:
            raise ValueError(
                f"invalid autopilot-split-ways {autopilot_split_ways!r} "
                "(want >= 2: a split needs at least two ranges)"
            )
        # CDC backbone (docs/OPERATIONS.md Replication & CDC):
        # cdc-enabled runs the peer tailer that makes cluster-edge
        # result caching safe; cdc-max-retention-bytes bounds how much
        # WAL history consumer cursors may pin against segment GC
        # (beyond the budget, reclaim wins and the lagging consumer
        # gets 410 + restart-from-snapshot); cdc-follow points a
        # non-member read replica at an upstream node's URI;
        # cdc-staleness-budget is the follower's declared read-lag
        # bound (X-Pilosa-Max-Staleness can only tighten it, 0 = no
        # bound).
        self.cdc_enabled = _parse_bool(cdc_enabled)
        self.cdc_max_retention_bytes = int(cdc_max_retention_bytes)
        if self.cdc_max_retention_bytes < 0:
            raise ValueError(
                f"invalid cdc-max-retention-bytes "
                f"{cdc_max_retention_bytes!r} (want >= 0)"
            )
        self.cdc_poll_interval = float(cdc_poll_interval)
        if self.cdc_poll_interval <= 0:
            raise ValueError(
                f"invalid cdc-poll-interval {cdc_poll_interval!r} "
                "(want > 0)"
            )
        self.cdc_max_batch_bytes = int(cdc_max_batch_bytes)
        if self.cdc_max_batch_bytes <= 0:
            raise ValueError(
                f"invalid cdc-max-batch-bytes {cdc_max_batch_bytes!r} "
                "(want > 0)"
            )
        self.cdc_follow = str(cdc_follow or "")
        self.cdc_staleness_budget = float(cdc_staleness_budget)
        if self.cdc_staleness_budget < 0:
            raise ValueError(
                f"invalid cdc-staleness-budget {cdc_staleness_budget!r} "
                "(want >= 0; 0 = unbounded)"
            )
        from pilosa_tpu.qos.slo import SLOEngine

        # build once to validate; Server.open builds the live engine
        SLOEngine.from_config(self.slo_objectives, self.slo_windows)

    @property
    def tls_enabled(self) -> bool:
        return bool(self.tls_certificate and self.tls_key)

    @classmethod
    def from_dict(cls, d: dict) -> "ServerConfig":
        # Accept snake_case for EVERY knob by normalizing up front —
        # the per-field d.get("kebab", d.get("snake", ...)) fallbacks
        # below predate this and had drifted (several newer knobs only
        # answered to kebab); the knob-parity contract test now pins
        # the whole surface (tests/test_config_parity.py).
        d = dict(d)
        for k in list(d):
            if isinstance(k, str) and "_" in k:
                d.setdefault(k.replace("_", "-"), d[k])
        tls = d.get("tls") if isinstance(d.get("tls"), dict) else {}
        return cls(
            data_dir=d.get("data-dir", d.get("data_dir", "~/.pilosa_tpu")),
            bind=d.get("bind", "localhost"),
            port=int(d.get("port", 10101)),
            anti_entropy_interval=float(
                d.get("anti-entropy-interval", d.get("anti_entropy_interval", 600.0))
            ),
            replica_n=int(d.get("replica-n", d.get("replica_n", 1))),
            verbose=_parse_bool(d.get("verbose", False)),
            name=d.get("name", ""),
            advertise=d.get("advertise", ""),
            seeds=_parse_list(d.get("seeds", d.get("gossip-seeds", []))),
            heartbeat_interval=float(d.get("heartbeat-interval", 5.0)),
            heartbeat_timeout=_parse_duration(
                d.get("heartbeat-timeout", d.get("heartbeat_timeout", 2.0))
            ),
            trace_sample_rate=float(
                d.get("trace-sample-rate", d.get("trace_sample_rate", 0.0))
            ),
            trace_log_dir=d.get("trace-log-dir",
                                d.get("trace_log_dir", "")),
            diagnostics_endpoint=d.get("diagnostics-endpoint", ""),
            statsd=d.get("statsd", ""),
            long_query_time=_parse_duration(
                d.get("long-query-time", d.get("long_query_time", 0.0))
            ),
            max_writes_per_request=int(
                d.get("max-writes-per-request",
                      d.get("max_writes_per_request", 5000))
            ),
            ingest_workers=int(
                d.get("ingest-workers", d.get("ingest_workers", 1))
            ),
            tls_certificate=d.get("tls-certificate", tls.get("certificate", "")),
            tls_key=d.get("tls-key", tls.get("key", "")),
            tls_skip_verify=_parse_bool(
                d.get("tls-skip-verify", tls.get("skip-verify", False))
            ),
            device_budget_bytes=(
                int(d["device-budget-bytes"])
                if d.get("device-budget-bytes") not in (None, "") else None
            ),
            use_mesh=(
                _parse_bool(d["use-mesh"])
                if d.get("use-mesh") not in (None, "") else None
            ),
            qos_max_inflight=int(d.get("qos-max-inflight", 0)),
            qos_tenant_inflight=int(d.get("qos-tenant-inflight", 0)),
            qos_default_deadline=_parse_duration(
                d.get("qos-default-deadline", 0.0)
            ),
            qos_hedge_delay=_parse_duration(d.get("qos-hedge-delay", 0.25)),
            qos_hedge_budget=float(d.get("qos-hedge-budget", 0.05)),
            qos_breaker_threshold=int(d.get("qos-breaker-threshold", 5)),
            qos_breaker_cooldown=_parse_duration(
                d.get("qos-breaker-cooldown", 5.0)
            ),
            client_pool_size=int(
                d.get("client-pool-size", d.get("client_pool_size", 8))
            ),
            remote_batch=_parse_bool(d.get("remote-batch", True)),
            sync_workers=int(
                d.get("sync-workers", d.get("sync_workers", 8))
            ),
            repair_max_bytes_per_sec=int(
                d.get("repair-max-bytes-per-sec",
                      d.get("repair_max_bytes_per_sec", 0))
            ),
            repair_max_inflight=int(
                d.get("repair-max-inflight",
                      d.get("repair_max_inflight", 0))
            ),
            repair_compression=_parse_bool(
                d.get("repair-compression",
                      d.get("repair_compression", True))
            ),
            durability_mode=str(
                d.get("durability-mode", d.get("durability_mode", "group"))
            ),
            group_commit_max_ms=float(
                d.get("group-commit-max-ms",
                      d.get("group_commit_max_ms", 2.0))
            ),
            group_commit_max_ops=int(
                d.get("group-commit-max-ops",
                      d.get("group_commit_max_ops", 256))
            ),
            slow_query_ring=int(
                d.get("slow-query-ring", d.get("slow_query_ring", 100))
            ),
            heat_half_life=_parse_duration(
                d.get("heat-half-life", d.get("heat_half_life", 300.0))
            ),
            slo_objectives=_parse_list(
                d.get("slo-objectives", d.get("slo_objectives", []))
            ),
            slo_windows=_parse_list(
                d.get("slo-windows", d.get("slo_windows", []))
            ),
            verify_on_load=_parse_bool(
                d.get("verify-on-load", d.get("verify_on_load", True))
            ),
            scrub_interval=_parse_duration(
                d.get("scrub-interval", d.get("scrub_interval", 0.0))
            ),
            scrub_max_bytes_per_sec=int(
                d.get("scrub-max-bytes-per-sec",
                      d.get("scrub_max_bytes_per_sec", 0))
            ),
            serving_workers=int(
                d.get("serving-workers", d.get("serving_workers", 0))
            ),
            ring_slots=int(
                d.get("ring-slots", d.get("ring_slots", 1024))
            ),
            ring_slot_bytes=int(
                d.get("ring-slot-bytes", d.get("ring_slot_bytes", 65536))
            ),
            result_cache_bytes=int(
                d.get("result-cache-bytes", d.get("result_cache_bytes", 0))
            ),
            residency_promote_interval=_parse_duration(
                d.get("residency-promote-interval",
                      d.get("residency_promote_interval", 0.0))
            ),
            residency_promote_heat=float(
                d.get("residency-promote-heat",
                      d.get("residency_promote_heat", 4.0))
            ),
            residency_demote_heat=float(
                d.get("residency-demote-heat",
                      d.get("residency_demote_heat", 1.0))
            ),
            residency_host_tier_bytes=int(
                d.get("residency-host-tier-bytes",
                      d.get("residency_host_tier_bytes", 1 << 30))
            ),
            autopilot_enabled=_parse_bool(
                d.get("autopilot-enabled", False)
            ),
            autopilot_interval=_parse_duration(
                d.get("autopilot-interval", 30.0)
            ),
            autopilot_heat_budget=float(
                d.get("autopilot-heat-budget", 1.5)
            ),
            autopilot_max_moves=int(
                d.get("autopilot-max-moves", 4)
            ),
            autopilot_min_dwell=_parse_duration(
                d.get("autopilot-min-dwell", 0.0)
            ),
            autopilot_split_threshold=float(
                d.get("autopilot-split-threshold",
                      d.get("autopilot_split_threshold", 0.0))
            ),
            autopilot_split_ways=int(
                d.get("autopilot-split-ways",
                      d.get("autopilot_split_ways", 2))
            ),
            cdc_enabled=_parse_bool(d.get("cdc-enabled", False)),
            cdc_max_retention_bytes=int(
                d.get("cdc-max-retention-bytes", 64 << 20)
            ),
            cdc_poll_interval=_parse_duration(
                d.get("cdc-poll-interval", 0.05)
            ),
            cdc_max_batch_bytes=int(
                d.get("cdc-max-batch-bytes", 1 << 20)
            ),
            cdc_follow=d.get("cdc-follow", ""),
            cdc_staleness_budget=_parse_duration(
                d.get("cdc-staleness-budget", 1.0)
            ),
        )

    def to_dict(self) -> dict:
        return {
            "data-dir": self.data_dir,
            "bind": self.bind,
            "port": self.port,
            "anti-entropy-interval": self.anti_entropy_interval,
            "replica-n": self.replica_n,
            "verbose": self.verbose,
            "name": self.name,
            "advertise": self.advertise,
            "seeds": self.seeds,
            "heartbeat-interval": self.heartbeat_interval,
            "heartbeat-timeout": self.heartbeat_timeout,
            "trace-sample-rate": self.trace_sample_rate,
            "trace-log-dir": self.trace_log_dir,
            "diagnostics-endpoint": self.diagnostics_endpoint,
            "statsd": self.statsd,
            "long-query-time": self.long_query_time,
            "max-writes-per-request": self.max_writes_per_request,
            "ingest-workers": self.ingest_workers,
            "tls-certificate": self.tls_certificate,
            "tls-key": self.tls_key,
            "tls-skip-verify": self.tls_skip_verify,
            "device-budget-bytes": self.device_budget_bytes,
            "use-mesh": self.use_mesh,
            "qos-max-inflight": self.qos_max_inflight,
            "qos-tenant-inflight": self.qos_tenant_inflight,
            "qos-default-deadline": self.qos_default_deadline,
            "qos-hedge-delay": self.qos_hedge_delay,
            "qos-hedge-budget": self.qos_hedge_budget,
            "qos-breaker-threshold": self.qos_breaker_threshold,
            "qos-breaker-cooldown": self.qos_breaker_cooldown,
            "client-pool-size": self.client_pool_size,
            "remote-batch": self.remote_batch,
            "sync-workers": self.sync_workers,
            "repair-max-bytes-per-sec": self.repair_max_bytes_per_sec,
            "repair-max-inflight": self.repair_max_inflight,
            "repair-compression": self.repair_compression,
            "durability-mode": self.durability_mode,
            "group-commit-max-ms": self.group_commit_max_ms,
            "group-commit-max-ops": self.group_commit_max_ops,
            "slow-query-ring": self.slow_query_ring,
            "heat-half-life": self.heat_half_life,
            "slo-objectives": self.slo_objectives,
            "slo-windows": self.slo_windows,
            "verify-on-load": self.verify_on_load,
            "scrub-interval": self.scrub_interval,
            "scrub-max-bytes-per-sec": self.scrub_max_bytes_per_sec,
            "serving-workers": self.serving_workers,
            "ring-slots": self.ring_slots,
            "ring-slot-bytes": self.ring_slot_bytes,
            "result-cache-bytes": self.result_cache_bytes,
            "residency-promote-interval": self.residency_promote_interval,
            "residency-promote-heat": self.residency_promote_heat,
            "residency-demote-heat": self.residency_demote_heat,
            "residency-host-tier-bytes": self.residency_host_tier_bytes,
            "autopilot-enabled": self.autopilot_enabled,
            "autopilot-interval": self.autopilot_interval,
            "autopilot-heat-budget": self.autopilot_heat_budget,
            "autopilot-max-moves": self.autopilot_max_moves,
            "autopilot-min-dwell": self.autopilot_min_dwell,
            "autopilot-split-threshold": self.autopilot_split_threshold,
            "autopilot-split-ways": self.autopilot_split_ways,
            "cdc-enabled": self.cdc_enabled,
            "cdc-max-retention-bytes": self.cdc_max_retention_bytes,
            "cdc-poll-interval": self.cdc_poll_interval,
            "cdc-max-batch-bytes": self.cdc_max_batch_bytes,
            "cdc-follow": self.cdc_follow,
            "cdc-staleness-budget": self.cdc_staleness_budget,
        }


def _parse_duration(value) -> float:
    """Seconds from a float or a Go-style duration string ('1m30s',
    '500ms' — the reference's TOML uses Go durations). One shared
    grammar for every knob (utils/durations.py; the SLO spec parser
    uses the same one)."""
    from pilosa_tpu.utils.durations import parse_duration

    return parse_duration(value)


def _parse_bool(value) -> bool:
    """TOML gives real bools; env vars give strings ('false', '0', ...)."""
    if isinstance(value, str):
        return value.strip().lower() in ("1", "true", "t", "yes", "on")
    return bool(value)


def _parse_list(value) -> list[str]:
    if isinstance(value, str):
        return [v.strip() for v in value.split(",") if v.strip()]
    return list(value)


class Server:
    def __init__(self, config: ServerConfig | None = None):
        self.config = config or ServerConfig()
        self.logger = new_standard_logger(verbose=self.config.verbose)
        self.holder = Holder(
            self.config.data_dir,
            durability_mode=self.config.durability_mode,
            group_commit_max_ms=self.config.group_commit_max_ms,
            group_commit_max_ops=self.config.group_commit_max_ops,
            verify_on_load=self.config.verify_on_load,
        )
        self.api = API(self.holder)
        self._http = None
        self._http_thread = None
        self._mpserve = None  # OwnerRuntime when serving-workers > 0
        self._anti_entropy_timer: threading.Timer | None = None
        self._heartbeat_timer: threading.Timer | None = None
        self._closed = threading.Event()

    @property
    def port(self) -> int:
        """The PUBLIC serving port: the SO_REUSEPORT workers' port in
        multi-process mode (the owner's full server moves to loopback),
        the single HTTP listener's otherwise."""
        if self._mpserve is not None:
            return self._mpserve.port
        return self._http.server_address[1] if self._http else self.config.port

    def open(self) -> "Server":
        from pilosa_tpu.storage import residency

        if self.config.device_budget_bytes:
            residency.set_global_row_cache(
                residency.DeviceRowCache(
                    self.config.device_budget_bytes,
                    host_budget_bytes=self.config
                    .residency_host_tier_bytes,
                )
            )
        else:
            # unset or 0: the budget follows the chips this server holds
            cache = residency.global_row_cache()
            cache.budget_bytes = residency.default_budget_bytes()
            cache.host_budget_bytes = self.config.residency_host_tier_bytes
        # write-invalidated result cache (serving/rescache.py): the
        # process global — fragment write hooks invalidate through it —
        # sized here; 0 keeps it disabled (and clears leftovers from a
        # previous in-process server)
        from pilosa_tpu.serving.rescache import global_result_cache

        global_result_cache().configure(
            self.config.result_cache_bytes,
            half_life_s=self.config.heat_half_life,
        )
        self.holder.open()
        self.api.long_query_time = self.config.long_query_time
        # slow-query ring capacity (slow-query-ring knob): replace the
        # default deque so /debug/queries/slow keeps as many offenders
        # as the operator asked for
        import collections as _collections

        self.api.long_queries = _collections.deque(
            maxlen=self.config.slow_query_ring
        )
        from pilosa_tpu.qos.slo import SLOEngine
        from pilosa_tpu.storage.heat import global_heat

        self.api.slo = SLOEngine.from_config(
            self.config.slo_objectives, self.config.slo_windows
        )
        global_heat().half_life_s = self.config.heat_half_life
        self.api.max_writes_per_request = self.config.max_writes_per_request
        self.api.ingest_workers = max(1, self.config.ingest_workers)
        self.api.logger = self.logger
        if self.config.statsd:
            # statsd sink must be wired BEFORE anything captures the
            # global stats client (ServingQos below) — a late swap would
            # leave qos counting sheds into the discarded default client
            from pilosa_tpu.utils.stats import StatsdStatsClient, set_global_stats

            host, _, port = self.config.statsd.partition(":")
            set_global_stats(
                StatsdStatsClient(host or "127.0.0.1", int(port or 8125))
            )
        from pilosa_tpu.qos import ServingQos
        from pilosa_tpu.utils.stats import global_stats

        self.api.qos = ServingQos(
            max_inflight=self.config.qos_max_inflight,
            tenant_max=self.config.qos_tenant_inflight,
            hedge_delay=self.config.qos_hedge_delay,
            hedge_budget=self.config.qos_hedge_budget,
            breaker_threshold=self.config.qos_breaker_threshold,
            breaker_cooldown=self.config.qos_breaker_cooldown,
            stats=global_stats(),
        )
        self.api.default_deadline_s = self.config.qos_default_deadline
        # Multi-process serving (docs/OPERATIONS.md deployment shapes):
        # with serving-workers > 0 the public port belongs to the
        # SO_REUSEPORT worker processes and THIS process — the device
        # owner — keeps its full HTTP surface on loopback (workers
        # proxy every non-hot route to it). Platforms that can't run
        # the shape fall back to single-process with a warning instead
        # of failing startup.
        mp_workers = 0
        if self.config.serving_workers > 0:
            from pilosa_tpu.serving.mpserve import mp_unsupported_reason

            reason = mp_unsupported_reason(self.config)
            if reason is None:
                mp_workers = self.config.serving_workers
            else:
                self.logger.warning(
                    "multi-process serving disabled: %s "
                    "(falling back to single-process mode)", reason,
                )
        if mp_workers:
            self._http = make_http_server(self.api, "127.0.0.1", 0)
        else:
            self._http = make_http_server(self.api, self.config.bind,
                                          self.config.port)
        if self.config.tls_enabled:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.config.tls_certificate, self.config.tls_key)
            # Wrap per-connection with the handshake deferred: accept() stays
            # cheap in the single accept loop; the handshake runs on first
            # read inside that connection's handler thread, so a stalled
            # client can't block other connections.
            plain_get_request = self._http.get_request

            def tls_get_request():
                conn, addr = plain_get_request()
                conn = ctx.wrap_socket(
                    conn, server_side=True, do_handshake_on_connect=False
                )
                return conn, addr

            self._http.get_request = tls_get_request
        self._http_thread = threading.Thread(
            target=self._http.serve_forever, daemon=True
        )
        self._http_thread.start()
        # tracer BEFORE the serving workers: each worker copies the
        # sample rate out of the handshake cfg, which reads the live
        # global tracer
        from pilosa_tpu.utils.tracing import (
            global_tracer,
            install_compile_listener,
        )

        global_tracer().sample_rate = self.config.trace_sample_rate
        # count compiles from the first query on (device_compiles_total)
        install_compile_listener()
        if mp_workers:
            from pilosa_tpu.serving.mpserve import OwnerRuntime

            self._mpserve = OwnerRuntime(self).start()
            self.api.mpserve = self._mpserve
        self._wire_cluster()
        # CDC backbone (docs/OPERATIONS.md Replication & CDC): the
        # retention budget applies whenever the grouped WAL exists (a
        # registered cursor may pin covered segments up to it); the
        # peer tailer runs only with cdc-enabled, a follower mirror
        # only with cdc-follow. Both ride the cluster's internal
        # client, so feed transfers share the RepairPacer + deflate
        # posture with the sync data plane.
        wal = getattr(self.holder, "wal", None)
        if wal is not None:
            wal.cdc_retention_bytes = self.config.cdc_max_retention_bytes
        self.api.cdc_staleness_budget_s = self.config.cdc_staleness_budget
        if self.config.cdc_enabled:
            from pilosa_tpu.cdc.tailer import CdcTailer

            self.api.cdc = CdcTailer(
                self.api, self.api.cluster.client,
                poll_interval=self.config.cdc_poll_interval,
                max_batch_bytes=self.config.cdc_max_batch_bytes,
                cursor_name=f"tailer:{self.api.cluster.local.id}",
                logger=self.logger,
            )
            self.api.cdc.start()
        if self.config.cdc_follow:
            from pilosa_tpu.cdc.tailer import CdcFollower

            self.api.follower = CdcFollower(
                self.api, self.api.cluster.client,
                self.config.cdc_follow,
                poll_interval=self.config.cdc_poll_interval,
                max_batch_bytes=self.config.cdc_max_batch_bytes,
                cursor_name=f"follower:{self.api.cluster.local.id}",
                logger=self.logger,
            )
            self.api.follower.start()
        # Elastic membership plane (docs/OPERATIONS.md elastic
        # operations): wired on every node — not just when autopilot is
        # on — so whichever node is the acting coordinator can drive a
        # drain, and can resume one adopted from a failed coordinator.
        from pilosa_tpu.autopilot.elastic import ElasticManager

        self.api.elastic = ElasticManager(
            self.api.cluster, logger=self.logger
        )
        if self.config.residency_promote_interval > 0:
            from pilosa_tpu.storage.heat import global_heat as _gh
            from pilosa_tpu.storage.residency import (
                global_row_cache as _grc,
            )
            from pilosa_tpu.storage.tiering import ResidencyTierer

            # promotion uploads share the node's RepairPacer: tiering
            # competes with repair for the same host<->device and wire
            # budgets, and must never starve serving of either
            self.api.tierer = ResidencyTierer(
                cache=_grc(), heat=_gh(),
                interval_s=self.config.residency_promote_interval,
                promote_heat=self.config.residency_promote_heat,
                demote_heat=self.config.residency_demote_heat,
                pacer=self.api.cluster.client.pacer,
                logger=self.logger,
            ).start()
        if self.config.autopilot_enabled:
            from pilosa_tpu.autopilot import Autopilot
            from pilosa_tpu.storage.heat import global_heat as _ap_heat

            # rebalance transfers ride the SAME RepairPacer as repair
            # and tiering: the autopilot's moves are maintenance traffic
            # and must never starve serving of wire or device budget
            self.api.autopilot = Autopilot(
                self.api.cluster, heat=_ap_heat(), slo=self.api.slo,
                interval_s=self.config.autopilot_interval,
                heat_budget=self.config.autopilot_heat_budget,
                max_moves=self.config.autopilot_max_moves,
                min_dwell_s=self.config.autopilot_min_dwell or None,
                split_threshold=self.config.autopilot_split_threshold,
                split_ways=self.config.autopilot_split_ways,
                pacer=self.api.cluster.client.pacer,
                logger=self.logger,
            ).start()
        import jax

        devices = jax.devices()
        self.logger.info(
            "listening on %s://%s:%d (data-dir %s, node %s, devices %d x "
            "%s %s)",
            "https" if self.config.tls_enabled else "http",
            self.config.bind, self.port, self.holder.data_dir,
            self.api.cluster.local.id, len(devices), devices[0].platform,
            devices[0].device_kind,
        )
        self.api.trace_log_dir = self.config.trace_log_dir
        from pilosa_tpu.utils.diagnostics import DiagnosticsCollector

        self._diagnostics = DiagnosticsCollector(
            self.api, self.config.diagnostics_endpoint
        )
        self._diagnostics.start()
        if self.config.scrub_interval > 0:
            from pilosa_tpu.parallel.scrub import Scrubber
            from pilosa_tpu.utils.stats import global_stats as _gs

            self.api.scrubber = Scrubber(
                self.holder, cluster=self.api.cluster,
                interval_s=self.config.scrub_interval,
                max_bytes_per_sec=self.config.scrub_max_bytes_per_sec,
                stats=_gs(), logger=self.logger,
            ).start()
        self._schedule_anti_entropy()
        self._schedule_heartbeat()
        return self

    def _wire_cluster(self) -> None:
        """Build the cluster + executor stack: local mesh executor wrapped
        by the cluster router (reference server.go composition)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.parallel.cluster import Cluster, Node
        from pilosa_tpu.parallel.cluster_exec import ClusterExecutor

        name = self.config.name or f"node-{self.port}"
        scheme = "https" if self.config.tls_enabled else "http"
        uri = self.config.advertise or f"{scheme}://{self.config.bind}:{self.port}"
        cluster = Cluster(
            Node(name, uri), replica_n=self.config.replica_n, holder=self.holder,
            insecure_tls=self.config.tls_skip_verify,
            pool_size=self.config.client_pool_size,
        )
        cluster.api = self.api
        cluster.logger = self.logger
        cluster.sync_workers = max(1, self.config.sync_workers)
        cluster.heartbeat_timeout = self.config.heartbeat_timeout
        # fault-injection identity (testing/faults.py): label outbound
        # traffic with this node's name and register the name→endpoint
        # mapping when a plane is installed, so partition rules written
        # against node names match this node's wire both ways
        from pilosa_tpu.testing import faults as _faults

        cluster.client.pool.fault_source = name
        _plane = _faults.active()
        if _plane is not None:
            # register the ADVERTISED endpoint — the hostname:port
            # peers dial (and the connpool keys traffic by) — not the
            # bind address, which differs under advertise= or wildcard
            # binds and would make name-addressed rules miss this node
            _plane.name_endpoint(name, uri.split("://", 1)[-1])
        # repair/resize data-plane shaping: one pacer per node's internal
        # client, shared by every transfer path (manifest deltas,
        # per-block fallbacks, whole-fragment resize fetches)
        from pilosa_tpu.parallel.pacer import RepairPacer
        from pilosa_tpu.utils.stats import global_stats as _stats

        cluster.client.pacer = RepairPacer(
            max_bytes_per_sec=self.config.repair_max_bytes_per_sec,
            max_inflight=self.config.repair_max_inflight,
            stats=_stats(),
        )
        cluster.client.compress_repair = self.config.repair_compression
        self.api.cluster = cluster

        use_mesh = self.config.use_mesh
        if use_mesh is None:
            import jax

            use_mesh = len(jax.devices()) > 1
        if use_mesh:
            from pilosa_tpu.parallel.dist import DistExecutor

            local = DistExecutor(self.holder)
        else:
            local = Executor(self.holder)
        self.api.executor = ClusterExecutor(
            local, cluster, qos=self.api.qos,
            remote_batch=self.config.remote_batch,
        )

        for seed in self.config.seeds:
            try:
                cluster.join(seed)
                break
            except Exception as e:
                self.logger.warning("join via %s failed: %s", seed, e)

    def close(self) -> None:
        self._closed.set()
        if self._mpserve is not None:
            # workers first: they proxy to the owner listener below, and
            # a worker outliving its owner would re-handshake into a
            # closing runtime
            self._mpserve.close()
            self._mpserve = None
            self.api.mpserve = None
        if self.api.scrubber is not None:
            self.api.scrubber.close()
        if self.api.autopilot is not None:
            self.api.autopilot.close()
            self.api.autopilot = None
        if self.api.elastic is not None:
            self.api.elastic.close()
            self.api.elastic = None
        if self.api.tierer is not None:
            self.api.tierer.close()
            self.api.tierer = None
        if self.api.cdc is not None:
            self.api.cdc.stop()
            self.api.cdc = None
        if self.api.follower is not None:
            self.api.follower.stop()
            self.api.follower = None
        if self._anti_entropy_timer is not None:
            self._anti_entropy_timer.cancel()
        if self._heartbeat_timer is not None:
            self._heartbeat_timer.cancel()
        if getattr(self, "_diagnostics", None) is not None:
            self._diagnostics.close()
        if self._http:
            self._http.shutdown()
            self._http.server_close()
        cluster = getattr(self.api, "cluster", None)
        if cluster is not None:
            pool = getattr(cluster.client, "pool", None)
            if pool is not None:
                pool.close()  # drop idle keep-alive connections to peers
        self.holder.close()

    def _schedule_anti_entropy(self) -> None:
        interval = self.config.anti_entropy_interval
        if interval <= 0:
            return

        def tick():
            if self._closed.is_set():
                return
            try:
                self.run_anti_entropy()
            except Exception as e:  # ticker must not die
                self.logger.warning("anti-entropy failed: %s", e)
            self._schedule_anti_entropy()

        timer = threading.Timer(interval, tick)
        timer.daemon = True
        timer.start()
        self._anti_entropy_timer = timer

    def _schedule_heartbeat(self) -> None:
        interval = self.config.heartbeat_interval
        if interval <= 0:
            return

        def tick():
            if self._closed.is_set():
                return
            try:
                if self.api.cluster is not None and len(self.api.cluster.nodes) > 1:
                    self.api.cluster.heartbeat()
                    # drain resumption rides the heartbeat tick: if this
                    # node became acting coordinator while a gossiped
                    # drain record is still active, pick up the state
                    # machine where the dead coordinator left it
                    if self.api.elastic is not None:
                        self.api.elastic.maybe_resume()
            except Exception as e:
                self.logger.warning("heartbeat failed: %s", e)
            self._schedule_heartbeat()

        timer = threading.Timer(interval, tick)
        timer.daemon = True
        timer.start()
        self._heartbeat_timer = timer

    def run_anti_entropy(self) -> None:
        """Replica repair pass (reference monitorAntiEntropy →
        HolderSyncer.SyncHolder — SURVEY.md §3.5). With no cluster peers
        configured this is a no-op."""
        if self.api.cluster is not None:
            self.api.cluster.sync_holder()
