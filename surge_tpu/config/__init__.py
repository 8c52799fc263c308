"""Config system — typed accessors over layered key/value config with env overrides.

Equivalent of the reference's Typesafe-Config (HOCON) ``reference.conf`` stack
(modules/common/src/main/resources/reference.conf, modules/command-engine/core/src/main/
resources/reference.conf) including the env-var-override-on-every-key pattern and the
typed accessor objects (surge/internal/config/{TimeoutConfig,RetryConfig,BackoffConfig}.scala).

Keys are dotted strings (``surge.producer.flush-interval-ms``). Resolution order:
explicit overrides > environment (``SURGE_PRODUCER_FLUSH_INTERVAL_MS``) > defaults.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Mapping


def _env_key(key: str) -> str:
    return key.upper().replace(".", "_").replace("-", "_")


#: Defaults mirroring the reference's reference.conf files (values in ms unless noted).
#: Citations: command-engine/core reference.conf:20-30 (flush interval, txn timeout,
#: ktable lag check), common reference.conf:15-21 (streams commit interval), :133-142
#: (aggregate init retries), :155-165 (ask timeout / passivation), :198-199 (restore
#: max poll records), :228-260 (health windows).
DEFAULTS: dict[str, Any] = {
    # --- log / producer (reference: surge.kafka.publisher.*) ---
    # group-commit flush triggers (the Kafka producer linger.ms /
    # batch.size analog): a batch commits when the FIRST pending publish has
    # lingered this long OR the batch hits max-records/max-bytes, whichever
    # comes first. An idle engine therefore commits a lone command in
    # ~linger time; a loaded engine fills batches.
    "surge.producer.linger-ms": 2,
    "surge.producer.batch-max-records": 512,
    "surge.producer.batch-max-bytes": 4 << 20,
    # bounded pipelining (max.in.flight.requests.per.connection analog):
    # how many publish transactions one partition lane may have in flight
    # concurrently. >1 requires a transport with pipelined commits (the gRPC
    # log client); exactly-once rests on the broker's per-producer txn_seq
    # dedup + in-order apply gate. In-process logs fall back to 1 (their
    # commit latency IS the group-commit pacing).
    "surge.producer.max-in-flight": 4,
    # backpressure: publishes past this many queued records await a slot
    # instead of growing the lane queue without bound under overload
    "surge.producer.pending-max-records": 16_384,
    # housekeeping tick: fenced-reinit retries, verbatim-retry pacing and
    # dedup-TTL purges run on this cadence (pre-group-commit it was the
    # fixed flush tick; the flush itself is event-driven now)
    "surge.producer.flush-interval-ms": 50,
    "surge.producer.slow-transaction-warning-ms": 1_000,
    "surge.producer.ktable-check-interval-ms": 500,
    "surge.producer.enable-transactions": True,
    # publish dedup window (the PublishTracker 60s TTL, KafkaProducerActorImpl.scala:580-608)
    "surge.producer.publish-dedup-ttl-ms": 60_000,
    # verbatim retries of an unknown-outcome batch before its waiters fail
    # over to the entity retry ladder
    "surge.producer.publish-retry-max": 8,
    # --- state store / ktable (reference: surge.kafka-streams.*) ---
    "surge.state-store.commit-interval-ms": 3_000,
    "surge.state-store.restore-max-poll-records": 500,
    "surge.state-store.wipe-state-on-start": False,
    "surge.state-store.backend": "memory",  # memory | native | rocks-like file store
    # warm standby copies of each partition's materialized state on other nodes
    # (Kafka Streams num.standby.replicas, common reference.conf:24-25): each
    # node also tails the partitions it is ring-standby for, so a rebalance
    # promotion needs no state re-read
    "surge.state-store.num-standby-replicas": 0,
    # --- aggregate actor (reference: surge.state-store-actor.*) ---
    "surge.aggregate.ask-timeout-ms": 30_000,
    "surge.aggregate.idle-passivation-ms": 30_000,
    "surge.aggregate.init-retry-interval-ms": 500,
    "surge.aggregate.init-fetch-retry-ms": 2_000,
    "surge.aggregate.init-max-attempts": 10,
    "surge.aggregate.publish-max-retries": 3,
    "surge.aggregate.publish-timeout-ms": 30_000,
    "surge.aggregate.passivation-buffer-limit": 1000,
    # --- serialization (core reference.conf:73-76) ---
    "surge.serialization.thread-pool-size": 32,
    # command-path fast path: event batches at most this long serialize
    # INLINE on the event loop instead of paying the thread-pool hop (~80us
    # per command) — big payloads still offload. 0 = always off-thread.
    "surge.serialization.inline-max-events": 4,
    # --- metrics ---
    # capture OpenMetrics exemplars (trace id per histogram bucket) on the
    # ENGINE registry; broker registries are always exemplar-on
    "surge.metrics.exemplars": False,
    # --- tracing: tail sampling + kept-trace rings (surge_tpu/tracing/tail) ---
    # buffer head-sampled spans per trace and KEEP a completed trace iff it
    # erred, breached tail.latency-ms, or landed in an SLO breach window —
    # under a bounded keep budget. Only matters when a tracer is wired
    # (tracer=None keeps every hop at zero cost as before).
    "surge.trace.tail.enabled": True,
    # a trace whose slowest span ran at least this is kept (the latency
    # breach criterion of the tail decision)
    "surge.trace.tail.latency-ms": 250,
    # keep budget: at most this many kept traces per budget window; eligible
    # traces past it are dropped and counted (surge.trace.dropped)
    "surge.trace.tail.keep-budget": 64,
    "surge.trace.tail.budget-window-ms": 10_000,
    # bound on spans buffered for in-flight traces; oldest traces evict past
    # it (leaked spans must not grow the buffer without bound)
    "surge.trace.tail.max-buffer-spans": 4096,
    # how long after an SLO breach every completing trace is kept (the
    # breach-adjacent anatomy evidence window)
    "surge.trace.tail.breach-window-ms": 30_000,
    # kept traces retained per engine/broker ring (DumpTraces RPC source)
    "surge.trace.ring-capacity": 256,
    # --- fleet telemetry plane (observability/federation.py + slo.py) ---
    # per-target fetch timeout of one federation pass (HTTP scrape or
    # GetMetricsText RPC); a slower target answers up{instance}=0 and keeps
    # serving its last payload with a staleness stamp
    "surge.fleet.scrape-timeout-ms": 2_000,
    # multiwindow burn-rate alerting (Google-SRE style): a breach fires only
    # when BOTH the fast and the slow window burn over the threshold.
    # 14.4 = the classic 1h/5m page pair's rate (budget exhausted in ~2 days)
    "surge.slo.fast-window-ms": 300_000,
    "surge.slo.slow-window-ms": 3_600_000,
    "surge.slo.burn-threshold": 14.4,
    # --- replay engine (new: the TPU north star; BASELINE.json replayBackend=tpu) ---
    "surge.replay.backend": "tpu",  # tpu | cpu (scalar fold)
    "surge.replay.restore-on-start": False,  # engine cold start folds the events topic
    "surge.replay.batch-size": 8192,  # aggregates per device step
    # events scanned per lax.scan segment of a streamed window. For the
    # resident fold it is a cap, the widest tile a plan may take: the plan
    # chooses its width from the corpus's own lengths, this or a narrower
    # (128 for logs of 100 events: replay/engine.py:_tile_width)
    "surge.replay.time-chunk": 512,
    # tail windows shrink through a power-of-two ladder down to this width instead
    # of padding to a full time-chunk (pad_ratio lever; 0/neg disables the ladder)
    "surge.replay.min-time-window": 8,
    # resident-corpus replay: HBM budget for one dispatch's [batch, width] slab
    # (plus its transpose); bounds the scan width of long-log chunks
    "surge.replay.resident-slab-cap-mb": 512,
    # order aggregates by log length before B-chunking so each chunk's local max
    # length ≈ its members' lengths (columnar replay pad_ratio lever)
    "surge.replay.sort-by-length": True,
    "surge.replay.length-buckets": "64,256,1024,4096",
    "surge.replay.mesh-axes": "data",
    "surge.replay.donate-carry": True,
    # donate the resident plane's slab + ordinals through the refresh
    # scatter programs (ISSUE 18 leg c): the round overwrites the slab
    # in place instead of copying it (the round-10 19 ms vs 49 ms device
    # leg at 1M rows WAS the copy). Kill-switchable like donate-carry:
    # false restores copying dispatches (no read path ever sees a
    # deleted buffer either way — the plane republishes the handle per
    # window and the gather lane retries across a donation race)
    "surge.replay.donate-refresh": True,
    # the tile-loop backend ("auto" picks the scanless assoc tree fold for
    # models shipping AssociativeFold, off a CPU host)
    "surge.replay.tile-backend": "auto",  # auto | xla | assoc
    # overlap segment-stream uploads with replay dispatches in N segments
    # (0/1 = plain upload+replay)
    "surge.replay.upload-stream-segments": 0,
    # columnar-segment cold start: keep the whole wire corpus resident on
    # device ("resident") or stream per-window ("streaming"); mesh-sharded
    # restores always stream
    "surge.replay.segment-backend": "resident",  # resident | streaming
    # cache the packed wire tensors alongside the segment for re-replays
    "surge.replay.segment-wire-cache": True,
    # columnar-segment cold start: when set, rebuild_from_events streams this
    # segment (building it once from the topics if absent) instead of folding
    # per-event Python objects
    "surge.replay.segment-path": "",
    # append delta chunks/snapshots for post-build offsets on each segment
    # rebuild, so repeated cold starts never re-crawl the topics
    "surge.replay.segment-auto-extend": True,
    # bounded-memory restore_from_events: topics whose total record count
    # exceeds this never materialize as one dict of per-event Python objects —
    # the tpu backend streams through a throwaway columnar segment (spill
    # files + per-chunk encode), the cpu backend folds in key-hash-range
    # passes (the restore consumer max.poll.records role, common
    # reference.conf:198-199). 0 forces the bounded route (cpu passes are
    # capped at 64, trading per-pass memory, not O(N^2) rescans); negative
    # disables spilling entirely.
    "surge.replay.restore-spill-events": 1_000_000,
    # aggregates per chunk for the throwaway restore segment (peak host
    # memory of the bounded tpu path = one chunk's decoded events)
    "surge.replay.restore-chunk-aggregates": 65536,
    # --- device-resident materialized state plane (replay/resident_state.py) ---
    # keep the KTable-equivalent state RESIDENT on device after the cold-start
    # replay, fold committed batches into it incrementally, and answer
    # getState/projections from batched device gathers (ROADMAP item 2)
    "surge.replay.resident.enabled": False,
    # hot-set bound: aggregates resident in the device slab at once; the
    # overflow spills to a host-side dict at its exact fold point and
    # re-admits on its next event
    "surge.replay.resident.capacity": 65536,
    # staleness bound for plane-served reads: a read falls back to the host
    # KV store when its partition's fold watermark lags the committed log by
    # more than this many records (entity init always demands lag 0)
    "surge.replay.resident.max-lag-records": 4096,
    # refresh loop: records pulled per partition per fold round, and how long
    # an idle round waits on wait_for_append before re-polling
    "surge.replay.resident.refresh-max-poll-records": 4096,
    "surge.replay.resident.refresh-interval-ms": 50,
    # refresh feed fast path (ISSUE 12): decode each round's committed tail
    # with ONE batch deserialize (e.g. JsonEventFormatting.read_events_batch)
    # over the native record-index read views, instead of a json.loads +
    # object build per event. false = the per-event Python feed (the paired
    # bench arm; also the behavior when the model wires no batch decoder)
    "surge.replay.resident.native-feed": True,
    # device observatory (ISSUE 16): refresh rounds retained in the engine's
    # bounded replay ledger ring (per-round padding-waste / stage timings /
    # gather legs, dumped via the DumpReplayLedger admin RPC)
    "surge.replay.resident.ledger-capacity": 512,
    # refresh dispatch shape (ISSUE 18): "bucketed" deals each round's lanes
    # into pow2 length buckets and issues one fused program per OCCUPIED
    # bucket (pay for occupied slots, with the compile-signature set bounded
    # by the layout's bucket table); "dense" restores the single
    # [pow8(lanes), pow2(max_len)] rectangle per window (the round-9
    # ~9x over-dispatch arm, kept as the paired-bench baseline)
    "surge.replay.resident.refresh-dispatch": "bucketed",  # bucketed | dense
    # --- mesh-native resident plane (surge_tpu.replay.plane_mesh) ---
    # how a mesh-backed plane resolves reads/folds against its sharded slab:
    # "local" (default) shards the slab [n_dev, rows] and answers each
    # batched read with device-local gathers + ONE cross-device collective,
    # with refresh rounds dealing lanes to their owning shard (one sharded
    # h2d, zero d2h, 1/n_dev fold work per device); "replicated" keeps the
    # legacy plain-jit programs whose gathers replicate the slab every read
    # (the paired-bench baseline arm and the rollback switch)
    "surge.replay.mesh.gather": "local",  # local | replicated
    # --- incremental materialized views + changefeeds (replay/views.py) ---
    # per-view delta ring depth: how many fold rounds a changefeed resume
    # watermark may lag before SubscribeView answers with a one-shot
    # reconciling snapshot instead of replaying the missed deltas
    "surge.replay.views.changefeed-rounds": 256,
    # group cap of one materialized view (distinct aggregate ids or group-by
    # keys); a view that overflows degrades to an error state rather than
    # growing its slab unbounded
    "surge.replay.views.max-groups": 1_048_576,
    # --- TPU scan engine over columnar segments (surge_tpu.replay.query) ---
    # event-axis pad bucket of one scan dispatch: chunks pad up to
    # power-of-two buckets at least this large so streamed chunks reuse a
    # handful of compiled scan programs
    "surge.query.chunk-events": 65536,
    # shard the scan's event axis over the engine's mesh (one psum/pmin/pmax
    # collective per output column); false scans single-device even when a
    # mesh is present
    "surge.query.mesh": True,
    # row cap of one QueryStates/ScanSegments RPC reply (the full columns are
    # available in-process through SurgeEngine.query)
    "surge.query.max-rows": 10_000,
    # --- state checkpoints (surge_tpu.store.checkpoint; compaction.md) ---
    # directory for atomic checkpoint files ("" disables the writer); the
    # incremental writer materializes on interval + min-events cadence and
    # retains the newest `keep` checkpoints
    "surge.store.checkpoint.path": "",
    "surge.store.checkpoint.interval-ms": 30_000,
    "surge.store.checkpoint.min-events": 1,
    "surge.store.checkpoint.keep": 2,
    # --- broker-side log compaction (surge_tpu.log.compactor; compaction.md) ---
    # dirty-ratio scheduler: a pass runs when dirty/total >= min-dirty-ratio
    # AND dirty records >= min-dirty-records, checked every interval;
    # tombstones older than the retention are GC'd
    "surge.log.compaction.enabled": False,
    "surge.log.compaction.interval-ms": 30_000,
    "surge.log.compaction.min-dirty-ratio": 0.5,
    "surge.log.compaction.min-dirty-records": 64,
    "surge.log.compaction.tombstone-retention-ms": 60_000,
    # --- log broker replication (acks=all role, common reference.conf:112-124) ---
    # how long a commit waits for the follower ack before failing back to the
    # client (which retries the same txn_seq and re-joins the queued item)
    "surge.log.replication-ack-timeout-ms": 5_000,
    # min.insync.replicas analog (count INCLUDES the leader): a follower that
    # keeps failing for longer than the isr-timeout is dropped from the
    # in-sync set — commits then ack without it — as long as the set stays
    # >= min-insync. 1 (default) = availability over durability with RF=2
    # (a lone leader keeps accepting writes; the dead follower must catch_up
    # before it re-joins); 2 = strict acks=all (a dead follower blocks
    # commits until it returns, the pre-r5 behavior).
    "surge.log.replication-min-insync": 1,
    "surge.log.replication-isr-timeout-ms": 10_000,
    # rejoin under live traffic: an out-of-sync follower lagging by at most
    # this many records is re-synced BY THE LEADER (missing suffix pushed
    # through the ordered Replicate stream + dedup table) during its probe —
    # a one-shot operator catch_up can never converge while commits keep
    # landing. Beyond the cap (fresh/empty replicas) the follower stays out
    # until catch_up bulk-copies it. 0 disables auto-resync.
    "surge.log.replication-auto-resync-max-records": 10_000,
    # quorum acks: replicas (leader included) that must hold a commit before
    # it acks; 0 = every in-sync replica (strict acks=all). N < replicas
    # trades the straggler's ship timeout out of commit latency and gates
    # follower reads at the quorum-acked high-watermark.
    "surge.log.replication.min-insync-acks": 0,
    # pipelined transactions: how long the broker's in-order apply gate
    # waits for a missing predecessor txn_seq (a pipelined window arriving
    # out of order) before answering retriable — the client retries the
    # same seq, preserving exactly-once
    "surge.log.txn-inorder-timeout-ms": 3_000,
    # --- leader failover (KIP-101/KIP-279 epoch fencing; docs/operations.md) ---
    # a follower started with follower_of= may probe its leader and promote
    # itself once the prober declares it dead (probe-failures consecutive
    # failures at probe-interval). The declare threshold is the availability/
    # split-brain dial: promotion while the leader still serves forks the log.
    "surge.log.failover.auto-promote": False,
    "surge.log.failover.probe-interval-ms": 1_000,
    "surge.log.failover.probe-failures": 3,
    # a peer NEVER seen alive gets probe-failures x this grace before being
    # declared dead (a follower booting first must not promote over a leader
    # that is still starting; bounded so a truly absent leader still fails over)
    "surge.log.failover.bootstrap-grace-factor": 10,
    # --- quorum cluster (majority-vote promotion; docs/operations.md) ---
    # full symmetric cluster membership (comma-separated, the SAME list on
    # every broker); non-empty switches prober-declared leader death from
    # self-promotion to VoteLeader campaigns
    "surge.log.quorum.peers": "",
    "surge.log.quorum.vote-timeout-ms": 1_000,  # per-peer VoteLeader RPC
    "surge.log.quorum.vote-rounds": 5,  # campaign rounds before stand-down
    # --- cluster self-healing: membership, leadership spread, autobalancer ---
    # spread partition leadership round-robin across the membership as
    # topics are created (else: ClusterMeta op "spread" triggers it
    # explicitly); false keeps the PR-7 whole-broker leadership
    "surge.cluster.spread": False,
    # how long a member's ships must keep failing (past the ISR drop)
    # before the coordinator reassigns its led partitions to survivors
    "surge.cluster.reassign-grace-ms": 5_000,
    # autobalancer (surge_tpu/cluster/autobalancer.py): decision cadence,
    # the planned-move budget per window, per-partition move hysteresis,
    # the lead-count skew (max-min) that triggers a rebalance, and dry-run
    # (decide + flight-record, never move)
    "surge.cluster.balancer.interval-ms": 5_000,
    "surge.cluster.balancer.move-budget": 4,
    "surge.cluster.balancer.window-ms": 60_000,
    "surge.cluster.balancer.hysteresis-ms": 30_000,
    "surge.cluster.balancer.max-lead-skew": 1,
    "surge.cluster.balancer.dry-run": False,
    # --- flight recorder ---
    # directory the broker auto-dumps its flight ring to when the fault
    # plane hard-kills it ("" disables; live dumps via the DumpFlight RPC)
    "surge.log.flight.dump-dir": "",
    # --- FileLog WAL journal rotation ---
    # rotate commits.log (which embeds WAL payloads) once its durable bytes
    # exceed this: segments are fsynced first, then a frontier line opens the
    # fresh journal and os.replace GCs the old generation. 0 disables.
    "surge.log.journal-rotate-bytes": 64 << 20,
    # --- engine command lane (ISSUE 12: the de-asyncio'd fast path) ---
    # "direct": entity -> publisher handoff without per-command event-loop
    # machinery — pendings of one forming batch share a single BATCH-LEVEL
    # ack future (resolved once per group commit), a timed-out caller's
    # records stay queued and a same-request_id retry JOINS them (the
    # request-id dedup keeps exactly-once), and entities await publishes
    # through a bare timer wait instead of a wrapper task. "classic": the
    # PR-3 per-command future + cancel-withdraw machinery (the paired bench
    # arm, and the fallback if a workload depends on withdraw-on-timeout).
    "surge.producer.command-lane": "direct",
    # --- native broker hot path (csrc/txn.cc via log/native_gate) ---
    # operator kill-switch for the C++ batch path: Transact payload decode,
    # the in-order/dedup gate kernel, WAL journal formatting, the per-round
    # journal append, lazy segment materialization and the segment read
    # decoder. false (or an unbuilt csrc/) falls back to the bit-identical
    # pure-Python path everywhere.
    "surge.log.native.enabled": True,
    # --- fault-injection plane (surge_tpu.testing.faults) ---
    # a named plan (e.g. "flaky-network") or JSON rule list armed at broker/
    # FileLog construction; empty = no plane, hooks cost one attribute check.
    # Runtime arming: the broker's ArmFaults RPC (tools/chaos.py).
    "surge.log.faults.plan": "",
    "surge.log.faults.seed": 0,
    # --- health (common reference.conf:228-260) ---
    "surge.health.window-frequency-ms": 10_000,
    "surge.health.window-buffer-size": 10,
    "surge.health.signal-buffer-size": 25,
    "surge.health.supervisor-restart-max": 3,
    # --- event-loop starvation prober (execution-context-prober analog) ---
    "surge.event-loop-prober.enabled": True,
    "surge.event-loop-prober.interval-ms": 1_000,
    "surge.event-loop-prober.threshold-ms": 200,
    "surge.event-loop-prober.late-probes": 3,
    # --- feature flags (core reference.conf:64-71) ---
    "surge.feature-flags.experimental.enable-mesh-sharding": False,
    # alternative clustering backend (external shard allocation; the
    # enable-akka-cluster analog, core reference.conf:64-66)
    "surge.feature-flags.experimental.enable-cluster-sharding": False,
    "surge.feature-flags.experimental.disable-single-record-transactions": False,
    # --- control plane (cross-process membership/assignment service) ---
    "surge.control-plane.ping-interval-ms": 500,
    "surge.control-plane.member-timeout-ms": 3_000,
    # --- gRPC transport security (KafkaSecurityConfiguration analog) ---
    "surge.grpc.tls.enabled": False,
    "surge.grpc.tls.cert-file": "",
    "surge.grpc.tls.key-file": "",
    "surge.grpc.tls.root-ca-file": "",
    "surge.grpc.tls.require-client-auth": False,
    # --- engine ---
    "surge.engine.num-partitions": 8,
    "surge.engine.dr-standby-enabled": False,
    # engine-side flight-recorder ring size (events); the admin DumpFlight
    # RPC and BrokerStatus-style stats report occupancy + dropped count
    "surge.engine.flight-capacity": 1024,
    # --- saga / process-manager orchestration (surge_tpu.saga) ---
    # per-step dispatch deadline, forward retry budget and exponential
    # backoff base; compensations get their own (larger) budget because
    # exhausting it parks the saga in the dead letter. poll-interval paces
    # the driver's state re-reads; max-concurrent bounds simultaneous
    # participant dispatches across all drivers.
    "surge.saga.step-timeout-ms": 10_000,
    "surge.saga.step-max-attempts": 4,
    "surge.saga.step-backoff-ms": 100,
    "surge.saga.compensation-max-attempts": 6,
    "surge.saga.poll-interval-ms": 50,
    "surge.saga.max-concurrent": 512,
    # --- consistency observatory (observability/audit.py) ---
    # opt-in: the auditor is a supervised Controllable the engine only
    # starts when enabled. interval paces cycles; cohort-size bounds the
    # aggregates shadow-replayed per cycle; digest-enabled gates the
    # cross-replica digest compare; dedup-probe gates the exactly-once
    # replay probe (skipped automatically on transports without a seq gate)
    "surge.audit.enabled": False,
    "surge.audit.interval-ms": 2_000,
    "surge.audit.cohort-size": 8,
    "surge.audit.digest-enabled": True,
    "surge.audit.dedup-probe": True,
}


@dataclass
class Config:
    """Layered config: overrides > env > DEFAULTS."""

    overrides: dict[str, Any] = field(default_factory=dict)
    defaults: Mapping[str, Any] = field(default_factory=lambda: DEFAULTS)

    def get(self, key: str, fallback: Any = None) -> Any:
        if key in self.overrides:
            return self.overrides[key]
        env = os.environ.get(_env_key(key))
        if env is not None:
            return _coerce(env, self.defaults.get(key, fallback))
        if key in self.defaults:
            return self.defaults[key]
        return fallback

    def get_int(self, key: str, fallback: int = 0) -> int:
        return int(self.get(key, fallback))

    def get_float(self, key: str, fallback: float = 0.0) -> float:
        return float(self.get(key, fallback))

    def get_bool(self, key: str, fallback: bool = False) -> bool:
        v = self.get(key, fallback)
        if isinstance(v, str):
            return v.strip().lower() in ("1", "true", "yes", "on")
        return bool(v)

    def get_str(self, key: str, fallback: str = "") -> str:
        return str(self.get(key, fallback))

    def get_int_list(self, key: str, fallback: str = "") -> list[int]:
        raw = self.get_str(key, fallback)
        return [int(p) for p in raw.split(",") if p.strip()]

    def get_seconds(self, key: str, fallback_ms: int = 0) -> float:
        """Millisecond config value as seconds (asyncio sleeps take seconds)."""
        return self.get_int(key, fallback_ms) / 1000.0

    def with_overrides(self, overrides: Mapping[str, Any] | None = None, **kv: Any) -> "Config":
        """Layer overrides on top. Dotted keys go in ``overrides``; keyword args use
        underscore form (``surge_replay_time_chunk``) and are canonicalized against the
        known default keys (so they actually match what ``get`` reads)."""
        merged = dict(self.overrides)
        merged.update(overrides or {})
        canonical = {_env_key(k): k for k in self.defaults}
        for k, v in kv.items():
            merged[canonical.get(_env_key(k), k)] = v
        return Config(overrides=merged, defaults=self.defaults)


def _coerce(env_value: str, exemplar: Any) -> Any:
    """Coerce an env-var string to the type of the default it overrides."""
    if isinstance(exemplar, bool):
        return env_value.strip().lower() in ("1", "true", "yes", "on")
    if isinstance(exemplar, int):
        try:
            return int(env_value)
        except ValueError:
            return env_value
    if isinstance(exemplar, float):
        try:
            return float(env_value)
        except ValueError:
            return env_value
    return env_value


_DEFAULT = Config()


def default_config() -> Config:
    return _DEFAULT


# --- Typed accessor bundles (surge/internal/config/*.scala equivalents) ---


@dataclass(frozen=True)
class TimeoutConfig:
    """surge/internal/config/TimeoutConfig.scala equivalent."""

    ask_timeout_s: float
    publish_timeout_s: float

    @staticmethod
    def from_config(cfg: Config) -> "TimeoutConfig":
        return TimeoutConfig(
            ask_timeout_s=cfg.get_seconds("surge.aggregate.ask-timeout-ms"),
            publish_timeout_s=cfg.get_seconds("surge.aggregate.publish-timeout-ms"),
        )


@dataclass(frozen=True)
class RetryConfig:
    """surge/internal/config/RetryConfig.scala equivalent."""

    init_retry_interval_s: float
    init_fetch_retry_s: float
    init_max_attempts: int
    publish_max_retries: int

    @staticmethod
    def from_config(cfg: Config) -> "RetryConfig":
        return RetryConfig(
            init_retry_interval_s=cfg.get_seconds("surge.aggregate.init-retry-interval-ms"),
            init_fetch_retry_s=cfg.get_seconds("surge.aggregate.init-fetch-retry-ms"),
            init_max_attempts=cfg.get_int("surge.aggregate.init-max-attempts"),
            publish_max_retries=cfg.get_int("surge.aggregate.publish-max-retries"),
        )


@dataclass(frozen=True)
class BackoffConfig:
    """surge/internal/config/BackoffConfig.scala equivalent (BackoffSupervisor knobs)."""

    min_backoff_s: float = 0.1
    max_backoff_s: float = 10.0
    random_factor: float = 0.2
    max_retries: int = 3
