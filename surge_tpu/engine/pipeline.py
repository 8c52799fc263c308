"""SurgeEngine — the wired engine object (SurgeMessagePipeline equivalent).

Reference: modules/command-engine/core/src/main/scala/surge/internal/domain/
SurgeMessagePipeline.scala:33-240 — constructs and owns the partition tracker, the
state-store indexer (KTable), the per-partition regions (publisher + shard), and the
router; implements ``Controllable`` start/stop/restart with an engine-status atomic
(SurgeEngineStatus.scala) and exposes ``aggregate_for`` (scaladsl/command/
SurgeCommand.scala:24-70).

Startup order follows :3.1's call stack: state-store indexer first, then router; in
single-node mode (no external control plane) the engine self-assigns every partition,
the PartitionTracker broadcast creates all local regions, and each region's publisher
runs its init-transactions + lag-gate protocol before serving. The optional
events-topic bulk restore (``surge.replay.restore-on-start``) runs the TPU replay
engine BEFORE indexing starts and fast-forwards the store watermarks — the
``replayBackend = tpu`` north star wired into the engine's cold start."""

from __future__ import annotations

import asyncio
import time
from enum import Enum
from typing import Callable, Dict, List, Optional

from surge_tpu.common import Ack, Controllable, DecodedState, logger
from surge_tpu.config import Config, default_config
from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic, SurgeModel
from surge_tpu.engine.entity import AggregateEntity, Envelope
from surge_tpu.engine.partition import HostPort, PartitionTracker
from surge_tpu.engine.publisher import PartitionPublisher
from surge_tpu.engine.ref import AggregateRef
from surge_tpu.engine.router import SurgePartitionRouter
from surge_tpu.engine.shard import Shard
from surge_tpu.health import HealthCheck, HealthSignalBus, HealthSupervisor, RegexMatcher
from surge_tpu.log import InMemoryLog, TopicSpec
from surge_tpu.metrics import Metrics, engine_metrics
from surge_tpu.store import StateStoreIndexer, restore_from_events


class EngineStatus(Enum):
    """SurgeEngineStatus.scala equivalents."""

    STOPPED = "stopped"
    STARTING = "starting"
    RUNNING = "running"
    STOPPING = "stopping"
    FAILED = "failed"


class _Region:
    """One partition's publisher + shard (PersistentActorRegion.scala:26-116)."""

    def __init__(self, partition: int, publisher: PartitionPublisher, shard: Shard) -> None:
        self.partition = partition
        self.publisher = publisher
        self.shard = shard
        self._publisher_start = asyncio.ensure_future(self._start_with_retry())
        self._publisher_start.add_done_callback(self._on_publisher_started)

    async def _start_with_retry(self) -> None:
        """Publisher init with backoff (the BackoffSupervisor role around the
        reference's producer actor, AggregateStateStoreKafkaStreams.scala:
        106-118): a transient broker hiccup during open/flush-record must not
        leave the partition permanently unservable."""
        backoff = 0.2
        for attempt in range(5):
            try:
                await self.publisher.start()
                return
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 — retry transient init failures
                if attempt == 4:
                    raise
                logger.warning(
                    "publisher init failed for partition %d "
                    "(attempt %d/5, retrying in %.1fs): %r",
                    self.partition, attempt + 1, backoff, exc)
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 2.0)

    def _on_publisher_started(self, task: asyncio.Task) -> None:
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            logger.error("publisher init failed for partition %d: %r",
                         self.partition, exc)

    def deliver(self, aggregate_id: str, env: Envelope) -> None:
        self.shard.deliver(aggregate_id, env)

    async def stop(self) -> None:
        await self.shard.stop()
        if not self._publisher_start.done():
            self._publisher_start.cancel()
        await self.publisher.stop()


class SurgeEngine(Controllable):
    """A running engine for one aggregate family."""

    def __init__(self, logic: SurgeCommandBusinessLogic, log=None,
                 config: Config | None = None,
                 local_host: HostPort | None = None,
                 tracker: PartitionTracker | None = None,
                 remote_deliver=None, mesh=None, tracer=None,
                 membership=None, shard_allocation=None) -> None:
        self.logic = logic
        self.config = config or default_config()
        self.log = log if log is not None else InMemoryLog()
        self.local_host = local_host or HostPort("localhost", 0)
        self.mesh = mesh
        self.status = EngineStatus.STOPPED
        self.num_partitions = self.config.get_int("surge.engine.num-partitions", 8)
        self._external_tracker = tracker is not None
        self.tracker = tracker or PartitionTracker()

        self.log.create_topic(TopicSpec(logic.state_topic, self.num_partitions, compacted=True))
        if logic.events_topic:
            self.log.create_topic(TopicSpec(logic.events_topic, self.num_partitions))
        # observability plane: metrics registry + health signal bus + supervisor
        # (SurgeMessagePipeline wires the SlidingHealthSignalStreamProvider + Metrics
        # the same way, SurgeMessagePipeline.scala:56-87)
        # surge.metrics.exemplars: timers' histograms capture the active
        # trace id per recording (OpenMetrics exemplars — a p99 publish
        # bucket links to one JSONL trace). Opt-in: the engine hot path
        # records several timers per command.
        self.metrics_registry = Metrics(
            exemplars=self.config.get_bool("surge.metrics.exemplars", False))
        self.metrics = engine_metrics(self.metrics_registry)
        if getattr(self.log, "metrics", False) is None:
            # a broker-backed transport (GrpcLogTransport) counts its
            # failover rolls / NOT_LEADER redirects into this engine's
            # registry (surge.log.failover.*) unless the caller wired its own
            self.log.metrics = self.metrics
        self.tracer = tracer  # None = tracing disabled (zero per-message overhead)
        self.health_bus = HealthSignalBus(
            self.config.get_int("surge.health.signal-buffer-size", 25))
        self.health_supervisor = HealthSupervisor(self.health_bus, self.config)
        # engine-side flight recorder (the broker ring's twin): publisher
        # lane transitions, rebalance fan-out, resident-plane moves and
        # health-bus restarts land here; DumpFlight on the admin RPC pulls
        # the merge-ready envelope so engine + broker dumps interleave into
        # one cross-host incident timeline (tools/flight_timeline.py)
        from surge_tpu.observability.flight import FlightRecorder

        self.flight = FlightRecorder(
            capacity=self.config.get_int("surge.engine.flight-capacity", 1024),
            name=f"engine:{logic.aggregate_name}", role="engine")
        self.health_bus.subscribe(self._flight_health_signal)
        # refresh-round ledger (the device observatory): every resident-plane
        # fold round's padding-waste / per-stage anatomy and every gather
        # drain's device legs, in the flight envelope shape — DumpReplayLedger
        # pulls it, merge_dumps interleaves it with flight dumps, and
        # tools/roofline_record.py snapshots its summary
        from surge_tpu.replay.ledger import ReplayLedger

        self.replay_ledger = ReplayLedger(
            capacity=self.config.get_int(
                "surge.replay.resident.ledger-capacity", 512),
            name=f"engine:{logic.aggregate_name}")
        # tail-kept trace ring (the flight ring's trace twin, ISSUE 14):
        # install_tail attaches a TailSampler to the tracer so completed
        # traces that erred / breached surge.trace.tail.latency-ms / landed
        # in an SLO breach window are retained; the admin DumpTraces RPC
        # pulls the merge-ready envelope for cross-process anatomy assembly.
        # None when tracer=None (the tail plane costs nothing untraced).
        from surge_tpu.tracing.tail import install_tail

        self.trace_ring = install_tail(
            tracer, self.config, name=f"engine:{logic.aggregate_name}",
            role="engine", metrics=self.metrics)
        from surge_tpu.health.prober import EventLoopProber

        self.loop_prober = (EventLoopProber(
            self.config, on_signal=self.health_bus.signal_fn("event-loop"))
            if self.config.get_bool("surge.event-loop-prober.enabled") else None)
        self.surge_model = SurgeModel(logic, self.config)
        # saga / process-manager plane (surge_tpu.saga): attached via
        # register_saga_manager on the engine whose aggregates hold the saga
        # state machines; started/supervised with the pipeline lifecycle
        self.saga_manager = None
        self.indexer = StateStoreIndexer(self.log, logic.state_topic, config=self.config,
                                         on_signal=self.health_bus.signal_fn("state-store"))
        # routing backend selection by feature flag (SurgePartitionRouterImpl.scala:
        # 34-161 picks between the partition router and cluster sharding the same way)
        if self.config.get_bool("surge.feature-flags.experimental.enable-cluster-sharding"):
            from surge_tpu.engine.cluster import ClusterShardingRouter

            self.router = ClusterShardingRouter(
                num_partitions=self.num_partitions, tracker=self.tracker,
                local_host=self.local_host, region_creator=self._create_region,
                membership=membership, allocation=shard_allocation,
                remote_deliver=remote_deliver)
        else:
            self.router = SurgePartitionRouter(
                num_partitions=self.num_partitions, tracker=self.tracker,
                local_host=self.local_host, region_creator=self._create_region,
                remote_deliver=remote_deliver,
                dr_standby=self.config.get_bool("surge.engine.dr-standby-enabled"))
        self.router.tracer = tracer  # routing-hop spans (None = zero overhead)
        self.metrics_server = None  # started on demand by serve_metrics()
        self._rebalance_listeners: List[Callable] = []
        self._indexer_listener: Optional[Callable] = None
        # log compaction + state checkpoints (docs/compaction.md): the
        # compactor exists unconditionally so the admin CompactLog RPC can
        # always force a pass; its background scheduler only runs when enabled
        from surge_tpu.log.compactor import LogCompactor

        self.compactor = LogCompactor(
            self.log, config=self.config, topics=[logic.state_topic],
            metrics=self.metrics,
            on_signal=self.health_bus.signal_fn("log-compactor"))
        # device-resident materialized state plane (docs/replay.md): the
        # KTable-equivalent slab stays on device after the cold-start replay,
        # a standing refresh loop folds committed batches into it, and
        # getState / projections are answered from batched device gathers
        # with the host KV store as the staleness/coverage fallback
        self.resident_plane = None
        # incremental materialized views + changefeeds (docs/replay.md
        # "Materialized views"): registered scan queries the resident plane
        # folds every refresh round; None when no plane is wired — views NEED
        # the refresh feed, there is nothing to fold them from without it
        self.views = None
        if (self.config.get_bool("surge.replay.resident.enabled")
                and logic.events_topic):
            spec = logic.replay_spec()
            if spec is not None:
                from surge_tpu.replay.resident_state import ResidentStatePlane
                from surge_tpu.replay.views import MaterializedViews

                # the refresh feed's batch decoder (one C-level parse per
                # round) when the event format offers one; None keeps the
                # per-event path
                batch_read = getattr(logic.event_format,
                                     "read_events_batch", None)
                # counter-only profiler, ALWAYS wired (the un-gated "refresh"
                # umbrella): per-stage seconds/counts accumulate for the
                # observatory while the surge.replay.profile.* histograms
                # stay opt-in behind a DEBUG registry (sensor-level gating)
                from surge_tpu.replay.profiler import ReplayProfiler
                # engine-side fault plane (surge.log.faults.plan): arms the
                # corrupt.slab-row site for the corruption-to-page e2e; None
                # (the default) keeps every fault check a no-op
                from surge_tpu.testing.faults import FaultPlane

                self.resident_plane = ResidentStatePlane(
                    self.log, logic.events_topic, spec, config=self.config,
                    faults=FaultPlane.from_config(self.config),
                    partitions=[],  # assigned at start (follows the indexer)
                    deserialize_event=self._deserialize_event,
                    deserialize_events=batch_read,
                    serialize_state=lambda a, s: logic.state_format.write_state(s).value,
                    encode_event=getattr(logic, "encode_event", None),
                    decode_state=getattr(logic, "decode_state", None),
                    derived_cols=getattr(logic, "derived_cols", None),
                    mesh=self._resolve_mesh(), metrics=self.metrics,
                    on_signal=self.health_bus.signal_fn("resident-plane"),
                    profiler=ReplayProfiler.counters(metrics=self.metrics,
                                                     tracer=tracer),
                    flight=self.flight, ledger=self.replay_ledger,
                    tracer=tracer)
                self.views = MaterializedViews(
                    spec, config=self.config, mesh=self._resolve_mesh(),
                    metrics=self.metrics, ledger=self.replay_ledger,
                    flight=self.flight)
                self.resident_plane.attach_views(self.views)
        # consistency observatory (observability/audit.py): shadow-replays a
        # rotating cohort of resident rows against a from-scratch log refold,
        # compares cross-replica chained log digests, and probes the
        # exactly-once gate — findings page via the state-divergence SLO.
        # Digest peers join post-construction (engine.auditor.add_digest_peer)
        # since only cluster wiring knows the replica set.
        self.auditor = None
        if (self.resident_plane is not None
                and self.config.get_bool("surge.audit.enabled")):
            from surge_tpu.observability.audit import ConsistencyAuditor

            self.auditor = ConsistencyAuditor(
                self.resident_plane, log=self.log, config=self.config,
                metrics=self.metrics, flight=self.flight,
                on_signal=self.health_bus.signal_fn("consistency-auditor"))
            self.auditor.set_digest_targets(
                [(logic.events_topic, p)
                 for p in range(self.log.num_partitions(logic.events_topic))])
        self.checkpoint_writer = None
        ckpt_path = self.config.get_str("surge.store.checkpoint.path", "")
        if ckpt_path and logic.events_topic:
            from surge_tpu.store.checkpoint import (CheckpointStore,
                                                    CheckpointWriter)

            self._checkpoint_store = CheckpointStore(
                ckpt_path,
                keep=self.config.get_int("surge.store.checkpoint.keep", 2))
            self.checkpoint_writer = CheckpointWriter(
                self.log, logic.events_topic, logic.model,
                self._checkpoint_store,
                serialize_state=lambda a, s: logic.state_format.write_state(s).value,
                deserialize_event=self._deserialize_event,
                deserialize_state=logic.state_format.read_state,
                config=self.config, metrics=self.metrics,
                on_signal=self.health_bus.signal_fn("checkpoint-writer"))
        else:
            self._checkpoint_store = None

    # -- lifecycle (SurgeMessagePipeline.scala:185-240) ----------------------------------

    async def start(self) -> Ack:
        self.status = EngineStatus.STARTING
        try:
            if self.config.get_bool("surge.replay.restore-on-start"):
                await self.rebuild_from_events()
            # restart the state store on fatal signals (the restartSignalPatterns of
            # AggregateStateStoreKafkaStreams.scala:74-76)
            self.health_supervisor.register(
                "state-store", self.indexer,
                restart_patterns=[RegexMatcher(r"state-store.*fatal")])
            self.health_supervisor.start()
            if self.loop_prober is not None:
                self.loop_prober.start()
            # the indexer materializes only the partitions this node serves and
            # follows rebalances (Kafka Streams restores per assigned partition,
            # SURVEY.md §3.3; task migration §3.5); the listener is kept so
            # stop() can unregister it from a shared long-lived tracker
            self.indexer.set_partitions(self._indexer_partitions())
            if self._indexer_listener is None:
                self._indexer_listener = (
                    lambda _asg, _ch: self._retarget_partitions())
                self.tracker.register(self._indexer_listener, replay_current=False)
            await self.indexer.start()
            if self.resident_plane is not None:
                # the plane follows the same assignment as the indexer; it
                # seeds its slab from the events topic (cold-start replay that
                # stays on device), so it starts AFTER the indexer is tailing —
                # reads fall back to the host store until the seed lands
                self.resident_plane.set_partitions(self._indexer_partitions())
                await self.resident_plane.start()
                self.health_supervisor.register(
                    "resident-plane", self.resident_plane,
                    restart_patterns=[RegexMatcher(r"resident-plane.*fatal")])
            if self.config.get_bool("surge.log.compaction.enabled"):
                await self.compactor.start()
                self.health_supervisor.register(
                    "log-compactor", self.compactor,
                    restart_patterns=[RegexMatcher(r"log-compactor.*fatal")])
            if self.checkpoint_writer is not None:
                await self.checkpoint_writer.start()
                self.health_supervisor.register(
                    "checkpoint-writer", self.checkpoint_writer,
                    restart_patterns=[RegexMatcher(r"checkpoint-writer.*fatal")])
            await self.router.start()
            if self.saga_manager is not None:
                await self.saga_manager.start()
                self.health_supervisor.register(
                    "saga-manager", self.saga_manager,
                    restart_patterns=[RegexMatcher(r"saga-manager.*fatal")])
            if self.auditor is not None:
                await self.auditor.start()
                self.health_supervisor.register(
                    "consistency-auditor", self.auditor,
                    restart_patterns=[
                        RegexMatcher(r"consistency-auditor.*fatal")])
            if not self._external_tracker and not self.tracker.assignments.assignments:
                # single-node mode: self-assign every partition (no external control
                # plane; multi-node engines share an externally-updated tracker)
                self.tracker.update({self.local_host: list(range(self.num_partitions))})
            self.status = EngineStatus.RUNNING
            return Ack()
        except Exception:
            self.status = EngineStatus.FAILED
            # unwind partially-started observability tasks: a failed engine must not
            # leave the prober ticking or the supervisor subscribed forever
            if self._indexer_listener is not None:
                self.tracker.unregister(self._indexer_listener)
                self._indexer_listener = None
            self.health_supervisor.stop()
            if self.loop_prober is not None:
                await self.loop_prober.stop()
            raise

    def serve_metrics(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Start the OpenMetrics HTTP scrape endpoint for this engine's
        registry (health-bus + supervisor counters included); returns the
        bound port. Stopped automatically by :meth:`stop`."""
        from surge_tpu.metrics.exposition import MetricsHTTPServer, health_collector

        if self.metrics_server is not None:
            return self.metrics_server.bound_port
        self.metrics_server = MetricsHTTPServer(
            self.metrics_registry, host=host, port=port,
            collectors=[health_collector(self.health_bus,
                                         self.health_supervisor)])
        return self.metrics_server.start()

    async def stop(self) -> Ack:
        self.status = EngineStatus.STOPPING
        if self.metrics_server is not None:
            # shutdown() blocks until the serve_forever poll notices (plus a
            # thread join) — off the event loop so in-flight replies never stall
            server, self.metrics_server = self.metrics_server, None
            await asyncio.get_running_loop().run_in_executor(None, server.stop)
        if self._indexer_listener is not None:
            self.tracker.unregister(self._indexer_listener)
            self._indexer_listener = None
        self.health_supervisor.stop()
        if self.loop_prober is not None:
            await self.loop_prober.stop()
        if self.auditor is not None:
            await self.auditor.stop()
        if self.saga_manager is not None:
            await self.saga_manager.stop()
        await self.router.stop()  # stops regions (shards + publishers)
        if self.views is not None:
            self.views.close()  # end changefeed subscriptions first
        if self.resident_plane is not None:
            await self.resident_plane.stop()
        await self.indexer.stop()
        await self.compactor.stop()
        if self.checkpoint_writer is not None:
            await self.checkpoint_writer.stop()
        self.surge_model.close()
        self.status = EngineStatus.STOPPED
        return Ack()

    async def shutdown(self) -> Ack:
        return await self.stop()

    # -- client surface ------------------------------------------------------------------

    def aggregate_for(self, aggregate_id: str) -> AggregateRef:
        """scaladsl SurgeCommand.aggregateFor (SurgeCommand.scala:52-54)."""
        return AggregateRef(aggregate_id, self._deliver_checked, self.config,
                            tracer=self.tracer)

    def _deliver_checked(self, aggregate_id: str, env: Envelope) -> None:
        if self.status != EngineStatus.RUNNING:
            raise EngineNotRunningError(
                f"engine status is {self.status.value} (SurgeEngineNotRunningException)")
        self.router.deliver(aggregate_id, env)

    # -- saga plane (surge_tpu.saga) -----------------------------------------------------

    def register_saga_manager(self, manager) -> None:
        """Attach a :class:`~surge_tpu.saga.manager.SagaManager` to this
        engine's lifecycle: started after the router, supervised under the
        ``saga-manager.*fatal`` restart pattern (a fired ``crash.saga.*``
        point restarts the manager, whose resume scan is the recovery path).
        Call before :meth:`start`; a manager registered on a running engine
        is started immediately by the caller."""
        if manager.on_signal is None:
            manager.on_signal = self.health_bus.signal_fn("saga-manager")
        if manager.metrics is None:
            manager.metrics = self.metrics
        if manager.flight is None:
            manager.flight = self.flight
        self.saga_manager = manager

    async def start_saga(self, saga_id: str, definition: str,
                         ctx=(0.0, 0.0, 0.0, 0.0)):
        """Admin-plane delegate → :meth:`SagaManager.start_saga`."""
        if self.saga_manager is None:
            raise RuntimeError("no saga manager registered on this engine")
        return await self.saga_manager.start_saga(saga_id, definition, ctx)

    async def saga_status(self, saga_id: str = ""):
        """Admin-plane delegate: one saga's ledger, or the fleet summary
        (counts + reconciliation verdict) when ``saga_id`` is empty."""
        if self.saga_manager is None:
            raise RuntimeError("no saga manager registered on this engine")
        if saga_id:
            return await self.saga_manager.status(saga_id)
        return self.saga_manager.summary()

    def audit_status(self) -> dict:
        """Admin-plane delegate: the consistency auditor's verdict
        (``ok`` is False while any divergence is unresolved)."""
        if self.auditor is None:
            raise RuntimeError("consistency auditor not enabled on this "
                               "engine (surge.audit.enabled)")
        return self.auditor.summary()

    def register_rebalance_listener(self, listener: Callable) -> None:
        """listener(assignments, changes) on every tracker update
        (registerRebalanceListener, SurgeMessagePipeline.scala:93-95)."""
        self.tracker.register(listener)

    # -- regions -------------------------------------------------------------------------

    def _flight_health_signal(self, signal) -> None:
        """Health-bus tap for the flight ring: restarts and error-level
        signals are incident-timeline material; trace/warning chatter is not
        (the bounded ring must survive to the post-mortem)."""
        if (signal.level == "error"
                or signal.name.startswith("health.component-")):
            self.flight.record("health.signal", name=signal.name,
                               level=signal.level, source=signal.source)

    def _retarget_partitions(self) -> None:
        """Rebalance fan-out: the indexer AND the resident plane follow the
        tracker's view of this node's partitions together, so the plane's
        fold watermarks always cover exactly what the host store tails."""
        prev = set(self.indexer.partitions)
        parts = self._indexer_partitions()
        if set(parts) != prev:
            self.flight.record("rebalance.retarget",
                               granted=sorted(set(parts) - prev),
                               revoked=sorted(prev - set(parts)))
        self.indexer.set_partitions(parts)
        if self.resident_plane is not None:
            self.resident_plane.set_partitions(parts)

    def _fetch_state(self, aggregate_id: str):
        """Entity-init state fetch: the resident plane first (one coalesced
        device gather, ``require_current`` — a command folded on stale state
        would fork the aggregate), host KV store on any miss. Sync KV path
        when no plane is wired (the entity never awaits then)."""
        if self.resident_plane is None or not self.resident_plane.running:
            return self.indexer.get_aggregate_bytes(aggregate_id)

        async def fetch():
            hit, state = await self.resident_plane.read_state(
                aggregate_id, require_current=True)
            if hit:
                return DecodedState(state)
            return self.indexer.get_aggregate_bytes(aggregate_id)

        return fetch()

    async def project_states(self, aggregate_ids, *,
                             require_current: bool = False) -> Dict[str, object]:
        """Read-side projection over many aggregates: every resident hit rides
        ONE batched device gather + a single fetch-barriered pull; misses
        (not resident, stale beyond ``surge.replay.resident.max-lag-records``,
        revoked, or no plane at all) are served from the host KV store.
        Returns ``{aggregate_id: state}``, omitting ids with no state."""
        out: Dict[str, object] = {}
        missing = list(aggregate_ids)
        if self.resident_plane is not None and self.resident_plane.running:
            hits = await self.resident_plane.project(
                missing, require_current=require_current)
            out.update(hits)
            missing = [a for a in missing if a not in hits]
        for agg in missing:
            data = self.indexer.get_aggregate_bytes(agg)
            if data is not None:
                out[agg] = self.logic.state_format.read_state(data)
        return out

    def _create_region(self, partition: int) -> _Region:
        if partition not in self.indexer.partitions:
            # a region implies serving this partition: its publisher's lag gate
            # needs the indexer tailing it even if the tracker view disagrees
            self.indexer.set_partitions(
                sorted(set(self.indexer.partitions) | {partition}))
            if self.resident_plane is not None:
                self.resident_plane.set_partitions(self.indexer.partitions)
        publisher = PartitionPublisher(
            self.log, self.logic.state_topic, self.logic.events_topic or None,
            partition, self.indexer, config=self.config,
            transactional_id_prefix=self.logic.transactional_id_prefix,
            still_owner=lambda p=partition: (
                self.tracker.assignments.partition_to_host().get(p) == self.local_host),
            on_signal=self.health_bus.signal_fn(f"publisher-{partition}"),
            metrics=self.metrics, tracer=self.tracer, flight=self.flight)
        shard = Shard(
            f"{self.logic.aggregate_name}-{partition}",
            lambda aggregate_id, on_passivate, on_stopped: AggregateEntity(
                aggregate_id, self.surge_model, publisher,
                fetch_state=self._fetch_state, partition=partition,
                config=self.config, on_passivate=on_passivate, on_stopped=on_stopped,
                metrics=self.metrics, tracer=self.tracer),
            buffer_limit=self.config.get_int("surge.aggregate.passivation-buffer-limit", 1000),
            tracer=self.tracer)
        return _Region(partition, publisher, shard)

    # -- health -------------------------------------------------------------------------

    def health_check(self) -> HealthCheck:
        """Engine → router → regions ask-chain (SurgeHealthCheck analog,
        KafkaPartitionShardRouterActor.getHealthCheck:353-366). Also refreshes the
        live-entity gauge."""
        regions = []
        live = 0
        for p, region in self.router.regions():
            live += region.shard.num_live_entities
            pub_ok = region.publisher.state == "processing"
            regions.append(HealthCheck(
                name=f"region-{p}",
                status="up" if pub_ok else "degraded",
                components=[HealthCheck(name=f"publisher-{p}",
                                        status="up" if pub_ok else "down")]))
        self.metrics.live_entities.record(live)
        # unconditional: a promoted node (standby set now empty) must read 0,
        # not its last pre-promotion lag
        self.metrics.standby_lag.record(
            self.indexer.lag_for(self.standby_partitions()))
        router_h = self.router.health()
        components = [
            HealthCheck(name="router",
                        status="up" if router_h["status"] == "up" else "down",
                        components=regions),
            HealthCheck(name="state-store",
                        status="up" if self.indexer.running else "down"),
        ]
        if self.resident_plane is not None:
            # degraded, not down: reads fall back to the host store, the
            # engine keeps serving
            components.append(HealthCheck(
                name="resident-plane",
                status="up" if self.resident_plane.running else "degraded"))
        if self.auditor is not None:
            # degraded-not-down while a divergence is unresolved: the page
            # means "read the flight timeline", never "restart over it"
            components.append(self.auditor.health_component())
        return HealthCheck(
            name=self.logic.aggregate_name,
            status="up" if self.status == EngineStatus.RUNNING else "down",
            components=components)

    def producer_stats(self) -> Dict[str, float]:
        """Aggregated group-commit lane stats across this node's partitions
        (the operator view of the adaptive publisher: how well batching and
        pipelining are doing). Sums counters, maxes peaks."""
        out = {"flushes": 0, "records_published": 0, "batches_failed": 0,
               "fences": 0, "reinitializations": 0, "dedup_hits": 0,
               "max_batch_records": 0, "inflight_peak": 0, "lanes": 0}
        for _p, region in self.router.regions():
            s = region.publisher.stats
            out["lanes"] += 1
            out["flushes"] += s.flushes
            out["records_published"] += s.records_published
            out["batches_failed"] += s.batches_failed
            out["fences"] += s.fences
            out["reinitializations"] += s.reinitializations
            out["dedup_hits"] += s.dedup_hits
            out["max_batch_records"] = max(out["max_batch_records"],
                                           s.max_batch_records)
            out["inflight_peak"] = max(out["inflight_peak"], s.inflight_peak)
        if out["flushes"]:
            out["records_per_flush"] = round(
                out["records_published"] / out["flushes"], 2)
        return out

    def owned_partitions(self) -> List[int]:
        """The partitions this node owns per the tracker — or ALL partitions when
        no assignments exist yet (single-node cold start self-assigns everything;
        a multi-node engine's external tracker is populated by the control plane
        before start)."""
        mapping = self.tracker.assignments.partition_to_host()
        if not mapping:
            return list(range(self.num_partitions))
        return sorted(p for p, h in mapping.items() if h == self.local_host)

    def _indexer_partitions(self) -> List[int]:
        """Partitions the state-store indexer must tail: owned ones, any with a
        live local region (a direct node-transport delivery can create a region
        the tracker view disclaims mid-rebalance — its publisher lag gate still
        needs the watermark to advance), plus this node's standby set. A region
        partition revoked later keeps tailing until the next assignment update;
        harmless, just idle reads."""
        parts = set(self.owned_partitions())
        parts.update(p for p, _ in self.router.regions())
        parts.update(self.standby_partitions())
        return sorted(parts)

    def standby_partitions(self) -> List[int]:
        """Partitions this node keeps a WARM standby copy of (Kafka Streams
        num.standby.replicas, SurgeStateStoreConsumer.scala:42 + common
        reference.conf:24-25): for each partition, the N hosts following its
        owner on the sorted-host ring tail it too, so a rebalance that promotes
        this node needs no state-topic re-read — the store rows and watermark
        are already current."""
        n = self.config.get_int("surge.state-store.num-standby-replicas", 0)
        if n <= 0:
            return []
        hosts = sorted(self.tracker.assignments.assignments)
        if self.local_host not in hosts or len(hosts) < 2:
            return []
        rank = {h: i for i, h in enumerate(hosts)}
        mine = rank[self.local_host]
        out = []
        for p, owner in self.tracker.assignments.partition_to_host().items():
            if owner == self.local_host:
                continue
            gap = (mine - rank[owner]) % len(hosts)
            if 1 <= gap <= min(n, len(hosts) - 1):
                out.append(p)
        return sorted(out)

    # -- TPU bulk restore ---------------------------------------------------------------

    def _resolve_mesh(self):
        """The replay mesh: an explicit ``mesh=`` wins; otherwise the
        enable-mesh-sharding feature flag builds a 1-D ``data`` mesh over every
        visible device (entity-parallel replay across the chip/pod, SURVEY.md
        §2.10)."""
        if self.mesh is not None:
            return self.mesh
        if not self.config.get_bool(
                "surge.feature-flags.experimental.enable-mesh-sharding"):
            return None
        import jax
        import numpy as _np

        devices = jax.devices()
        if len(devices) < 2:
            return None  # a 1-device mesh adds sharding overhead for nothing
        axis = (self.config.get_str("surge.replay.mesh-axes", "data")
                .split(",")[0].strip() or "data")  # must match ReplayEngine's axis
        self.mesh = jax.sharding.Mesh(_np.asarray(devices), (axis,))
        return self.mesh

    async def rebuild_from_events(self):
        """Traced wrapper around :meth:`_rebuild_from_events_inner` — the bulk
        restore is the engine's single heaviest operation, so it gets a span of
        its own (root unless the caller nests it)."""
        if self.tracer is None:
            return await self._rebuild_from_events_inner()
        with self.tracer.start_span("engine.rebuild-from-events") as span:
            result = await self._rebuild_from_events_inner()
            span.set_attribute("num_events", result.num_events)
            span.set_attribute("num_aggregates", result.num_aggregates)
            span.set_attribute("backend", result.backend)
            return result

    async def _rebuild_from_events_inner(self):
        """Rebuild the materialized store by folding the events topic through the
        configured replay backend, then bring the indexer current.

        Two paths:
        - ``surge.replay.segment-path`` set → **columnar segment restore** (the
          100M-event-scale path): build the segment once if absent (events topic →
          struct-of-arrays chunks + state-only snapshot carry), then stream it
          through the batched ReplayEngine with no per-event Python objects, and
          prime the indexer at the segment's build-time state watermarks so
          tail-indexing covers everything since (events+state commit atomically, so
          every post-build change has a post-watermark snapshot).
        - otherwise → the object-based fold (small-topic fallback) + a full
          state-topic snapshot overlay.
        """
        if not self.logic.events_topic:
            raise ValueError("rebuild_from_events requires an events topic")
        evt_fmt = self.logic.event_format
        state_fmt = self.logic.state_format
        from surge_tpu.serialization import SerializedMessage

        spec = self.logic.replay_spec()
        mesh = self._resolve_mesh()
        # restore ONLY the partitions this node serves (the reference restores per
        # assigned task, SURVEY.md §3.3 — active AND standby tasks): a multi-node
        # cold start does 1/N (+standbys) of the work and never writes unrelated
        # nodes' aggregates into the local store
        owned = sorted(set(self.owned_partitions()) | set(self.standby_partitions()))

        rebuild_t0 = time.monotonic()
        segment_path = self.config.get_str("surge.replay.segment-path", "")
        if segment_path:
            result = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._rebuild_from_segment(
                    segment_path, spec, mesh, owned))
            if result.watermarks:  # snapshot-carrying segment: no full state scan
                # already scoped to `owned`: restore_from_segment filters its
                # returned watermarks by the partitions it was given
                watermarks = result.watermarks
                # Segment states are BUILD-time states. Wherever the indexer has
                # already advanced past the build watermark (warm rebuild, or the
                # tail loop ran concurrently with the restore), those snapshots
                # will never be re-read after prime()'s max() — re-apply exactly
                # that window so the restore cannot revert the store to stale
                # values (advisor r3 finding #2). Cold starts have watermark 0
                # everywhere and skip this entirely.
                self._replay_state_window(watermarks)
                self.indexer.prime(watermarks)
            else:  # segment built without a state topic: overlay + prime at now
                self._overlay_snapshots_and_prime(owned)
            self._record_replay_metrics(result, rebuild_t0)
            logger.info("rebuild_from_events: %d aggregates from %d events via %s",
                        result.num_aggregates, result.num_events, result.backend)
            return result

        # checkpointed cold start: fold only the tail past the newest durable
        # checkpoint's watermarks (docs/compaction.md). None when no checkpoint
        # store is configured or none has been written yet — then the fold
        # runs from offset 0 exactly as before. latest() reads + decodes the
        # whole checkpoint file, so it runs in the executor with the fold.
        result = await asyncio.get_running_loop().run_in_executor(None, lambda: restore_from_events(
            self.log, self.logic.events_topic, self.indexer.store,
            deserialize_event=self._deserialize_event,
            serialize_state=lambda agg_id, st: state_fmt.write_state(st).value,
            model=self.logic.model, replay_spec=spec,
            encode_event=getattr(self.logic, "encode_event", None),
            decode_state=getattr(self.logic, "decode_state", None),
            config=self.config, mesh=mesh, partitions=owned,
            checkpoint=(self._checkpoint_store.latest()
                        if self._checkpoint_store is not None else None),
            deserialize_state=state_fmt.read_state,
            encode_state=getattr(self.logic, "encode_state", None)))
        self._overlay_snapshots_and_prime(owned)
        self._record_replay_metrics(result, rebuild_t0)
        logger.info("rebuild_from_events: %d aggregates from %d events via %s",
                    result.num_aggregates, result.num_events, result.backend)
        return result

    def _deserialize_event(self, raw: bytes):
        from surge_tpu.serialization import SerializedMessage

        return self.logic.event_format.read_event(
            SerializedMessage(key="", value=raw))

    def _record_replay_metrics(self, result, t0: float) -> None:
        """Feed the predeclared replay instruments (SURVEY §5.5): fold wall
        time and achieved events/s of the bulk rebuild."""
        elapsed = max(time.monotonic() - t0, 1e-9)
        self.metrics.replay_timer.record_ms(elapsed * 1000.0)
        self.metrics.replay_events_per_sec.record(result.num_events / elapsed)

    def _replay_state_window(self, build_watermarks: Dict[int, int]) -> None:
        """Re-apply state-topic records in [build watermark, current indexer
        watermark) per partition — the window a segment restore just clobbered and
        the tail loop will not revisit. Latest-wins with tombstone deletes, same
        as the indexer's own apply path."""
        store = self.indexer.store
        for p in build_watermarks:
            start = build_watermarks.get(p, 0)
            current = self.indexer.indexed_watermark(self.logic.state_topic, p)
            if current <= start:
                continue
            for r in self.log.read(self.logic.state_topic, p, start):
                if r.offset >= current or r.key is None:
                    continue
                if r.value is None:
                    store.delete(r.key)
                else:
                    store.put(r.key, r.value)

    def _overlay_snapshots_and_prime(self, partitions: List[int] | None = None) -> None:
        """Overlay the state topic's latest snapshot per key (for ``partitions``,
        default all) and prime the indexer at the current end offsets. Latest-wins
        unconditionally: events+state commit atomically, so a snapshot is always ≥
        any state replayed from events it covers — this both fills in state-only
        aggregates (apply_events) and corrects states replayed from a stale
        externally-built segment."""
        store = self.indexer.store
        parts = list(range(self.num_partitions)) if partitions is None else partitions
        for p in parts:
            for key, rec in self.log.latest_by_key(self.logic.state_topic, p).items():
                if rec.value is None:  # tombstone, same as the indexer's tail path
                    store.delete(key)
                else:
                    store.put(key, rec.value)
        self.indexer.prime({p: self.log.end_offset(self.logic.state_topic, p)
                            for p in parts})

    def _rebuild_from_segment(self, segment_path: str, spec, mesh,
                              owned: List[int] | None = None):
        """Blocking half of the segment rebuild (runs in the executor): build the
        segment if absent (always covering EVERY partition — it is a shared
        artifact), then stream-restore only this node's ``owned`` partitions'
        chunks from it."""
        from surge_tpu.replay import ReplayEngine
        from surge_tpu.store.restore import restore_from_segment

        state_fmt = self.logic.state_format
        self._ensure_segment(segment_path, spec)
        # one engine a pipeline: the programs a rebuild compiled serve the
        # next rebuild of this process
        reng = getattr(self, "_restore_replay_engine", None)
        if reng is None:
            reng = self._restore_replay_engine = ReplayEngine(
                spec, config=self.config, mesh=mesh)
        return restore_from_segment(
            segment_path, self.indexer.store, replay_spec=spec,
            serialize_state=lambda agg_id, st: state_fmt.write_state(st).value,
            decode_state=getattr(self.logic, "decode_state", None),
            config=self.config, mesh=mesh, partitions=owned, engine=reng)

    def _ensure_segment(self, segment_path: str, spec) -> None:
        """Build the columnar segment if absent (covering EVERY partition — it
        is a shared artifact), else auto-extend it with the post-build delta.
        Blocking; callers run it in the executor. Shared by the segment
        restore and the query engine (both scan committed chunks)."""
        import os

        from surge_tpu.log.columnar import build_segment_from_topic

        evt_fmt = self.logic.event_format
        if not os.path.exists(segment_path):
            # build to a UNIQUE temp path and rename: a crash mid-build must
            # not leave a partial file later cold starts would silently
            # restore from, and two concurrent builders (queries racing the
            # first build) must never interleave writes into one tmp file —
            # each builds a complete segment and the atomic os.replace makes
            # the last one win whole (a duplicate build is wasted work, never
            # corruption)
            import glob
            import time as _time
            import uuid

            # sweep partials orphaned by a hard-killed builder (the unique
            # names never self-heal by overwrite); the age guard protects a
            # concurrent builder's live tmp file
            for stale in glob.glob(f"{segment_path}.building.*"):
                try:
                    if _time.time() - os.path.getmtime(stale) > 600:
                        os.unlink(stale)
                        logger.warning("removed stale segment build %s", stale)
                except OSError:
                    pass
            tmp_path = f"{segment_path}.building.{os.getpid()}.{uuid.uuid4().hex[:8]}"
            try:
                build_segment_from_topic(
                    self.log, self.logic.events_topic, spec.registry,
                    evt_fmt.read_event, tmp_path,
                    encode_event=getattr(self.logic, "encode_event", None),
                    derived_cols=getattr(self.logic, "derived_cols", None),
                    state_topic=self.logic.state_topic)
                os.replace(tmp_path, segment_path)
            finally:
                # a failed build's uniquely-named partial must not linger
                if os.path.exists(tmp_path):
                    try:
                        os.unlink(tmp_path)
                    except OSError:
                        pass
        elif self.config.get_bool("surge.replay.segment-auto-extend", True):
            # incremental maintenance: append delta chunks/snapshots for offsets
            # past the segment's watermarks so THIS restore (and the next one)
            # covers them without a state-topic crawl. Best-effort exclusive
            # lock — if another engine on a shared path is extending, skip; the
            # post-restore state window replay covers the delta anyway.
            from surge_tpu.log.columnar import extend_segment_from_topic

            lock_path = segment_path + ".extending"
            try:
                fd = os.open(lock_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                fd = None
                try:  # a crash mid-extend must not disable extension forever:
                    # reclaim locks older than 10 minutes (extends are fast —
                    # they cover only the post-build delta)
                    import time as _time

                    if _time.time() - os.path.getmtime(lock_path) > 600:
                        os.unlink(lock_path)
                        fd = os.open(lock_path,
                                     os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                        logger.warning("reclaimed stale segment-extend lock %s",
                                       lock_path)
                    else:
                        logger.info("segment extend skipped: %s held by a "
                                    "concurrent extender", lock_path)
                except OSError:
                    fd = None
            if fd is not None:
                try:
                    extend_segment_from_topic(
                        self.log, self.logic.events_topic, spec.registry,
                        evt_fmt.read_event, segment_path,
                        encode_event=getattr(self.logic, "encode_event", None),
                        state_topic=self.logic.state_topic)
                finally:
                    os.close(fd)
                    os.unlink(lock_path)

    # -- query engine (TPU scans over committed columnar segments) ----------------------

    @property
    def query_engine(self):
        """Lazily-built :class:`surge_tpu.replay.query.QueryEngine` for this
        family (mesh-aware: scans shard their event axis over the replay
        mesh). The analytics half of the KTable analogy — docs/replay.md
        "Query engine"."""
        eng = getattr(self, "_query_engine", None)
        if eng is None:
            from surge_tpu.replay.query import QueryEngine

            eng = self._query_engine = QueryEngine(
                self.logic.replay_spec(), config=self.config,
                mesh=self._resolve_mesh())
        return eng

    def _segment_path_for_query(self) -> str:
        path = self.config.get_str("surge.replay.segment-path", "")
        if not path:
            raise ValueError(
                "query requires surge.replay.segment-path (the committed "
                "columnar segment the scan engine reads)")
        return path

    async def query(self, query, partitions=None):
        """Run a :class:`~surge_tpu.replay.query.ScanQuery` (or its JSON dict
        form) over the committed columnar segment: predicate-pushdown filter +
        grouped aggregates keyed by aggregate id, batched (and mesh-sharded)
        on device. Builds/extends the segment first if needed; the whole scan
        runs in the executor — the event loop keeps serving commands."""
        from surge_tpu.replay.query import ScanQuery

        if isinstance(query, dict):
            query = ScanQuery.from_json(query)
        path = self._segment_path_for_query()
        spec = self.logic.replay_spec()
        loop = asyncio.get_running_loop()

        def run():
            self._ensure_segment(path, spec)
            return self.query_engine.scan_segment(
                path, query,
                partitions=set(partitions) if partitions is not None else None)

        result = await loop.run_in_executor(None, run)
        self._record_query(result, "scan")
        return result

    async def query_states(self, query, partitions=None):
        """Run a :class:`~surge_tpu.replay.query.StateQuery` (or its JSON dict
        form): fold the segment's chunks to current aggregate state through
        the (mesh-aware) replay engine, filter on state columns, project
        ``select``. The "every matching aggregate's current state" read the
        per-key store cannot answer without a full scan."""
        from surge_tpu.replay.query import StateQuery

        if isinstance(query, dict):
            query = StateQuery.from_json(query)
        path = self._segment_path_for_query()
        spec = self.logic.replay_spec()
        loop = asyncio.get_running_loop()

        def run():
            self._ensure_segment(path, spec)
            from surge_tpu.replay import ReplayEngine

            reng = getattr(self, "_query_replay_engine", None)
            if reng is None:
                reng = self._query_replay_engine = ReplayEngine(
                    spec, config=self.config, mesh=self._resolve_mesh())
            return self.query_engine.query_states_segment(
                path, query, reng,
                partitions=set(partitions) if partitions is not None else None)

        result = await loop.run_in_executor(None, run)
        self._record_query(result, "state")
        return result

    def _record_query(self, result, kind: str) -> None:
        """Query-engine observability off one scan result: the coarse
        timers plus the observatory's scan-rows / pushdown-selectivity
        instruments, the ledger's ``query`` event, and (traced) a
        retro-dated ``query.scan`` span whose device leg lets trace
        anatomy attribute a slow query to device dispatch."""
        m = self.metrics
        m.query_scan_timer.record_ms(result.elapsed_s * 1000.0)
        m.query_scanned_events.record(result.scanned_events)
        m.query_result_rows.record(result.num_aggregates)
        m.query_scan_rows.record(result.num_aggregates)
        m.query_pushdown_selectivity.record(
            result.matched_events / result.scanned_events
            if result.scanned_events else 0.0)
        self.replay_ledger.record_query(
            rows=result.num_aggregates, scanned=result.scanned_events,
            matched=result.matched_events,
            elapsed_us=result.elapsed_s * 1e6, kind=kind)
        if self.tracer is not None:
            span = self.tracer.start_span("query.scan")
            # retro-dated on BOTH clocks (the profiler span discipline):
            # the tail sampler and anatomy read the mono pair first
            span.start_time = time.time() - result.elapsed_s
            span.start_mono = time.monotonic() - result.elapsed_s
            try:
                span.set_attribute("kind", kind)
                span.set_attribute("leg.dispatch-ms",
                                   round(result.elapsed_s * 1000.0, 3))
                span.set_attribute("rows", result.num_aggregates)
                span.set_attribute("scanned", result.scanned_events)
            finally:
                span.finish()

    # -- materialized views + changefeeds (docs/replay.md) ------------------------------

    def _require_views(self):
        if self.views is None:
            raise RuntimeError(
                "materialized views need the resident plane "
                "(surge.replay.resident.enabled) — there is no refresh feed "
                "to fold them from without it")
        return self.views

    def register_view(self, view) -> None:
        """Register a :class:`~surge_tpu.replay.views.ViewDef` (or its JSON
        dict form). Before the plane's seed it joins the seed fold; on a
        running plane it parks pending and the plane backfills the committed
        prefix between refresh rounds."""
        from surge_tpu.replay.views import ViewDef

        self._require_views()
        if isinstance(view, dict):
            view = ViewDef.from_json(view)
        self.resident_plane.register_view(view)

    def unregister_view(self, name: str) -> bool:
        return self._require_views().unregister(name)

    async def query_view(self, name: str) -> dict:
        """Snapshot one materialized view: normalized columns over sorted
        keys (top-k cut applied), version + fold watermarks. Runs in the
        executor — a fold round may hold the views lock through a device
        scan, and the event loop must keep serving commands."""
        views = self._require_views()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, views.snapshot, name)

    async def view_summary(self) -> list:
        """One operator row per registered view (the ``QueryView`` RPC's
        no-name form, ``chaos.py views`` and surgetop)."""
        views = self._require_views()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, views.summary)

    async def subscribe_view(self, name: str, from_version=None):
        """Open a changefeed subscription (the ``SubscribeView`` RPC):
        yields per-round delta entries, starting with a reconciling snapshot
        unless ``from_version`` is a resume watermark the delta ring still
        covers. Close with ``engine.views.unsubscribe(sub)``."""
        views = self._require_views()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: views.subscribe(name, from_version, loop=loop))


class EngineNotRunningError(Exception):
    """SurgeEngineNotRunningException analog (scaladsl/common)."""
