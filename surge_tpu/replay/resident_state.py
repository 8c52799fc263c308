"""Device-resident materialized state plane — the KTable as device memory.

The reference serves every aggregate read from a host-side KeyValueStore fed
by the state-topic indexer (AggregateStateStoreKafkaStreams.scala:126-140);
the TPU replay engine only ever ran on cold starts. This module fuses the two
halves (ROADMAP item 2): after a cold-start replay the dense state slab STAYS
on device, a standing refresh loop folds each committed events batch into it
incrementally, and reads are answered by batched device gathers.

Design:

- **Slab + directory.** State lives as ``{field: [capacity+1]}`` device
  columns plus an int32 ordinal column (already-folded event count per slot,
  the derived-ordinal base). Row ``capacity`` is a scratch slot that absorbs
  every padded scatter/gather index, so all programs run on power-of-two
  bucketed shapes and the compile count stays bounded. A host-side directory
  maps aggregate id → slot.
- **Refresh loop (one h2d, zero d2h).** A supervised task tails the events
  topic off the same log subscription the :class:`StateStoreIndexer` uses
  (``read`` + ``wait_for_append`` per assigned partition), wire-packs each
  committed batch (surge_tpu.codec.wire — the same bit-packed format the bulk
  replay ships), and dispatches ONE jitted program per refresh window:
  admission scatter → gather lane carries → decode+fold → scatter back. The
  only host⇄device traffic is the packed window riding the dispatch; nothing
  comes back. A per-partition fold watermark tracks progress.
- **Admission / eviction.** The hot set is capacity-bounded. Aggregates are
  admitted when their events arrive (or at seed time); when the slab is full,
  least-recently-touched aggregates NOT in the current batch are evicted —
  their rows are pulled once (the one small d2h exception) into a host spill
  dict, so a later re-admission restores the exact fold point and the
  incremental invariant holds across evict/re-admit cycles (golden-tested).
- **Batched gather reads (single fetch-barriered pull).** Concurrent
  ``read_state`` calls queue onto a gather lane; a drainer coalesces them into
  one device gather and ONE device→host fetch — on a u16 wire when every state
  column is integral (d2h is the 25 MB/s wall; overflow triggers one wide
  refetch, correctness never depends on the guess — the same contract as
  ``ReplayEngine._pull_states``). Reads fall back to the host KV store when
  the aggregate is not resident or the partition's fold watermark lags beyond
  ``surge.replay.resident.max-lag-records``; the entity-init path demands
  ``require_current=True`` (lag 0), because a command processed on a stale
  snapshot would fork the aggregate — bounded staleness is only for read-side
  projections.
- **Rebalance.** ``set_partitions`` follows the indexer's assignment: revoked
  partitions purge their aggregates (resident + spill) outright — a stale row
  must never be servable — and granted partitions re-anchor at offset 0, so
  the refresh loop refolds them from scratch and can never double-fold.

Consistency model (docs/replay.md "Resident state plane"): every resident or
spilled row equals the fold of ALL its partition's committed events below the
partition watermark. Events+state commit atomically in one transaction, so a
row at watermark W is exactly the state snapshot the indexer will hold once it
passes W's transaction — byte-identical after the serialize chain.
"""

from __future__ import annotations

import asyncio
import functools
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from surge_tpu.codec.tensor import (encode_events, encode_events_columnar,
                                    state_columns, state_materializer)
from surge_tpu.codec.wire import WireFormat
from surge_tpu.common import (Ack, BackgroundTask, Controllable, logger,
                              spawn_reaped)
from surge_tpu.config import Config, default_config
from surge_tpu.engine.model import ReplaySpec
from surge_tpu.log.transport import page_keyed_records
from surge_tpu.replay.engine import ReplayEngine, make_batch_fold
from surge_tpu.replay.ledger import shard_skew, waste_ratio

__all__ = ["ResidentStatePlane"]


def _pow2(n: int, lo: int = 8) -> int:
    """Next power of two ≥ n (min ``lo``) — the shape bucket every plane
    program runs at, so concurrent batch sizes reuse compiled programs."""
    cap = lo
    while cap < n:
        cap *= 2
    return cap


def _pow8(n: int, lo: int = 8) -> int:
    """Next power of EIGHT ≥ n (min ``lo``) — the refresh program's coarser
    lane bucket. Steady incremental folds see a new batch size almost every
    round; a ×2 ladder would compile a fresh XLA program for half of them
    (~300 ms each on this class of host), which is exactly the latency spike
    the command path must not share the loop with. Padding lanes all target
    the scratch row, so the ≤8× over-dispatch is harmless device work."""
    cap = lo
    while cap < n:
        cap *= 8
    return cap


class ResidentStatePlane(Controllable):
    """Incrementally-maintained on-chip KTable over one events topic."""

    def __init__(self, log, events_topic: str, spec: ReplaySpec, *,
                 config: Config | None = None,
                 partitions: Optional[Sequence[int]] = None,
                 deserialize_event: Callable[[bytes], Any],
                 serialize_state: Callable[[str, Any], bytes],
                 deserialize_events: Callable[[Sequence[bytes]], list] | None = None,
                 encode_event: Callable[[Any], Any] | None = None,
                 decode_state: Callable[[str, Any], Any] | None = None,
                 derived_cols: Mapping[str, str] | None = None,
                 mesh=None, metrics=None,
                 on_signal: Callable[[str, str], None] | None = None,
                 profiler=None, flight=None, ledger=None, tracer=None,
                 faults=None) -> None:
        self.log = log
        self.events_topic = events_topic
        self.spec = spec
        self.config = config or default_config()
        self.deserialize_event = deserialize_event
        # the native-feed fast path (ISSUE 12): one batch deserialize per
        # refresh round (e.g. JsonEventFormatting.read_events_batch — ONE
        # C-level JSON parse per round) riding the native record-index read
        # views, instead of a json.loads + object build per event. The flag
        # is the paired-bench arm AND the operator kill-switch; a failing
        # batch degrades to the per-event path, which finds + poisons the
        # offending aggregate exactly as before.
        self.deserialize_events = (
            deserialize_events if self.config.get_bool(
                "surge.replay.resident.native-feed", True) else None)
        self.serialize_state = serialize_state
        self.encode_event = encode_event
        self.decode_state = decode_state
        self.derived = dict(derived_cols or {})
        self.mesh = mesh
        self.metrics = metrics  # EngineMetrics (resident_* instruments) or None
        self.on_signal = on_signal or (lambda name, level: None)
        self.profiler = profiler
        #: engine flight recorder (optional): seed/evict/re-anchor moves are
        #: incident-timeline material (a rebalance purging slab rows explains
        #: the fallback-read spike that follows it)
        self.flight = flight
        #: refresh-round ledger (surge_tpu.replay.ledger.ReplayLedger,
        #: optional): every round's padding-waste / per-stage anatomy, every
        #: gather drain's coalesce+device legs — the device observatory
        self.ledger = ledger
        #: tracer (optional): the gather lane emits "resident.gather" spans
        #: carrying leg.{coalesce,dispatch,fetch,decode}-ms attributes, so
        #: tail-kept traces break down into device legs in trace anatomy
        self.tracer = tracer
        #: FaultPlane (optional): the refresh executor passes through the
        #: "resident.refresh.dispatch" site — the stall-anatomy e2e's hook
        self._faults = faults

        self.capacity = max(
            self.config.get_int("surge.replay.resident.capacity", 65536), 8)
        # mesh-native slab (surge_tpu.replay.plane_mesh): "local" shards the
        # slab [n_dev, per_dev+1] with device-local gather lanes and
        # per-shard refresh deals; "replicated" keeps the legacy plain-jit
        # programs whose reads replicate the slab (the paired-bench baseline
        # arm). Capacity rounds UP to a device multiple so every shard holds
        # the same row count (the operator's floor is always honored).
        self._mesh_gather = self.config.get_str(
            "surge.replay.mesh.gather", "local")
        if self._mesh_gather not in ("local", "replicated"):
            raise ValueError(
                f"unknown surge.replay.mesh.gather {self._mesh_gather!r} "
                "(local|replicated)")
        self._meshp = None
        if mesh is not None:
            n_dev = int(np.prod(mesh.devices.shape))
            self.capacity = -(-self.capacity // n_dev) * n_dev
        self.max_lag = self.config.get_int(
            "surge.replay.resident.max-lag-records", 4096)
        self._max_poll = self.config.get_int(
            "surge.replay.resident.refresh-max-poll-records", 4096)
        self._poll_timeout = max(self.config.get_seconds(
            "surge.replay.resident.refresh-interval-ms", 50), 0.001)
        # refresh window width: the time-chunk rounded to a power of two —
        # rounds longer than one window fold through several chained windows
        self._window = _pow2(
            max(self.config.get_int("surge.replay.time-chunk", 512), 8))
        # refresh dispatch shape (ISSUE 18): "bucketed" (default) deals each
        # round's lanes into pow2 LENGTH buckets and issues one fused
        # admission→fold→scatter program per occupied bucket, so a steady
        # ragged round pays for slots near its occupied count instead of the
        # dense _pow8(lanes) × _pow2(max_len) rectangle; "dense" keeps the
        # single-rectangle dispatch (the paired-bench baseline arm and the
        # rollback switch)
        self._refresh_dispatch = self.config.get_str(
            "surge.replay.resident.refresh-dispatch", "bucketed")
        if self._refresh_dispatch not in ("bucketed", "dense"):
            raise ValueError(
                f"unknown surge.replay.resident.refresh-dispatch "
                f"{self._refresh_dispatch!r} (bucketed|dense)")
        # donate the slab+ordinal columns through every refresh scatter so
        # the round stops copying the slab it writes (kill-switchable like
        # donate-carry; see _build_programs for the read-race contract)
        self._donate_refresh = self.config.get_bool(
            "surge.replay.donate-refresh", True)
        #: every (lanes_b, width) pair a refresh program may compile at —
        #: the product of the pow2 lane ladder (8.._pow2(capacity)) and the
        #: pow2 width ladder (2..window). Both the dense sigs (pow8 lanes ⊂
        #: pow2 lanes, widths ≥ 8) and the bucketed sigs draw from this set,
        #: so the compile-signature count per slab layout is bounded by it
        #: however adversarially lane counts / tail lengths vary.
        self.bucket_table = self._build_bucket_table()

        self.partitions: List[int] = sorted(
            partitions if partitions is not None
            else range(log.num_partitions(events_topic)))
        self._watermarks: Dict[int, int] = {}
        self._last_ends: Dict[int, int] = {}
        # anchor generation per partition: bumped by every set_partitions
        # revoke OR grant. A refresh round captures the gens at poll time and
        # commits (fold + watermark advance) only where the gen is unchanged —
        # a revoke→re-grant pair landing while a slow round is in flight must
        # not let that round's commit overwrite the re-grant's 0-anchor (the
        # whole-partition refold would silently be skipped)
        self._anchor_gen: Dict[int, int] = {}
        # the bulk-replay engine used for seeding (its resident fold leaves
        # the cold-start slab on device; we gather rows out of it)
        self.engine = ReplayEngine(spec, config=self.config, mesh=mesh,
                                   profiler=profiler)
        self._wire = WireFormat(spec.registry, self.derived)
        self._fields = spec.registry.state.fields
        self._dtypes = {f.name: np.dtype(f.dtype) for f in self._fields}
        self._make_state = state_materializer(
            spec.registry.state, decode_state, with_ids=True)
        # a remote (broker) log turns end_offset into a blocking RPC — the
        # read path's freshness check must ride the executor there, never
        # the event loop it shares with the command path
        self._remote_log = bool(getattr(log, "is_remote", False))

        # host-side bookkeeping
        self._dir: Dict[str, int] = {}          # id -> slot
        self._free: List[int] = list(range(self.capacity))
        self._spill: Dict[str, Tuple[dict, int]] = {}  # id -> (row, ordinal)
        self._agg_part: Dict[str, int] = {}
        self._poisoned: Dict[str, int] = {}     # id -> partition (unfoldable)
        self._lru: Dict[str, int] = {}
        self._tick = 0
        self._warned_poison = False

        # device state (built on first start/seed)
        self._slab: dict | None = None
        self._ords = None
        self._programs_built = False
        self._signatures: set = set()  # (kind, shape...) — compile detection

        # read gather lane
        self._pending: List[Tuple[str, asyncio.Future]] = []
        self._draining = False
        self._drain_tasks: set = set()

        self._task: Optional[BackgroundTask] = None
        self._running = False
        self._stopped = False  # a STOPPED plane must miss: its freshness view
        #                        (_last_ends) is frozen while the log moves on
        self._seeded = False
        #: MaterializedViews (surge_tpu.replay.views) riding this plane's
        #: refresh feed, or None — every committed round folds into the
        #: registered views, and every partition purge drops their partials
        self._views = None
        self.stats = {"rounds": 0, "folded_events": 0, "evictions": 0,
                      "gathers": 0, "gathered_rows": 0, "fallbacks": 0}
        #: why reads fell back, cumulatively ({cause: n}) — the labeled
        #: split of the flat fallbacks counter (see _record_fallback)
        self.fallback_causes: Dict[str, int] = {}
        self._round_causes: Dict[str, int] = {}  # deltas since last round
        # per-round fold accounting (reset each refresh round): padded event
        # slots dispatched vs occupied, device dispatch wall, window count —
        # the padding-waste ledger's raw material
        self._round_acc: Dict[str, Any] = self._fresh_round_acc()
        self._pending_t0: Optional[float] = None  # gather coalesce-wait start

    @staticmethod
    def _fresh_round_acc() -> Dict[str, Any]:
        return {"windows": 0, "dispatched": 0, "occupied": 0,
                "dispatch_s": 0.0, "lanes": 0, "batch": 0, "width": 0,
                "evictions": 0, "programs": 0, "lane_slots": 0, "buckets": []}

    def _build_bucket_table(self) -> frozenset:
        """The bounded compile-signature set: every (lane bucket, window
        width) a refresh program may be shaped at for this capacity/window
        layout. Small by construction — O(log capacity × log window)."""
        lanes, cap = [], 8
        top = _pow2(self.capacity)
        while cap <= top:
            lanes.append(cap)
            cap *= 2
        widths, w = [], 2
        while w <= self._window:
            widths.append(w)
            w *= 2
        return frozenset((lb, wb) for lb in lanes for wb in widths)

    def _states_of_batch(self, ids: Sequence[str],
                         rows: Mapping[str, np.ndarray], k: int) -> list:
        """Materialize ``k`` gathered rows into domain states, the batch read
        path's per-row cost, through the bulk restores' own materializer
        (``codec.tensor.state_materializer``: ``StateSchema.from_record`` +
        ``restore._with_aggregate_id`` + ``decode_state`` with the per-field
        dispatch worked out once a schema), fed plain Python scalars off one
        C-speed ``ndarray.tolist()`` a column."""
        cols = state_columns(self.spec.registry.state, rows, k)
        make = self._make_state
        return [make(agg, cols, j) for j, agg in enumerate(ids)]

    # -- device programs ----------------------------------------------------------------

    def _sharded(self, arr):
        """The ``mesh.gather = replicated`` arm's slab layout: every device
        holds the WHOLE column and the plain-jit programs run SPMD over the
        replica set (n_dev× the scatter/fold work, n_dev× the memory — the
        baseline the device-local layout is paired against). The old P(axis)
        1-D sharding is gone: capacity+1 never divides the device count, and
        arbitrary-index gathers made XLA replicate it per read anyway."""
        if self.mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    @property
    def _mesh_local(self) -> bool:
        return self.mesh is not None and self._mesh_gather == "local"

    def _ensure_device_state(self) -> None:
        if self._slab is not None:
            return
        if self._mesh_local:
            from surge_tpu.replay.plane_mesh import MeshPlane

            if self._meshp is None:  # kept across a deleted-slab recovery
                self._meshp = MeshPlane(self)
            self._slab, self._ords = self._meshp.init_slab()
            self._build_programs()
            return
        init = self.spec.init_state_tree()
        cap1 = self.capacity + 1  # +1: the scratch row
        self._slab = {f.name: self._sharded(np.full(
            (cap1,), init[f.name], dtype=f.dtype)) for f in self._fields}
        self._ords = self._sharded(np.zeros((cap1,), dtype=np.int32))
        self._build_programs()

    def _build_programs(self) -> None:
        if self._programs_built:
            return
        import jax
        import jax.numpy as jnp

        wire = self._wire
        fold = make_batch_fold(self.spec)
        names = [f.name for f in self._fields]
        # the read wire follows the DEVICE dtypes, not the schema's: with
        # jax_enable_x64 off (the default) a 64-bit schema column is
        # canonicalized to its 32-bit kin on device — decoding a gather by
        # the schema dtype would misparse the buffer. Host decode widens
        # back to the schema dtype (the same contract as the bulk engine's
        # >4-byte per-field-pull guard in ReplayEngine._pull_states).
        dts = [np.dtype(self._slab[n].dtype) for n in names]
        self._dev_dts = dict(zip(names, dts))
        # u32 words per packed field row (2 for a genuine device-64-bit
        # column under jax_enable_x64)
        self._wide_words = [max(dt.itemsize // 4, 1) for dt in dts]
        # u16 read wire eligibility (shared by both slab layouts)
        self._narrow_ok = not any(np.issubdtype(dt, np.floating)
                                  or dt.itemsize > 4 for dt in dts)

        if self._mesh_local:
            # the sharded-slab programs live in plane_mesh (shard_map:
            # device-local refresh deals, one-collective gathers); the
            # single-device jit programs below never build
            self._refresh_prog = None
            self._seed_scatter = None
            self._gather_wide = self._meshp.gather_wide
            self._gather_narrow = (self._meshp.gather_narrow
                                   if self._narrow_ok else None)
            self._fetch_off_loop = jax.default_backend() != "cpu"
            self._programs_built = True
            return

        def refresh(slab, ords, admit_idx, admit_vals, admit_ord,
                    lane_slots, lane_counts, packed, side):
            # 1. admission scatter (spilled carries / init rows re-enter)
            slab = {k: v.at[admit_idx].set(admit_vals[k])
                    for k, v in slab.items()}
            ords = ords.at[admit_idx].set(admit_ord)
            # 2. gather the touched lanes' carries, decode+fold the window
            carry = {k: v[lane_slots] for k, v in slab.items()}
            events = wire.decode(packed, side, ords[lane_slots])
            out = fold(carry, events)
            # 3. scatter back + advance per-slot ordinals (padding lanes all
            # target the scratch row, so duplicate-index writes are harmless)
            slab = {k: v.at[lane_slots].set(out[k]) for k, v in slab.items()}
            ords = ords.at[lane_slots].add(lane_counts)
            return slab, ords

        # slab+ordinal donation (surge.replay.donate-refresh, default on):
        # the refresh scatter consumes the columns it rewrites instead of
        # copying the capacity-sized slab every window (the round-10 ladder's
        # replicated-arm collapse WAS this copy). The gather lane may still
        # hold an in-flight read of the previous slab while a fold
        # dispatches: _fold_group republishes self._slab after every donated
        # window and _drain_batch re-pins + retries on the deleted-buffer
        # error; a dispatch that fails after consuming its inputs rebuilds
        # through _recover_if_slab_deleted. The kill-switch restores the old
        # copying jit wholesale.
        self._refresh_prog = jax.jit(
            refresh, donate_argnums=(0, 1) if self._donate_refresh else ())

        def gather_wide(slab, ords, idx):
            cols = []
            for name, dt in zip(names, dts):
                v = slab[name][idx]
                if np.issubdtype(dt, np.floating) and dt.itemsize < 4:
                    v = jax.lax.bitcast_convert_type(
                        v.astype(jnp.float32), jnp.uint32)
                elif dt == np.bool_ or dt.itemsize < 4:
                    v = v.astype(jnp.uint32)
                elif dt != np.dtype(np.uint32):
                    v = jax.lax.bitcast_convert_type(v, jnp.uint32)
                if v.ndim == 2:  # 64-bit column: one row per u32 word
                    cols.extend(v[:, j] for j in range(v.shape[1]))
                else:
                    cols.append(v)
            return jnp.stack(cols), ords[idx]

        self._gather_wide = jax.jit(gather_wide)

        # u16 read wire: all-integer/bool schemas pull reads at half width
        # with device-computed fit flags at the tail — one flat buffer, one
        # fetch (the same narrow contract as ReplayEngine._pull_states)
        def gather_narrow(slab, idx):
            cols, flags = [], []
            for name, dt in zip(names, dts):
                v = slab[name][idx]
                if dt == np.bool_:
                    fits = jnp.bool_(True)
                elif np.issubdtype(dt, np.signedinteger):
                    fits = jnp.all((v >= -32768) & (v <= 32767))
                else:
                    fits = jnp.all(v <= 65535)
                cols.append(v.astype(jnp.uint16).ravel())
                flags.append(fits.astype(jnp.uint16))
            return jnp.concatenate(cols + [jnp.stack(flags)])

        self._gather_narrow = (jax.jit(gather_narrow)
                               if self._narrow_ok else None)

        def seed_scatter(slab, ords, src_slab, src_pos, dst_slots, lens):
            slab = {k: v.at[dst_slots].set(src_slab[k][src_pos])
                    for k, v in slab.items()}
            ords = ords.at[dst_slots].set(lens)
            return slab, ords

        self._seed_scatter = jax.jit(seed_scatter)
        # the gather lane's fetch runs off-loop only when the fetch is a real
        # device→host transfer; on the host cpu backend np.asarray is a
        # memcpy and the executor hop would cost more than the fetch
        self._fetch_off_loop = jax.default_backend() != "cpu"
        self._programs_built = True

    # -- lifecycle (Controllable) -------------------------------------------------------

    @property
    def running(self) -> bool:
        return self._running

    async def start(self) -> Ack:
        if self._running:
            return Ack()
        self._ensure_device_state()
        if not self._seeded:
            # the cold-start replay: heavy host-side scan/pack runs off the
            # event loop; the folded slab never leaves the device
            await asyncio.get_running_loop().run_in_executor(
                None, self.seed_from_log)
        self._task = BackgroundTask(self._refresh_loop, "resident-refresh")
        self._task.start()
        self._running = True
        self._stopped = False
        return Ack()

    async def stop(self) -> Ack:
        self._running = False
        self._stopped = True
        if self._task is not None:
            await self._task.stop()
            self._task = None
        # fail pending reads over to the host path promptly
        pending, self._pending = self._pending, []
        for target, fut in pending:
            if not fut.done():
                fut.set_result((False, None) if isinstance(target, str)
                               else {})
        return Ack()

    # -- seeding ------------------------------------------------------------------------

    def seed_from_log(self) -> None:
        """Cold-start seed: replay the assigned partitions' events through the
        bulk engine's resident path and gather the folded rows straight into
        the plane slab ON DEVICE (the state columns never round-trip through
        the host on the single-device path). Watermarks anchor at the
        pre-captured end offsets, so the refresh loop resumes exactly past
        what was folded. Aggregates beyond ``capacity`` (admitted
        longest-log-first — the cold heuristic for "hot") are pulled once and
        spilled; they re-admit on their next event or stay served from spill.

        The seed runs in the EXECUTOR (``start`` keeps the loop free), so a
        rebalance landing on the loop mid-seed cannot be fenced at each
        commit the way ``_fold_group`` fences — instead the whole seed is
        reconciled after the fact: any partition whose anchor generation
        moved while the seed flew is purged and de-anchored (a revoked
        partition's rows must never be servable; a re-granted one refolds
        from 0 through the refresh loop, which re-anchors assigned
        partitions via ``setdefault``)."""
        self._ensure_device_state()
        gens = {p: self._anchor_gen.get(p, 0) for p in self.partitions}
        ends = {p: self.log.end_offset(self.events_topic, p) for p in gens}
        try:
            self._seed_scan_fold(ends)
        finally:
            for p in ends:
                if (p not in self.partitions
                        or self._anchor_gen.get(p, 0) != gens.get(p, 0)):
                    self._purge_partition(p)
                    self._watermarks.pop(p, None)
        if self.flight is not None:
            self.flight.record("resident.seed",
                               partitions=sorted(ends),
                               resident=len(self._dir),
                               spilled=len(self._spill))

    def _seed_scan_fold(self, ends: Dict[int, int]) -> None:
        logs: Dict[str, list] = {}
        part_of: Dict[str, int] = {}
        for p in ends:
            for rec in page_keyed_records(self.log, self.events_topic, p,
                                          upto=ends[p]):
                ev = self._encode_checked(rec.key, rec.value, p)
                if ev is None:
                    logs.pop(rec.key, None)
                    continue
                logs.setdefault(rec.key, []).append(ev)
                part_of[rec.key] = p
        self._watermarks.update(ends)
        self._seeded = True
        if self._views is not None and self._views.active_or_pending:
            # the seed IS round zero for every registered view: fold the same
            # scanned logs, anchored at the same end offsets (pending views
            # activate here — the seed covers them from offset 0). Partitions
            # re-anchored mid-seed are reconciled by seed_from_log's purge,
            # which drops their view partials too.
            self._views.fold_round(logs, part_of, dict(ends),
                                   activate_pending=True)
        if not logs:
            self._record_gauges()
            return
        # longest logs first: they are the expensive-to-refold rows, keep them
        ids = sorted(logs, key=lambda a: len(logs[a]), reverse=True)
        lengths = np.asarray([len(logs[a]) for a in ids], dtype=np.int32)
        colev = encode_events_columnar(self.spec.registry,
                                       [logs[a] for a in ids])
        colev.derived_cols = dict(self.derived)

        if self.mesh is not None:
            # mesh-sharded cold start (ShardedResident): fold across devices,
            # then deal-indexed gather into the sharded plane slab
            from surge_tpu.replay.resident_mesh import fold_resident_sharded

            sharded = self.engine.prepare_resident_sharded(colev)
            slab_dev = fold_resident_sharded(self.engine, sharded)
            host = {k: np.asarray(v) for k, v in slab_dev.items()}
            states = {k: np.empty((len(ids),), dtype=self._dtypes[k])
                      for k in host}
            perm = sharded.wire_host.perm
            for d, lanes in enumerate(sharded.deals):
                for k in states:
                    # lanes are sorted ranks; perm maps rank -> original index
                    orig = lanes if perm is None else perm[lanes]
                    states[k][orig] = host[k][d, : len(lanes)]
            self._seed_from_host_rows(ids, states, lengths, part_of)
            self._record_gauges()
            return

        wire = self.engine.pack_resident(colev)
        corpus = self.engine.upload_resident(wire)
        slab_sorted, _ = self.engine.fold_resident_slab(corpus)
        # sorted position of original aggregate i: inv_perm[i]
        b = len(ids)
        if corpus.perm is None:
            inv = np.arange(b, dtype=np.int32)
        else:
            inv = np.empty((b,), dtype=np.int32)
            inv[corpus.perm] = np.arange(b, dtype=np.int32)
        n_res = min(b, self.capacity)
        dst = np.fromiter((self._free.pop() for _ in range(n_res)),
                          dtype=np.int32, count=n_res)
        k_b = _pow2(n_res)
        src_p = np.zeros((k_b,), dtype=np.int32)
        src_p[:n_res] = inv[:n_res]
        dst_p = np.full((k_b,), self.capacity, dtype=np.int32)
        dst_p[:n_res] = dst
        lens_p = np.zeros((k_b,), dtype=np.int32)
        lens_p[:n_res] = lengths[:n_res]
        self._slab, self._ords = self._seed_scatter(
            self._slab, self._ords, slab_sorted, src_p, dst_p, lens_p)
        for j, agg in enumerate(ids[:n_res]):
            self._dir[agg] = int(dst[j])
            self._agg_part[agg] = part_of[agg]
            self._touch(agg)
        if b > n_res:
            # overflow: one pull of the cold rows into the host spill
            over_pos = inv[n_res:]
            rows, _ = self._pull_positions(slab_sorted, over_pos)
            for j, agg in enumerate(ids[n_res:]):
                self._spill[agg] = ({k: rows[k][j] for k in rows},
                                    int(lengths[n_res + j]))
                self._agg_part[agg] = part_of[agg]

    def _seed_from_host_rows(self, ids, states, lengths, part_of) -> None:
        """Admit host-side state columns (the mesh seed path) into the slab."""
        n_res = min(len(ids), self.capacity)
        dst = np.fromiter((self._free.pop() for _ in range(n_res)),
                          dtype=np.int32, count=n_res)
        k_b = _pow2(max(n_res, 1))
        dst_p = np.full((k_b,), self.capacity, dtype=np.int32)
        dst_p[:n_res] = dst
        vals = {k: np.zeros((k_b,), dtype=self._dtypes[k]) for k in states}
        for k in states:
            vals[k][:n_res] = states[k][:n_res]
        lens_p = np.zeros((k_b,), dtype=np.int32)
        lens_p[:n_res] = lengths[:n_res]
        if self._mesh_local:
            # sharded-slab admission: values ride replicated, every device
            # keeps only the rows it owns (plane_mesh.seed_rows)
            self._slab, self._ords = self._meshp.seed_rows(
                self._slab, self._ords, vals, dst_p, lens_p)
        else:
            # reuse the admission half of the refresh program via
            # seed_scatter on an identity source: scatter host values
            # through a device_put
            slab_src = {k: self._sharded(vals[k]) for k in vals}
            pos = np.arange(k_b, dtype=np.int32)
            self._slab, self._ords = self._seed_scatter(
                self._slab, self._ords, slab_src, pos, dst_p, lens_p)
        for j, agg in enumerate(ids[:n_res]):
            self._dir[agg] = int(dst[j])
            self._agg_part[agg] = part_of[agg]
            self._touch(agg)
        for j, agg in enumerate(ids[n_res:]):
            self._spill[agg] = ({k: states[k][n_res + j] for k in states},
                                int(lengths[n_res + j]))
            self._agg_part[agg] = part_of[agg]

    # -- consistency audit surface (observability/audit.py) -----------------------------

    def audit_pull(self, agg_ids: Sequence[str]) -> Dict[str, tuple]:
        """ONE gather of the LIVE slab rows + fold ordinals for the given
        aggregates (the shadow-replay audit's ground truth). Call ON the
        loop: the (row, ordinal) pairs come out of a single device gather
        against the pinned slab, so they are atomic w.r.t. fold commits —
        a row is always the fold of exactly its ordinal's event prefix.
        Aggregates not resident (spilled/evicted/poisoned) are omitted;
        returns ``{agg: ({field: scalar}, ordinal)}``."""
        ids = [a for a in agg_ids if a in self._dir]
        if not ids:
            return {}
        idx = np.fromiter((self._dir[a] for a in ids), dtype=np.int32,
                          count=len(ids))
        rows, ords = self._pull_positions(self._slab, idx, ords=self._ords)
        return {a: ({k: rows[k][j] for k in rows}, int(ords[j]))
                for j, a in enumerate(ids)}

    def shadow_replay_rows(self, event_logs: List[list]
                           ) -> Dict[str, np.ndarray]:
        """Re-fold per-aggregate event lists FROM SCRATCH through the same
        device fold that built the live rows (the seed path:
        ``pack_resident`` → ``fold_resident_slab``) and pull the folded rows
        to host — the auditor's shadow replay. Pure w.r.t. plane state: the
        fold runs on a fresh one-shot corpus, nothing scatters into the live
        slab. Heavy (encode + pack + device dispatch) — run in the executor.
        Returns ``{field: np[b]}`` in ``event_logs`` order."""
        b = len(event_logs)
        colev = encode_events_columnar(self.spec.registry, event_logs)
        colev.derived_cols = dict(self.derived)
        if self.mesh is not None:
            from surge_tpu.replay.resident_mesh import fold_resident_sharded

            sharded = self.engine.prepare_resident_sharded(colev)
            slab_dev = fold_resident_sharded(self.engine, sharded)
            host = {k: np.asarray(v) for k, v in slab_dev.items()}
            states = {k: np.empty((b,), dtype=self._dtypes[k]) for k in host}
            perm = sharded.wire_host.perm
            for d, lanes in enumerate(sharded.deals):
                for k in states:
                    orig = lanes if perm is None else perm[lanes]
                    states[k][orig] = host[k][d, : len(lanes)]
            return states
        wire = self.engine.pack_resident(colev)
        corpus = self.engine.upload_resident(wire)
        slab_sorted, _ = self.engine.fold_resident_slab(corpus)
        if corpus.perm is None:
            inv = np.arange(b, dtype=np.int32)
        else:
            inv = np.empty((b,), dtype=np.int32)
            inv[corpus.perm] = np.arange(b, dtype=np.int32)
        rows, _ = self._pull_positions(slab_sorted, inv)
        return rows

    def _corrupt_resident_row(self) -> Optional[str]:
        """Flip one bit in one LIVE resident slab row (the armed
        ``corrupt.slab-row`` fault firing): the log stays correct, the
        device row now lies — exactly the silent rot only the shadow-replay
        audit can see. The row's fold ordinal is preserved (the corruption
        must look like a validly-folded row, not an admission glitch). Flips
        the raw top byte's sign bit so the change survives any on-wire
        dtype narrowing. Returns the corrupted aggregate id, or None when
        nothing is resident."""
        if not self._dir:
            return None
        agg = next(iter(self._dir))
        slot = self._dir[agg]
        rows, ords = self._pull_positions(
            self._slab, np.asarray([slot], dtype=np.int32), ords=self._ords)
        victim = next((f.name for f in self._fields
                       if f.dtype != np.bool_), self._fields[0].name)
        k_b = _pow2(1)
        dst_p = np.full((k_b,), self.capacity, dtype=np.int32)
        dst_p[0] = slot
        lens_p = np.zeros((k_b,), dtype=np.int32)
        lens_p[0] = int(ords[0])
        vals_p = {k: np.zeros((k_b,), dtype=self._dtypes[k]) for k in rows}
        for k in rows:
            v = rows[k][:1].copy()
            if k == victim:
                if v.dtype == np.bool_:
                    v[0] = not v[0]
                else:
                    v.view(np.uint8)[-1] ^= 0x80
            vals_p[k][0] = v[0]
        if self._mesh_local:
            self._slab, self._ords = self._meshp.seed_rows(
                self._slab, self._ords, vals_p, dst_p, lens_p)
        else:
            slab_src = {k: self._sharded(vals_p[k]) for k in vals_p}
            pos = np.arange(k_b, dtype=np.int32)
            self._slab, self._ords = self._seed_scatter(
                self._slab, self._ords, slab_src, pos, dst_p, lens_p)
        logger.warning("fault plane corrupted resident row of %r "
                       "(field %s)", agg, victim)
        return agg

    def prime(self, watermarks: Dict[int, int]) -> None:
        """Fast-forward fold watermarks after an out-of-band seed covered the
        offsets (the :meth:`StateStoreIndexer.prime` analog — only valid
        together with a slab seed of the same coverage)."""
        for p, off in watermarks.items():
            if p in self._watermarks:
                self._watermarks[p] = max(self._watermarks[p], off)

    # -- rebalance ----------------------------------------------------------------------

    def set_partitions(self, partitions: Sequence[int]) -> None:
        """Retarget the assigned partitions (follows the indexer's rebalance).
        Revoked partitions purge their aggregates — resident rows, spill AND
        poison marks — because the plane stops folding them and a stale row
        must never be servable. Granted partitions re-anchor at offset 0: the
        refresh loop refolds the whole partition through fresh admissions, so
        a revoke→re-grant cycle can never double-fold an event."""
        new = sorted(set(partitions))
        if new == self.partitions:
            return
        removed = [p for p in self.partitions if p not in new]
        added = [p for p in new if p not in self.partitions]
        self.partitions = new
        for p in removed:
            self._watermarks.pop(p, None)
            self._anchor_gen[p] = self._anchor_gen.get(p, 0) + 1
            self._purge_partition(p)
        for p in added:
            self._purge_partition(p)  # defensive: must never double-fold
            self._watermarks[p] = 0
            self._anchor_gen[p] = self._anchor_gen.get(p, 0) + 1
        if self.flight is not None:
            self.flight.record("resident.re-anchor", granted=added,
                               revoked=removed, resident=len(self._dir))
        self._record_gauges()

    # -- materialized views (surge_tpu.replay.views) ------------------------------------

    def attach_views(self, views) -> None:
        """Hand the plane the engine's :class:`MaterializedViews`: every
        committed refresh round (and the cold-start seed) folds into them,
        and every re-anchor path drops their per-partition partials."""
        self._views = views

    def register_view(self, vdef) -> None:
        """Register a view against this plane's feed. Before the seed it
        simply activates (the seed fold covers it from offset 0); on a
        seeded plane it parks PENDING and the refresh loop backfills the
        already-folded prefix between rounds — registration never races a
        fold."""
        if self._views is None:
            raise RuntimeError(
                "no MaterializedViews attached to this resident plane")
        self._views.register(vdef, active=not self._seeded)

    def _backfill_pending_views(self) -> None:
        """Executor half of register-while-running: re-read each assigned
        partition's committed prefix [0, watermark) and fold it into every
        pending view. Runs between refresh rounds (the loop awaits it), so
        it never races a fold; a rebalance landing mid-backfill is fenced
        exactly like the seed — partitions whose anchor generation moved are
        dropped from the commit."""
        views = self._views
        gens = {p: self._anchor_gen.get(p, 0) for p in self.partitions}
        wms = {p: self._watermarks.get(p, 0) for p in gens}
        logs: Dict[str, list] = {}
        part_of: Dict[str, int] = {}
        for p, wm in wms.items():
            if wm <= 0:
                continue
            for rec in page_keyed_records(self.log, self.events_topic, p,
                                          upto=wm):
                ev = self._encode_checked(rec.key, rec.value, p)
                if ev is None:
                    logs.pop(rec.key, None)
                    continue
                logs.setdefault(rec.key, []).append(ev)
                part_of[rec.key] = p
        committed = {p: wm for p, wm in wms.items()
                     if p in self._watermarks
                     and self._anchor_gen.get(p, 0) == gens[p]}
        for name in [v["view"] for v in views.summary() if not v["active"]]:
            views.fold_view_backfill(name, logs, part_of, committed)

    def _purge_partition(self, p: int) -> None:
        if self._views is not None:
            self._views.drop_partition(p)
        for agg in [a for a, ap in self._agg_part.items() if ap == p]:
            slot = self._dir.pop(agg, None)
            if slot is not None:
                self._free.append(slot)
            self._spill.pop(agg, None)
            self._lru.pop(agg, None)
            self._agg_part.pop(agg, None)
        for agg in [a for a, ap in self._poisoned.items() if ap == p]:
            self._poisoned.pop(agg, None)

    # -- refresh loop -------------------------------------------------------------------

    async def _refresh_loop(self) -> None:
        backoff = 0.25
        while True:
            try:
                t0 = time.perf_counter()
                if await self._refresh_once():
                    backoff = 0.25
                    # PACE the loop: at most one fold round per refresh
                    # interval. Without this a continuous publisher turns the
                    # loop into a spin — hundreds of tiny rounds/s each
                    # paying the poll+dispatch overhead — instead of one
                    # round per interval folding the whole committed batch.
                    # The interval is therefore also the plane's staleness
                    # cadence (docs/replay.md).
                    spent = time.perf_counter() - t0
                    if spent < self._poll_timeout:
                        await asyncio.sleep(self._poll_timeout - spent)
                    continue
                await self._wait_for_any_append()
                backoff = 0.25
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — keep the plane alive
                logger.exception("resident refresh round failed; retrying "
                                 "in %.2fs", backoff)
                try:
                    self.on_signal("surge.replay.resident.refresh-error",
                                   "error")
                except Exception:  # noqa: BLE001
                    logger.exception("on_signal failed")
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 30.0)

    async def _wait_for_any_append(self) -> None:
        if not self.partitions:
            await asyncio.sleep(self._poll_timeout)
            return
        waiters = [asyncio.ensure_future(
            self.log.wait_for_append(self.events_topic, p,
                                     self._watermarks.get(p, 0)))
            for p in self.partitions]
        try:
            await asyncio.wait(waiters, timeout=self._poll_timeout,
                               return_when=asyncio.FIRST_COMPLETED)
        finally:
            for w in waiters:
                if not w.done():
                    w.cancel()
                else:
                    w.exception()  # retrieve, avoid un-awaited warnings

    def _poll_batches(self, watermarks: Dict[int, int]):
        """Executor half of the poll: read each partition's committed tail
        past its watermark. Log reads stat/open real files on a FileLog —
        polling ON the loop every interval is exactly the latency tax the
        command path must not pay. Returns ``(batches, ends)`` — ``ends``
        carries every polled partition's end offset for gauge/fast-forward
        use without another on-loop log call.

        The PR-6 sustained-fold wall was this read's host-side decode: on a
        FileLog these reads now ride the native record-index decoder
        (csrc/txn.cc ``surge_seg_index`` via ``segment.decode_records``),
        guarded by the same ``surge.log.native.enabled`` fallback flag as
        the broker hot path — unbuilt/disabled checkouts keep the pure-
        Python uvarint walk, record-identical."""
        batches: Dict[int, list] = {}
        ends: Dict[int, int] = {}
        for p, wm in watermarks.items():
            recs = self.log.read(self.events_topic, p, wm,
                                 max_records=self._max_poll)
            if not recs:
                end = self.log.end_offset(self.events_topic, p)
                if end > wm:
                    # records may have been made durable between the empty
                    # read and the end offset: only what a read made AFTER
                    # the end was taken still cannot see is a hole the
                    # caller may fast-forward over
                    recs = self.log.read(self.events_topic, p, wm,
                                         max_records=self._max_poll)
            if recs:
                batches[p] = recs
                ends[p] = recs[-1].offset + 1
            else:
                ends[p] = end
        return batches, ends

    async def _refresh_once(self) -> bool:
        """One refresh round: read each partition's committed tail, fold it
        into the slab (admitting/evicting as needed), advance watermarks.
        Returns False when nothing was pending."""
        loop = asyncio.get_running_loop()
        if self._views is not None and self._views.has_pending:
            # register-while-running: backfill the committed prefix into the
            # pending views BETWEEN rounds (the loop awaits; no fold races)
            await loop.run_in_executor(None, self._backfill_pending_views)
        wms = {p: self._watermarks.setdefault(p, 0)
               for p in list(self.partitions)}
        gens = {p: self._anchor_gen.get(p, 0) for p in wms}
        feed_t0 = time.perf_counter()
        batches, ends = await loop.run_in_executor(
            None, self._poll_batches, wms)
        self._last_ends = ends
        for p, end in ends.items():
            if (p in batches or p not in self._watermarks
                    or self._anchor_gen.get(p, 0) != gens[p]):
                continue
            if end > self._watermarks[p]:
                # compaction hole at the tail: fast-forward like the indexer
                self._watermarks[p] = end
        if not batches:
            self._record_gauges()
            return False
        t0 = time.perf_counter()
        self._round_acc = self._fresh_round_acc()
        # the heavy host-side work — per-record deserialize + tensor encode —
        # runs OFF the event loop: a fold round must not stall the command
        # path it shares the loop with (only state mutation + the program
        # dispatches run on-loop, in await-free sections)
        logs, part_of, n_events, poisons = await loop.run_in_executor(
            None, self._decode_batches, batches)
        feed_s = time.perf_counter() - feed_t0
        if self.metrics is not None:
            # the feed's host leg: committed-tail read (native record-index
            # views) + event deserialize (one batch decode on the native
            # feed) — what the ≥100k ev/s sustained-fold target is about
            self.metrics.resident_feed_timer.record_ms(feed_s * 1000.0)
        for agg, p in poisons.items():
            self._poison(agg, p)
        enc_s = time.perf_counter() - t0
        ids = list(logs)
        # capacity-bounded fold groups (a round's distinct aggregates can
        # exceed the slab; each group admits/evicts then folds)
        try:
            for lo in range(0, len(ids), self.capacity):
                group = ids[lo: lo + self.capacity]
                await self._fold_group(group, logs, part_of, gens)
        except Exception:
            # a mid-round failure leaves the groups committed SO FAR folded
            # past the round's (un-advanced) watermarks — the retry would
            # refold their events (double-fold). Re-anchor every polled
            # partition through the re-grant path: purge + watermark 0 + gen
            # bump, so the next rounds refold each partition from scratch
            # (the golden-tested never-double-fold route).
            for p in batches:
                if (p in self._watermarks
                        and self._anchor_gen.get(p, 0) == gens.get(p, 0)):
                    self._purge_partition(p)
                    self._watermarks[p] = 0
                    self._anchor_gen[p] = self._anchor_gen.get(p, 0) + 1
            # a donated dispatch that failed AFTER consuming its inputs
            # leaves no slab to serve from — rebuild it empty and re-anchor
            # EVERY tracked partition for refold (the never-double-fold route)
            self._recover_if_slab_deleted()
            raise
        committed: Dict[int, int] = {}
        for p, recs in batches.items():
            # skip partitions revoked OR re-anchored (revoke→re-grant) while
            # the round flew: overwriting a re-grant's 0-anchor would skip
            # the whole-partition refold
            if (p in self._watermarks
                    and self._anchor_gen.get(p, 0) == gens[p]):
                self._watermarks[p] = recs[-1].offset + 1
                committed[p] = recs[-1].offset + 1
        if (self._views is not None and committed
                and self._views.active_or_pending):
            # the views' leg of the round rides the same decoded logs, under
            # the same gen fence the slab commit just passed — one columnar
            # encode per committed partition, shared by every view. Off-loop:
            # the view scans are device dispatches the command path must not
            # share the loop with. fold_round never raises (a failing view
            # degrades alone); the plane's watermark advance above stands.
            await loop.run_in_executor(
                None, self._views.fold_round, logs, part_of, committed)
        elapsed = time.perf_counter() - t0
        self.stats["rounds"] += 1
        self.stats["folded_events"] += n_events
        if self.metrics is not None:
            self.metrics.resident_fold_round_timer.record_ms(elapsed * 1000.0)
        if self.profiler is not None:
            # the incremental-fold stage of the per-stage replay profile:
            # encode (host pack) reported separately, the umbrella `refresh`
            # covers encode+h2d+dispatch of the round (the h2d rides the
            # dispatch on this path — nothing is transferred ahead of it)
            self.profiler.record("encode", enc_s, kind="refresh")
            # the umbrella span carries its measured device legs so the
            # command anatomy decomposes it instead of binning the whole
            # round into `other` (the stage spans map by name; an umbrella
            # maps by attributes — anatomy claims one or the other)
            self.profiler.record(
                "refresh", elapsed, events=n_events, aggregates=len(ids),
                **{"leg.decode-ms": round(feed_s * 1000.0, 3),
                   "leg.dispatch-ms": round(
                       self._round_acc["dispatch_s"] * 1000.0, 3)})
        self._observe_round(n_events, feed_s, enc_s)
        self._record_gauges()
        if (self._faults is not None
                and self._faults.corrupt_point("corrupt.slab-row")):
            # corruption-to-page e2e: rot one live row AFTER the round
            # committed — the log stays right, the slab lies
            corrupted = self._corrupt_resident_row()
            if corrupted is not None and self.flight is not None:
                self.flight.record("fault.corrupt", site="corrupt.slab-row",
                                   aggregate=corrupted)
        return True

    def _slab_deleted(self) -> bool:
        if self._slab is None:
            return False
        leaf = next(iter(self._slab.values()))
        deleted = getattr(leaf, "is_deleted", None)
        return bool(deleted()) if callable(deleted) else False

    def _recover_if_slab_deleted(self) -> None:
        """Last-ditch donation recovery: a refresh dispatch that raised after
        donation consumed the slab left neither the old columns nor a result
        to rebind. Every resident/spilled row's provenance is the log, so the
        plane rebuilds EMPTY and re-anchors every tracked partition at 0 —
        the refresh loop refolds them from scratch exactly like a re-grant,
        which can never double-fold. No-op while the slab is live (the
        common failure path: the error fired before the dispatch consumed)."""
        if not self._slab_deleted():
            return
        for p in list(self._watermarks):
            self._purge_partition(p)
            self._watermarks[p] = 0
            self._anchor_gen[p] = self._anchor_gen.get(p, 0) + 1
        # defensive sweep: every row was consumed with the slab, so nothing
        # host-side may keep claiming residency or spill coverage
        self._dir.clear()
        self._spill.clear()
        self._lru.clear()
        self._agg_part.clear()
        self._free = list(range(self.capacity))
        self._slab = None
        self._ords = None
        self._ensure_device_state()
        logger.warning(
            "resident slab was consumed by a failed donated refresh "
            "dispatch; rebuilt empty and re-anchored %d partition(s) for "
            "refold", len(self._watermarks))

    def _observe_round(self, n_events: int, feed_s: float,
                       enc_s: float) -> None:
        """Device-observatory round close: the padding-waste gauges off the
        round's slot accounting and the ledger's ``round`` event. Always on —
        these are the instruments ROADMAP item 2's bucketing work is judged
        against, and a waste spike you only see under DEBUG never pages."""
        acc = self._round_acc
        dispatched, occupied = acc["dispatched"], acc["occupied"]
        waste = waste_ratio(dispatched, occupied)
        dispatch_us = acc["dispatch_s"] * 1e6
        deal = self._meshp.last_deal if self._meshp is not None else None
        lane_slots = acc["lane_slots"]
        if self.metrics is not None:
            m = self.metrics
            m.resident_round_events.record(n_events)
            m.resident_padding_waste_ratio.record(waste)
            m.resident_dispatch_occupancy.record(
                occupied / dispatched if dispatched else 0.0)
            m.resident_events_per_dispatch_us.record(
                n_events / dispatch_us if dispatch_us > 0 else 0.0)
            m.resident_shard_skew.record(shard_skew(deal))
            m.resident_bucket_dispatches.record(acc["programs"])
            m.resident_bucket_fill_ratio.record(
                acc["lanes"] / lane_slots if lane_slots else 0.0)
        if self.ledger is not None:
            causes, self._round_causes = self._round_causes, {}
            self.ledger.record_round(
                events=n_events, lanes=acc["lanes"], windows=acc["windows"],
                dispatched=dispatched, occupied=occupied,
                batch=acc["batch"], width=acc["width"],
                feed_us=feed_s * 1e6, encode_us=enc_s * 1e6,
                dispatch_us=dispatch_us, deal_sizes=deal,
                causes=causes or None, evictions=acc["evictions"],
                buckets=acc["buckets"] or None,
                bucket_table=len(self.bucket_table))

    def _decode_batches(self, batches: Dict[int, list]):
        """Executor half of a refresh round: deserialize + encode every
        record, grouping events per aggregate. Pure w.r.t. plane state —
        poison candidates are RETURNED (``{agg: partition}``) and applied on
        the loop, so the reader lane never observes a half-applied poison.

        With a batch deserializer wired (the native feed), the whole
        round's payloads decode in ONE call per partition; a batch that
        fails (a poisoned payload hiding inside) falls back to the
        per-event path, which locates and poisons the offender exactly as
        the pre-batch feed did."""
        logs: Dict[str, list] = {}
        part_of: Dict[str, int] = {}
        n_events = 0
        poisons: Dict[str, int] = {}
        poisoned = self._poisoned
        batch_decode = self.deserialize_events
        for p, recs in batches.items():
            pend = []
            for r in recs:
                key = r.key
                if (key is None or r.value is None or key in poisoned
                        or key in poisons):
                    continue
                pend.append((key, r.value))
            if not pend:
                continue
            events = None
            if batch_decode is not None:
                try:
                    events = batch_decode([v for _k, v in pend])
                    if len(events) != len(pend):  # pragma: no cover — a
                        events = None  # misbehaving custom batch decoder
                except Exception:  # noqa: BLE001 — per-event path poisons
                    events = None
            if events is not None:
                encode = self.encode_event
                schema_for = self.spec.registry.schema_for_cls
                for (key, _raw), ev in zip(pend, events):
                    if key in poisons:
                        continue
                    try:
                        if encode is not None:
                            ev = encode(ev)
                        schema_for(type(ev))
                    except Exception:  # noqa: BLE001 — per-agg degradation
                        poisons[key] = p
                        logs.pop(key, None)
                        continue
                    logs.setdefault(key, []).append(ev)
                    part_of[key] = p
                    n_events += 1
                continue
            for key, raw in pend:
                if key in poisons:
                    continue
                try:
                    ev = self._encode_event(raw)
                except Exception:  # noqa: BLE001 — per-aggregate degradation
                    poisons[key] = p
                    logs.pop(key, None)
                    continue
                logs.setdefault(key, []).append(ev)
                part_of[key] = p
                n_events += 1
        return logs, part_of, n_events, poisons

    def _encode_event(self, raw: bytes) -> Any:
        """Deserialize + producer-encode one record and check its type rides
        the replay schema; raises when it can't (callers poison the
        aggregate). Pure w.r.t. plane state — safe in the executor."""
        ev = self.deserialize_event(raw)
        if self.encode_event is not None:
            ev = self.encode_event(ev)
        self.spec.registry.schema_for_cls(type(ev))
        return ev

    def _encode_checked(self, agg_id: str, raw: bytes,
                        partition: int) -> Any:
        """:meth:`_encode_event`, or None when the aggregate cannot ride the
        tensor path. Events outside the replay schema (or failing the
        producer's encode) poison their aggregate: the plane stops tracking
        it — reads fall back to the host KV store, whose scalar fold handles
        every event type — instead of wedging the refresh loop."""
        if agg_id in self._poisoned:
            return None
        try:
            return self._encode_event(raw)
        except Exception:  # noqa: BLE001 — per-aggregate degradation
            self._poison(agg_id, partition)
            return None

    def _poison(self, agg_id: str, partition: int) -> None:
        self._poisoned[agg_id] = partition
        slot = self._dir.pop(agg_id, None)
        if slot is not None:
            self._free.append(slot)
        self._spill.pop(agg_id, None)
        self._lru.pop(agg_id, None)
        self._agg_part.pop(agg_id, None)
        if not self._warned_poison:
            self._warned_poison = True
            logger.warning(
                "aggregate %s emitted an event type outside the replay "
                "schema; it (and any later such aggregate) is served from "
                "the host store only", agg_id)

    def _encode_pack_group(self, event_logs: List[list]):
        """Executor half of one fold group: ragged encode + wire pack of
        every refresh plan. Pure — touches no plane state.

        Returns ``(b, plans)``. Each plan is one fused program dispatch
        shape ``(sel, lanes_b, width, wins)``: ``wins = [(packed, side,
        counts), ...]`` are the chained windows of the jit rectangle fold and
        ``sel`` indexes the plan's lanes back into the group.

        Dense dispatch is ONE plan covering the whole group at the
        ``_pow8(b) × _pow2(max_len)`` rectangle. Bucketed dispatch deals
        lanes into pow2 LENGTH buckets first, so a steady ragged round (many
        1–5-event lanes under one long tail) stops paying the long lane's
        width across every short lane — each occupied bucket dispatches its
        own ``_pow2(lanes, 8) × bucket_width`` grid and the union of scatters
        still lands on disjoint slots (every lane is in exactly one bucket),
        which is what keeps the fold byte-identical to the dense path."""
        b = len(event_logs)
        if self._refresh_dispatch == "dense":
            enc = encode_events(self.spec.registry, event_logs)
            # window width adapts to the batch's tail length (bucketed pow2
            # under the configured cap): a steady incremental round folds 1–5
            # events per aggregate, and scanning the full 512-step cold-start
            # window for it would make every refresh ~100x more device work
            # than its events
            width = min(self._window, _pow2(enc.max_len))
            sel = np.arange(b, dtype=np.int64)
            return b, [(sel, _pow8(b), width,
                        self._pack_windows(enc, _pow8(b), width))]
        lens = np.fromiter((len(ev) for ev in event_logs), dtype=np.int64,
                           count=b)
        deal: Dict[int, list] = {}
        for i in range(b):
            wb = min(self._window, _pow2(max(int(lens[i]), 1), 2))
            deal.setdefault(wb, []).append(i)
        plans = []
        for wb in sorted(deal):
            sel = np.asarray(deal[wb], dtype=np.int64)
            enc = encode_events(self.spec.registry,
                                [event_logs[i] for i in sel])
            lanes_b = _pow2(len(sel))
            plans.append((sel, lanes_b, wb,
                          self._pack_windows(enc, lanes_b, wb)))
        return b, plans

    def _pack_windows(self, enc, lanes_b: int, width: int):
        """Chained dense windows of one plan: ``[(packed, side, counts)]``."""
        wins = []
        for s in range(0, enc.max_len, width):
            e = min(s + width, enc.max_len)
            packed, side = self._wire.pack_window(
                enc.type_ids, enc.cols, s, e, width, lanes_b)
            counts = np.zeros((lanes_b,), dtype=np.int32)
            counts[:enc.batch_size] = np.clip(enc.lengths - s, 0, width)
            wins.append((packed, side, counts))
        return wins

    async def _fold_group(self, group: List[str], logs: Dict[str, list],
                          part_of: Dict[str, int],
                          gens: Dict[int, int]) -> None:
        """Admit + fold one ≤capacity group of aggregates' new events.

        Encode+pack AND the window dispatches run in the executor (an XLA
        dispatch/compile releases the GIL; keeping it off the loop keeps the
        command path's latency flat while the plane folds). Correctness
        across the awaits rests on DEFERRED COMMIT: slots are reserved but
        the directory, spill and watermarks only change after the fold
        lands — a concurrent read of an admitting aggregate is served from
        its (exact, pre-batch) spill row or falls back, never from a
        half-admitted slab row. A rebalance racing the fold is detected at
        commit (the partition left ``_watermarks``, or its anchor generation
        moved — a revoke→re-grant pair both purges AND re-anchors, so the
        stale fold must not land) and its aggregates' reservations are
        rolled back."""
        b, plans = await asyncio.get_running_loop().run_in_executor(
            None, self._encode_pack_group, [logs[a] for a in group])

        # -- sync: evict + reserve slots + per-lane admission rows ----------
        # reservation stays GROUP-level (one evict pass, one slot per lane);
        # each plan below slices its lanes' rows out of these flat arrays
        admit_ids = [a for a in group if a not in self._dir]
        short = len(admit_ids) - len(self._free)
        if short > 0:
            self._evict(short, protect=set(group))
        init = self.spec.init_state_tree()
        new_slots: Dict[str, int] = {}
        slot_of = np.empty((b,), dtype=np.int32)
        admit_lane = np.zeros((b,), dtype=bool)
        admit_ord_of = np.zeros((b,), dtype=np.int32)
        admit_val_of = {f.name: np.full((b,), init[f.name], dtype=f.dtype)
                        for f in self._fields}
        for i, agg in enumerate(group):
            s = self._dir.get(agg)
            if s is not None:
                slot_of[i] = s
                continue
            slot = self._free.pop()
            new_slots[agg] = slot
            slot_of[i] = slot
            admit_lane[i] = True
            spilled = self._spill.get(agg)  # peek — popped at commit
            if spilled is not None:
                row, ordinal = spilled
                admit_ord_of[i] = ordinal
                for k in admit_val_of:
                    admit_val_of[k][i] = row[k]

        # -- dispatch off-loop (reads keep serving from the pinned slab) ----
        # every lane is in exactly one plan, so each plan's admissions are
        # the group's admits restricted to its lanes and the plans' scatters
        # hit disjoint slots — dispatch order cannot change the fold
        slab, ords = self._slab, self._ords
        loop = asyncio.get_running_loop()
        acc = self._round_acc
        acc["lanes"] += b
        for plan in plans:
            slab, ords = await self._dispatch_plan(
                loop, plan, slab, ords, slot_of, admit_lane, admit_ord_of,
                admit_val_of, init)

        # -- sync commit: publish the folded slab + directory ---------------
        self._slab, self._ords = slab, ords
        for agg in group:
            p = part_of[agg]
            if (p not in self._watermarks      # revoked while the fold flew
                    or self._anchor_gen.get(p, 0) != gens.get(p, 0)):
                # ...or re-anchored (revoke→re-grant): either way this fold
                # used the OLD anchor's carry/events — roll the agg back
                slot = new_slots.pop(agg, None)
                if slot is not None:
                    self._free.append(slot)
                continue
            slot = new_slots.get(agg)
            if slot is not None:
                self._dir[agg] = slot
                self._spill.pop(agg, None)
            elif agg not in self._dir:
                continue  # purged mid-flight; stays purged
            self._agg_part[agg] = p
            self._touch(agg)

    async def _dispatch_plan(self, loop, plan, slab, ords,
                             slot_of: np.ndarray, admit_lane: np.ndarray,
                             admit_ord_of: np.ndarray,
                             admit_val_of: Dict[str, np.ndarray], init):
        """Dispatch one refresh plan's chained windows. Pads the plan's
        admission/lane arrays to its ``lanes_b`` bucket (so every window of a
        bucket shares ONE compiled signature — shape churn is what turns
        steady folds into compile storms), runs each window in the executor,
        and — when donation is on — republishes ``self._slab`` after every
        dispatch so readers re-pin live buffers (the consumed predecessor
        would raise on them; directory/spill commit stays deferred, so
        mid-round rows are folds of committed per-lane prefixes — valid
        bounded-stale states under the plane's consistency model)."""
        sel, lanes_b, width, wins = plan
        nb = len(sel)
        adm = sel[admit_lane[sel]]
        admit_idx = np.full((lanes_b,), self.capacity, dtype=np.int32)
        admit_idx[:len(adm)] = slot_of[adm]
        admit_ord = np.zeros((lanes_b,), dtype=np.int32)
        admit_ord[:len(adm)] = admit_ord_of[adm]
        admit_vals = {f.name: np.full((lanes_b,), init[f.name], dtype=f.dtype)
                      for f in self._fields}
        for k in admit_vals:
            admit_vals[k][:len(adm)] = admit_val_of[k][adm]
        lane_slots = np.full((lanes_b,), self.capacity, dtype=np.int32)
        lane_slots[:nb] = slot_of[sel]

        sig = ("refresh", lanes_b, width)
        prog = (self._meshp.refresh if self._mesh_local
                else self._refresh_prog)
        fresh = sig not in self._signatures
        self._signatures.add(sig)
        acc = self._round_acc
        acc["batch"] = lanes_b
        acc["width"] = width
        acc["programs"] += 1
        acc["lane_slots"] += lanes_b
        occupied = 0
        faults = self._faults
        donate = self._donate_refresh
        first = True
        noop_ord = np.zeros((lanes_b,), dtype=np.int32)
        noop_idx = np.full((lanes_b,), self.capacity, dtype=np.int32)
        noop_vals = None  # built once on the first later window
        for win in wins:
            if first:
                ai, av, ao = admit_idx, admit_vals, admit_ord
                first = False
            else:  # later windows: no-op admissions (all-scratch; the jitted
                # program never mutates its inputs, so one dict serves all)
                if noop_vals is None:
                    noop_vals = {
                        f.name: np.full((lanes_b,), init[f.name],
                                        dtype=f.dtype) for f in self._fields}
                ai, av, ao = noop_idx, noop_vals, noop_ord
            packed, side, counts = win
            run = functools.partial(prog, slab, ords, ai, av,
                                    ao, lane_slots, counts, packed, side)
            if faults is not None:
                # the stall-anatomy e2e's site, INSIDE the executor thunk so
                # an armed delay lands in the dispatch stage's measured time
                run = functools.partial(
                    (lambda f, thunk: (f.point("resident.refresh.dispatch"),
                                       thunk())[1]), faults, run)
            d0 = time.perf_counter()
            if self.profiler is None:
                slab, ords = await loop.run_in_executor(None, run)
            else:
                with self.profiler.stage("compile" if fresh else "dispatch",
                                         width=width, batch=lanes_b):
                    slab, ords = await loop.run_in_executor(None, run)
                fresh = False
            if donate:
                self._slab, self._ords = slab, ords
            # padding-waste accounting: the program always runs the full
            # lanes_b × width slot grid; counts carries the occupied slots
            acc["windows"] += 1
            acc["dispatched"] += lanes_b * width
            acc["occupied"] += int(counts.sum())
            occupied += int(counts.sum())
            acc["dispatch_s"] += time.perf_counter() - d0
        acc["buckets"].append({
            "width": width, "lanes_b": lanes_b, "lanes": nb,
            "windows": len(wins), "dispatched": lanes_b * width * len(wins),
            "occupied": occupied})
        return slab, ords

    def _touch(self, agg_id: str) -> None:
        self._tick += 1
        self._lru[agg_id] = self._tick

    def _evict(self, n: int, protect: set) -> None:
        """Pull the n least-recently-touched unprotected rows to the host
        spill and free their slots (the one small d2h the plane ever does
        outside reads; a spilled row re-admits at its exact fold point)."""
        victims = sorted((a for a in self._dir if a not in protect),
                         key=lambda a: self._lru.get(a, 0))[:n]
        if len(victims) < n:
            raise RuntimeError(
                f"resident slab cannot hold the refresh batch: need {n} more "
                f"slots, only {len(victims)} evictable "
                f"(capacity {self.capacity})")
        idx = np.fromiter((self._dir[v] for v in victims), dtype=np.int32,
                          count=len(victims))
        rows, ords = self._pull_positions(self._slab, idx, ords=self._ords)
        for j, v in enumerate(victims):
            self._spill[v] = ({k: rows[k][j] for k in rows}, int(ords[j]))
            self._free.append(self._dir.pop(v))
            self._lru.pop(v, None)
        self.stats["evictions"] += len(victims)
        self._round_acc["evictions"] += len(victims)
        if self.metrics is not None:
            self.metrics.resident_evictions.record(len(victims))
        if self.flight is not None:
            self.flight.record("resident.evict", count=len(victims),
                               resident=len(self._dir),
                               spilled=len(self._spill))
        if self.ledger is not None:
            self.ledger.record_evict(len(victims), resident=len(self._dir),
                                     cause="capacity")

    # -- pulls / decode -----------------------------------------------------------------

    def _pull_positions(self, slab, positions: np.ndarray, ords=None):
        """Wide (u32) gather of ``positions`` rows + one fetch; returns
        ``({field: np[k]}, ordinals np[k])`` decoded to schema dtypes."""
        if ords is None:
            import jax.numpy as jnp

            ords = jnp.zeros((int(np.max(positions, initial=0)) + 1,),
                             dtype=jnp.int32)
        k = len(positions)
        k_b = _pow2(max(k, 1))
        idx = np.zeros((k_b,), dtype=np.int32)
        idx[:k] = positions
        mat, o = self._gather_wide(slab, ords, idx)
        mat = np.asarray(mat)  # the fetch barrier
        o = np.asarray(o)
        return self._decode_wide(mat, k), o[:k]

    def _decode_wide(self, mat: np.ndarray, k: int) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        row = 0
        for f, w in zip(self._fields, self._wide_words):
            dev = self._dev_dts[f.name]
            dt = self._dtypes[f.name]  # widen back to the schema dtype
            raw = mat[row: row + w, :k]
            row += w
            if np.issubdtype(dev, np.floating) and dev.itemsize < 4:
                out[f.name] = raw[0].view(np.float32).astype(dt)
            elif dev == np.bool_ or dev.itemsize < 4:
                out[f.name] = raw[0].astype(dt)
            elif w > 1:  # w u32 word-rows -> (k, w) contiguous -> one column
                out[f.name] = np.ascontiguousarray(raw.T).view(dev)[:, 0]
            else:
                out[f.name] = raw[0].view(dev).astype(dt)
        return out

    def _decode_narrow(self, buf: np.ndarray, k: int, k_b: int
                       ) -> Optional[Dict[str, np.ndarray]]:
        """Decode the u16 gather buffer; None when a column overflowed (the
        caller refetches wide — exactness never depends on the guess)."""
        nf = len(self._fields)
        if not buf[nf * k_b:].all():
            return None
        out: Dict[str, np.ndarray] = {}
        for i, f in enumerate(self._fields):
            dt = self._dtypes[f.name]
            raw = buf[i * k_b: i * k_b + k]
            if dt == np.bool_:
                out[f.name] = raw.astype(dt)
            elif np.issubdtype(dt, np.signedinteger):
                out[f.name] = raw.view(np.int16).astype(dt)
            else:
                out[f.name] = raw.astype(dt)
        return out

    # -- read path ----------------------------------------------------------------------

    def lag_records(self) -> int:
        """Σ over assigned partitions of (end offset − fold watermark)."""
        return sum(self.partition_lag(p) for p in self.partitions)

    def partition_lag(self, p: int) -> int:
        return max(self.log.end_offset(self.events_topic, p)
                   - self._watermarks.get(p, 0), 0)

    def _ends_sync(self, parts: Sequence[int]) -> Dict[int, int]:
        return {p: self.log.end_offset(self.events_topic, p) for p in parts}

    async def _ends_for(self, parts: Sequence[int]) -> Dict[int, int]:
        """Live end-offset view for a read's freshness check. Local logs
        answer from memory/a stat; a remote (broker) log turns each call
        into a blocking RPC, so there the view rides the executor — the
        read path shares its event loop with the command path."""
        parts = [p for p in parts if p in self._watermarks]
        if not parts:
            return {}
        if self._remote_log:
            return await asyncio.get_running_loop().run_in_executor(
                None, self._ends_sync, parts)
        return self._ends_sync(parts)

    def _fresh_enough(self, p: Optional[int], require_current: bool,
                      ends: Optional[Mapping[int, int]] = None) -> bool:
        if p is None or p not in self._watermarks:
            return False
        bound = 0 if require_current else self.max_lag
        if ends is not None:
            end = ends.get(p)
            if end is None:
                return False
            return max(end - self._watermarks.get(p, 0), 0) <= bound
        return self.partition_lag(p) <= bound

    #: fallback cause -> the EngineMetrics counter carrying its split
    _FALLBACK_CAUSE_SENSORS = {
        "lag-exceeded": "resident_fallbacks_lag",
        "lane-error": "resident_fallbacks_lane_error",
        "unschema-poison": "resident_fallbacks_poison",
        "untracked": "resident_fallbacks_untracked",
    }

    def _record_fallback(self, n: int = 1, cause: str = "untracked") -> None:
        """One or more reads fell back to the host store, and WHY:
        ``lag-exceeded`` (the partition's fold watermark is too stale for the
        read's bound), ``lane-error`` (the gather batch failed on device or
        in decode), ``unschema-poison`` (the aggregate emitted an event
        outside the replay schema and is host-served for good), ``untracked``
        (not resident/spilled, revoked, or the plane is stopped/unseeded).
        The flat total keeps its name; the splits ride
        ``surge.replay.resident.fallback-reads.<cause>``."""
        self.stats["fallbacks"] += n
        self.fallback_causes[cause] = self.fallback_causes.get(cause, 0) + n
        self._round_causes[cause] = self._round_causes.get(cause, 0) + n
        if self.metrics is not None:
            self.metrics.resident_fallbacks.record(n)
            getattr(self.metrics,
                    self._FALLBACK_CAUSE_SENSORS[cause]).record(n)

    async def read_state(self, aggregate_id: str, *,
                         require_current: bool = False
                         ) -> Tuple[bool, Any]:
        """Read one aggregate's state: ``(hit, state)``. A miss means the
        caller must fall back to the host KV store — not resident, revoked,
        poisoned, or the partition's fold watermark is too stale.

        ``require_current=True`` demands lag 0 on the aggregate's partition —
        the entity-init contract (processing a command on bounded-stale state
        would fork the aggregate); the default tolerates
        ``surge.replay.resident.max-lag-records`` (read-side projections)."""
        if self._stopped or not self._seeded:
            self._record_fallback()
            return (False, None)
        p = self._agg_part.get(aggregate_id)
        if p is None or p not in self._watermarks:
            self._record_fallback(cause="unschema-poison"
                                  if aggregate_id in self._poisoned
                                  else "untracked")
            return (False, None)
        ends = await self._ends_for((p,))
        if not self._fresh_enough(p, require_current, ends):
            self._record_fallback(cause="lag-exceeded")
            return (False, None)
        spilled = self._spill.get(aggregate_id)
        if spilled is not None:
            row, _ord = spilled
            return (True, self._state_of(aggregate_id,
                                         {k: np.asarray(v)
                                          for k, v in row.items()}, 0))
        if aggregate_id not in self._dir:
            self._record_fallback()
            return (False, None)
        fut = asyncio.get_running_loop().create_future()
        if not self._pending:
            self._pending_t0 = time.perf_counter()
        self._pending.append((aggregate_id, fut))
        self._touch(aggregate_id)
        self._kick_drain()
        return await fut

    async def read_bytes(self, aggregate_id: str, *,
                         require_current: bool = False
                         ) -> Tuple[bool, Optional[bytes]]:
        """:meth:`read_state` + the restore serialize chain — byte-identical
        to what the host KV store holds for the same fold point."""
        hit, state = await self.read_state(aggregate_id,
                                           require_current=require_current)
        if not hit:
            return (False, None)
        return (True, self.serialize_state(aggregate_id, state))

    async def read_many(self, aggregate_ids: Sequence[str], *,
                        require_current: bool = False) -> Dict[str, Any]:
        """Bulk read: ``{aggregate_id: state}`` for every id the plane can
        serve; misses (not tracked, stale, revoked, poisoned) are OMITTED —
        the caller overlays the host store. The whole call rides the gather
        lane as ONE queued item: a single future, one device gather shared
        with every concurrent reader, and a batch-materialized decode — the
        per-id asyncio machinery of :meth:`read_state` is paid once per call,
        which is what makes read-side projections cheaper than per-key host
        lookups at high concurrency."""
        if self._stopped or not self._seeded:
            self._record_fallback(len(aggregate_ids))
            return {}
        # freshness varies only by PARTITION: resolve each assigned
        # partition's lag once per call, not once per id. When EVERY assigned
        # partition is fresh (the steady state), the per-id loop disappears
        # entirely — untracked ids miss in the drain and fall back there,
        # exactly as a per-id check would have concluded.
        ends = await self._ends_for(self.partitions)
        if all(self._fresh_enough(p, require_current, ends)
               for p in self.partitions):
            ok: Sequence[str] = tuple(aggregate_ids)
        else:
            fresh: Dict[Optional[int], bool] = {None: False}
            ok_list: List[str] = []
            stale = 0
            part = self._agg_part
            for agg in aggregate_ids:
                p = part.get(agg)
                f = fresh.get(p)
                if f is None:
                    f = fresh[p] = self._fresh_enough(p, require_current,
                                                      ends)
                if f:
                    ok_list.append(agg)
                else:
                    stale += 1
            if stale:
                self._record_fallback(stale, cause="lag-exceeded")
            ok = ok_list
        if not ok:
            return {}
        fut = asyncio.get_running_loop().create_future()
        if not self._pending:
            self._pending_t0 = time.perf_counter()
        self._pending.append((ok, fut))
        self._kick_drain()
        return await fut

    async def project(self, aggregate_ids: Sequence[str], *,
                      require_current: bool = False) -> Dict[str, Any]:
        """Batched read-side projection — alias of :meth:`read_many`."""
        return await self.read_many(aggregate_ids,
                                    require_current=require_current)

    def _kick_drain(self) -> None:
        if not self._draining:
            self._draining = True
            # retained + reaped: if the drain task were GC'd mid-flight,
            # _draining would stay True forever and the gather lane would
            # wedge; an escaping failure logs instead of rotting
            spawn_reaped(self._drain_tasks, self._drain_reads(),
                         "resident gather-lane drain")

    async def _drain_reads(self) -> None:
        """The gather lane: coalesce every queued read — single ``read_state``
        futures and whole ``read_many`` groups alike — into one device gather
        + a single fetch-barriered pull (u16 wire when the schema allows)."""
        loop = asyncio.get_running_loop()
        try:
            while self._pending:
                batch, self._pending = self._pending, []
                # coalesce wait: first enqueue of this batch → drain start
                # (the gather-coalesce leg of the read's device anatomy)
                t0, self._pending_t0 = self._pending_t0, None
                wait_s = max(time.perf_counter() - t0, 0.0) if t0 else 0.0
                try:
                    await self._drain_batch(loop, batch, wait_s)
                except Exception:  # noqa: BLE001 — the plane is an optimization:
                    # a device/decode failure must fail the batch over to the
                    # host KV store, never strand its futures (an entity init
                    # awaiting one would hang forever, commands queuing behind
                    # it — the exact case the host fallback exists for)
                    logger.exception(
                        "resident gather batch failed; failing %d read(s) "
                        "over to the host store", len(batch))
                    try:
                        self.on_signal("surge.replay.resident.gather-error",
                                       "error")
                    except Exception:  # noqa: BLE001
                        logger.exception("on_signal failed")
                    n = 0
                    for target, fut in batch:
                        if not fut.done():
                            n += 1
                            fut.set_result((False, None)
                                           if isinstance(target, str) else {})
                    if n:
                        self._record_fallback(n, cause="lane-error")
        finally:
            self._draining = False

    async def _drain_batch(self, loop, batch, wait_s: float = 0.0) -> None:
        # snapshot slots atomically on the loop; ids evicted since
        # enqueue are served from their (exact) spill rows instead.
        # refs per id: gather position, ("spill", row) or None=miss;
        # refs is None for the common all-resident call, whose gather
        # rows are the contiguous range [start, start+len(ids)) —
        # results then assemble via one C-speed dict(zip(...))
        calls = []
        gather_ids: List[str] = []
        slots: List[int] = []
        dir_get, spill_get = self._dir.get, self._spill.get
        for target, fut in batch:
            if fut.done():
                continue
            single = isinstance(target, str)
            ids = (target,) if single else target
            start = len(slots)
            refs: Optional[List[Any]] = None
            looked = [dir_get(a) for a in ids]
            if None not in looked:  # all resident: pure C-speed path
                slots.extend(looked)
                gather_ids.extend(ids)
            else:
                refs = []
                for agg, slot in zip(ids, looked):
                    if slot is not None:
                        refs.append(len(slots))
                        slots.append(slot)
                        gather_ids.append(agg)
                    else:
                        spilled = spill_get(agg)
                        refs.append(("spill", spilled[0])
                                    if spilled is not None else None)
            calls.append((fut, single, ids, refs, start))
        states: list = []
        if slots:
            k = len(slots)
            k_b = _pow2(k)
            # pad with the first LIVE slot, not the scratch row: the
            # u16 fit flags scan every gathered value, and scratch
            # garbage would force the wide refetch on every read
            idx = np.full((k_b,), slots[0], dtype=np.int32)
            idx[:k] = slots
            off_loop = self._fetch_off_loop
            rows: Optional[Dict[str, np.ndarray]] = None
            # device-leg clocks for the observatory: dispatch (gather program
            # call), fetch-barrier (the d2h asarray), decode (buffer → rows →
            # domain states) — a u16 overflow refetch accumulates both passes
            disp_s = fetch_s = dec_s = 0.0
            # a DONATED refresh window may consume the pinned slab between
            # the dispatch below and its fetch (the fold runs in the
            # executor concurrently) — the deleted-buffer error re-pins the
            # republished slab and retries; a persistent failure falls
            # through to the gather lane's host failover
            for attempt in range(3):
                # pin: a fold may replace self._slab/_ords mid-drain
                slab, s_ords = self._slab, self._ords
                rows = None
                try:
                    t = time.perf_counter()
                    if self._gather_narrow is not None:
                        buf = self._gather_narrow(slab, idx)  # dispatch
                        disp_s += time.perf_counter() - t
                        t = time.perf_counter()
                        host = (await loop.run_in_executor(
                            None, np.asarray, buf)
                            if off_loop else np.asarray(buf))
                        fetch_s += time.perf_counter() - t
                        t = time.perf_counter()
                        rows = self._decode_narrow(host, k, k_b)
                        dec_s += time.perf_counter() - t
                    if rows is None:  # wide schema, or a u16 overflow refetch
                        t = time.perf_counter()
                        mat, _ = self._gather_wide(slab, s_ords, idx)
                        disp_s += time.perf_counter() - t
                        t = time.perf_counter()
                        host = (await loop.run_in_executor(
                            None, np.asarray, mat)
                            if off_loop else np.asarray(mat))
                        fetch_s += time.perf_counter() - t
                        t = time.perf_counter()
                        rows = self._decode_wide(host, k)
                        dec_s += time.perf_counter() - t
                    break
                except RuntimeError as exc:
                    if attempt == 2 or "delet" not in str(exc).lower():
                        raise
                    await asyncio.sleep(0.001)
            t = time.perf_counter()
            states = self._states_of_batch(gather_ids, rows, k)
            dec_s += time.perf_counter() - t
            # one batched LRU touch for every gathered hit (read_many
            # skips per-id touching on its fast path)
            self._tick += 1
            self._lru.update(dict.fromkeys(gather_ids, self._tick))
            self.stats["gathers"] += 1
            self.stats["gathered_rows"] += k
            if self.metrics is not None:
                self.metrics.resident_gather_batch.record(k)
            if self.ledger is not None:
                self.ledger.record_gather(
                    reads=len(calls), rows=k, wait_us=wait_s * 1e6,
                    dispatch_us=disp_s * 1e6, fetch_us=fetch_s * 1e6,
                    decode_us=dec_s * 1e6)
            if self.tracer is not None:
                self._emit_gather_span(wait_s, disp_s, fetch_s, dec_s, k)
        for fut, single, ids, refs, start in calls:
            if fut.done():
                continue
            try:
                if refs is None:  # all resident, contiguous rows
                    if single:
                        fut.set_result((True, states[start]))
                    else:
                        fut.set_result(dict(zip(
                            ids, states[start:start + len(ids)])))
                    continue
                out: Dict[str, Any] = {}
                misses = poisons = 0
                for agg, ref in zip(ids, refs):
                    if ref is None:
                        if agg in self._poisoned:
                            poisons += 1
                        else:
                            misses += 1
                    elif isinstance(ref, int):
                        out[agg] = states[ref]
                    else:  # exact-fold-point spill row
                        out[agg] = self._state_of(
                            agg, {k: np.asarray(v)
                                  for k, v in ref[1].items()}, 0)
                if misses:
                    self._record_fallback(misses)
                if poisons:
                    self._record_fallback(poisons, cause="unschema-poison")
                if single:
                    agg = ids[0]
                    fut.set_result((agg in out, out.get(agg)))
                else:
                    fut.set_result(out)
            except Exception as exc:  # noqa: BLE001 — decode bug
                if not fut.done():
                    fut.set_exception(exc)

    def _emit_gather_span(self, wait_s: float, disp_s: float, fetch_s: float,
                          dec_s: float, rows: int) -> None:
        """One retro-dated ``resident.gather`` span per drained batch, its
        device legs as ``leg.*-ms`` attributes — the read-side fold anatomy.
        BOTH clocks are retro-dated to the measured interval (the profiler's
        span discipline): the tail sampler's keep decision and the anatomy
        placement read the mono pair first, so a wall-only retro-date would
        make a stalled 2 s gather look like a 0 ms span."""
        total = wait_s + disp_s + fetch_s + dec_s
        span = self.tracer.start_span("resident.gather")
        span.start_time = time.time() - total
        span.start_mono = time.monotonic() - total
        try:
            span.set_attribute("leg.coalesce-ms", round(wait_s * 1000.0, 3))
            span.set_attribute("leg.dispatch-ms", round(disp_s * 1000.0, 3))
            span.set_attribute("leg.fetch-ms", round(fetch_s * 1000.0, 3))
            span.set_attribute("leg.decode-ms", round(dec_s * 1000.0, 3))
            span.set_attribute("rows", rows)
        finally:
            span.finish()  # unconditional: a leaked span pins its trace

    def _state_of(self, aggregate_id: str, record: Mapping[str, Any],
                  _j: int) -> Any:
        """Tensor row → domain state, through the exact restore chain
        (from_record → aggregate-id reattach → decode_state)."""
        from surge_tpu.store.restore import _with_aggregate_id

        state = self.spec.registry.state.from_record(record)
        state = _with_aggregate_id(state, aggregate_id)
        if self.decode_state is not None:
            state = self.decode_state(aggregate_id, state)
        return state

    # -- introspection ------------------------------------------------------------------

    def occupancy(self) -> int:
        return len(self._dir)

    def resident_ids(self) -> List[str]:
        return sorted(self._dir)

    def _record_gauges(self) -> None:
        if self.metrics is None:
            return
        self.metrics.resident_occupancy.record(len(self._dir))
        # gauge lag from the last poll's end offsets — a live end_offset per
        # partition here would put the FileLog's stat() back on the loop
        ends = self._last_ends
        self.metrics.resident_fold_lag.record(sum(
            max(ends.get(p, 0) - self._watermarks.get(p, 0), 0)
            for p in self.partitions))

    def snapshot_states(self) -> Dict[str, Any]:
        """Host snapshot of every tracked aggregate's state (resident + spill)
        — the golden-test surface; one wide gather for the resident rows."""
        out: Dict[str, Any] = {}
        ids = list(self._dir)
        if ids:
            idx = np.fromiter((self._dir[a] for a in ids), dtype=np.int32,
                              count=len(ids))
            rows, _ = self._pull_positions(self._slab, idx, ords=self._ords)
            for j, agg in enumerate(ids):
                out[agg] = self._state_of(
                    agg, {k: rows[k][j] for k in rows}, j)
        for agg, (row, _ord) in self._spill.items():
            out[agg] = self._state_of(
                agg, {k: np.asarray(v) for k, v in row.items()}, 0)
        return out
