"""Replica driver — the host actor owning one device-resident CRDT state.

TPU-native counterpart of ``DeltaCrdt.CausalCrdt`` (``causal_crdt.ex``):
where the reference serialises every state transition through a GenServer
mailbox, this driver serialises through a lock and issues **batched,
jit-compiled kernel calls** against the device state (the bucket-binned
engine, :mod:`delta_crdt_ex_tpu.models.binned`). Capabilities map 1:1
(SURVEY §2.2):

- mutate (sync) / mutate_async → queued mutation batch, flushed before
  any read/sync (mailbox-order semantics of ``handle_call``/``handle_cast``,
  ``causal_crdt.ex:192-198``);
- periodic anti-entropy with ≤1 in-flight sync per neighbour, cleared by
  acks (``outstanding_syncs``, ``causal_crdt.ex:25,264-287,406-412``);
- neighbour monitoring with pruning on death (``:127-145,291-314``);
- ``on_diffs`` change feed with the reference's exact emission rules
  (no-op writes are silent, a ``nil`` value reads as a remove diff —
  ``delta_subscriber_test.exs:23-27``);
- pluggable storage with crash-rehydrate keeping the node id (dot
  continuity, ``causal_crdt.ex:220-231``);
- telemetry ``(delta_crdt, sync, done)`` on every merge (``:396-398``).

Capacity is tiered: kernels signal overflow via ``ok``/``need_*`` flags
and the driver compacts or grows a tier and retries — the only
data-dependent control flow, and it lives on the host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import secrets
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from delta_crdt_ex_tpu.utils.hashing import (
    key_hash64,
    key_hash64_batch,
    value_hash32,
    value_hash32_batch,
)
from delta_crdt_ex_tpu.models.binned import BinnedStore, pow2_tier, pow4_tier
from delta_crdt_ex_tpu.models.binned_map import BinnedAWLWWMap, CtxGapError
from delta_crdt_ex_tpu.ops.apply import OP_ADD, OP_CLEAR, OP_PAD, OP_REMOVE
from delta_crdt_ex_tpu.runtime import (
    metrics as metrics_mod,
    sync as sync_proto,
    telemetry,
    tracing,
    transition,
    treesync,
)
from delta_crdt_ex_tpu.runtime.clock import Clock
from delta_crdt_ex_tpu.runtime.storage import (
    FileStorage,
    Snapshot,
    Storage,
    name_key,
    require_layout,
)
from delta_crdt_ex_tpu.runtime.transport import (
    Down,
    LocalTransport,
    default_transport,
    forward_fleet_entries,
)
from delta_crdt_ex_tpu.runtime.wal import ReplayClock, WalLog
from delta_crdt_ex_tpu.utils import transfers
from delta_crdt_ex_tpu.utils.faults import faultpoint

logger = logging.getLogger("delta_crdt_ex_tpu")

_SLICE_COLUMNS = ("key", "valh", "ts", "node", "ctr", "alive")

# -- audited device↔host transfer sites (crdtlint TRANSFER001) --------
# Every crossing on the replica paths goes through one of these, so the
# ledger (utils/transfers) prices each boundary and the bench gates can
# pin steady-state per-round crossing counts. Labels are the ledger /
# crdt_transfers_total{site=...} keys — rename = dashboard break.
_TR_DIGEST_LEVELS = transfers.register("replica.digest_levels")
_TR_STATE_PLACE = transfers.register("replica.state_place")
_TR_SNAPSHOT = transfers.register("replica.snapshot")
_TR_READ_KEYS = transfers.register("replica.read_keys")
_TR_APPLY_COUNTS = transfers.register("replica.apply_counts")
_TR_INGEST_COUNTS = transfers.register("replica.ingest_counts")
_TR_DIFF_WINNERS = transfers.register("replica.diff_winners")
_TR_WINNER_ALL = transfers.register("replica.winner_all")
_TR_WINNER_ROWS = transfers.register("replica.winner_rows")
_TR_CANONICAL_STATE = transfers.register("replica.canonical_state")
_TR_OWN_CTR_CACHE = transfers.register("replica.own_ctr_cache")
_TR_RELAY_ACCOUNTING = transfers.register("replica.relay_accounting")
_TR_SLICE_PAYLOAD_DOTS = transfers.register("replica.slice_payload_dots")
_TR_SLICE_WIRE = transfers.register("replica.slice_wire")
_TR_SLICE_PLACE = transfers.register("replica.slice_place")
_TR_WAL_ENTRIES = transfers.register("replica.wal_entries")
_TR_GC_SCAN = transfers.register("replica.gc_scan")
_TR_DRAIN_ACCOUNTING = transfers.register("replica.drain_accounting")


def _pow2(n: int, floor: int = 8) -> int:
    return pow2_tier(n, floor)


#: wire tier (x4 steps): every data-dependent slice/query shape goes
#: through this so the distinct-compile count stays small (pow4_tier doc)
def _wire(n: int, floor: int = 8) -> int:
    return pow4_tier(n, floor)


class _LazyLevels:
    """Digest-tree levels, device-resident, host-materialised per level
    on first access.

    The sync walk usually terminates in the top few levels (equal trees
    compare only the root block), so copying every level to host on each
    state change — ~128 KB at L=2^14 — paid for readbacks the walk never
    looked at. Indexing ``tree[level]`` now transfers just that level,
    once, caching the numpy array for the walk's repeat visits.
    """

    __slots__ = ("_dev", "_host")

    def __init__(self, levels: list) -> None:
        self._dev = levels
        self._host: list[np.ndarray | None] = [None] * len(levels)

    def __len__(self) -> int:
        return len(self._dev)

    def __getitem__(self, level: int) -> np.ndarray:
        h = self._host[level]
        if h is None:
            h = self._host[level] = np.asarray(
                _TR_DIGEST_LEVELS.get(self._dev[level])
            )
        return h


class _StackedLevels:
    """Digest-tree levels for a whole fleet egress bucket, built by ONE
    vmapped dispatch (``transition.fleet_tree_from_leaves``): level j is
    ``[N, 2^j]``. Host materialisation is per LEVEL and shared by every
    member lane — the opener path prefetches the top
    ``levels_per_round`` levels (tiny: 2^0..2^8 digests per lane) in
    one batched transfer, and a deep receive-side walk by any one
    member materialises that level for all of them."""

    __slots__ = ("_dev", "_host")

    def __init__(self, levels: list) -> None:
        self._dev = levels
        self._host: list[np.ndarray | None] = [None] * len(levels)

    def __len__(self) -> int:
        return len(self._dev)

    def prefetch(self, upto: int) -> None:
        """Materialise levels ``0..upto`` (inclusive, clamped) with one
        batched device fetch — the opener's whole working set."""
        upto = min(upto, len(self._dev) - 1)
        want = [j for j in range(upto + 1) if self._host[j] is None]
        if not want:
            return
        got = _TR_DIGEST_LEVELS.get([self._dev[j] for j in want])
        for j, arr in zip(want, got):
            self._host[j] = np.asarray(arr)

    def lane_level(self, level: int, lane: int) -> np.ndarray:
        h = self._host[level]
        if h is None:
            h = self._host[level] = np.asarray(
                _TR_DIGEST_LEVELS.get(self._dev[level])
            )
        return h[lane]


class _LaneLevels:
    """One member's view of a :class:`_StackedLevels` — duck-compatible
    with :class:`_LazyLevels` (the walk and ``make_blocks`` only index
    and ``len()``), bit-identical to the member's solo tree."""

    __slots__ = ("_stack", "_lane")

    def __init__(self, stack: _StackedLevels, lane: int) -> None:
        self._stack = stack
        self._lane = lane

    def __len__(self) -> int:
        return len(self._stack)

    def __getitem__(self, level: int) -> np.ndarray:
        return self._stack.lane_level(level, self._lane)


class _PushJob:
    """One planned eager-push extraction (``_eager_jobs``): the rows /
    interval bounds to gather and the peers the resulting slice fans
    out to. Planning, extraction, and emission are separate steps so
    the fleet can run many members' extractions as ONE vmapped
    dispatch between a member's plan and its emit — the slice is a pure
    function of ``(state snapshot, rows, lo)``, so batched and solo
    extraction are interchangeable bit-for-bit."""

    __slots__ = ("kind", "rows", "lo", "pending", "peers", "advance", "new_cursor")

    def __init__(self, kind, rows, lo, pending, peers, advance=None, new_cursor=0):
        self.kind = kind  # "delta" (own-interval) | "rows" (kill-touched)
        self.rows = rows  # int32[U] bucket rows, -1 pads (wire tier)
        self.lo = lo  # uint32[U] interval lower bounds ("delta" only)
        self.pending = pending  # int64-able real bucket indices
        self.peers = peers  # "delta": [(addr, cursor array)]; "rows": [addr]
        self.advance = advance  # "delta": own counters to advance cursors to
        self.new_cursor = new_cursor  # "rows": touch-seq cursor after this push


class Replica:
    def __init__(
        self,
        crdt_module=BinnedAWLWWMap,
        *,
        name: Any = None,
        node_id: int | None = None,
        sync_interval: float = 0.2,
        max_sync_size: int | str = 200,
        on_diffs: Callable | tuple | None = None,
        storage_module: Storage | None = None,
        storage_mode: str = "every_op",
        wal_dir: str | None = None,
        fsync_mode: str = "batch",
        segment_bytes: int = 4 << 20,
        compact_every: int = 1024,
        transport: LocalTransport | None = None,
        clock: Clock | None = None,
        capacity: int = 1024,
        replica_capacity: int = 8,
        tree_depth: int = 12,
        levels_per_round: int = 8,
        sync_timeout: float | None = None,
        checkpoint_interval: float = 5.0,
        eager_deltas: bool = True,
        ingress_coalesce: bool = True,
        max_coalesce: int = 16,
        ingress_batch: int = 256,
        membership_compaction: bool = True,
        membership_retain: int | None = None,
        log_shipping: bool = True,
        catchup_chunk_rows: int = 1024,
        catchup_suffix_ratio: float = 4.0,
        gc_interval_ops: int = 4096,
        tree_gossip: bool = False,
        tree_fanout: int = 8,
        tree_seed: int = 0,
        tree_degrade_ratio: float = 0.25,
        tree_group=None,
        obs=None,
        flight_dump_path: str | None = None,
        device=None,
    ):
        # max_sync_size validation (reference raises, causal_crdt.ex:52-62)
        if max_sync_size == "infinite":
            self.max_sync_size: float = float("inf")
        elif isinstance(max_sync_size, int) and not isinstance(max_sync_size, bool) and max_sync_size > 0:
            self.max_sync_size = max_sync_size
        else:
            raise ValueError(f"{max_sync_size!r} is not a valid max_sync_size")

        self.model = crdt_module
        self.name = name if name is not None else f"crdt-{secrets.token_hex(6)}"
        self.sync_interval = sync_interval
        self.on_diffs = on_diffs
        self.storage_module = storage_module
        self.storage_mode = storage_mode
        self.checkpoint_interval = checkpoint_interval
        #: durable delta log (runtime/wal.py): with a ``wal_dir``,
        #: ``every_op`` durability becomes an O(delta) record append
        #: instead of the reference's O(state) full-image write, and
        #: snapshots become compaction checkpoints
        self.compact_every = int(compact_every)
        self._wal: WalLog | None = None
        self._wal_unc = 0  # records appended since the last compaction
        self._replaying = False
        if wal_dir is not None:
            if self.storage_module is None:
                # compaction checkpoints default to living beside the
                # log — fsynced, because compaction DELETES the fsynced
                # records the snapshot supersedes (an unflushed
                # checkpoint would trade durable records for page cache)
                self.storage_module = FileStorage(
                    os.path.join(wal_dir, "snapshots"),
                    fsync=fsync_mode != "none",
                )
            elif getattr(self.storage_module, "fsync", None) is False:
                # compaction DELETES fsynced records once a snapshot
                # covers them — through a non-fsynced store that trades
                # durable records for page cache on power loss. (A store
                # with NO fsync attribute is treated as volatile:
                # _compact_wal then keeps segments instead of deleting.)
                logger.warning(
                    "WAL compaction checkpoints for %r go through a "
                    "non-fsynced storage module; pass "
                    "FileStorage(..., fsync=True) for machine-crash "
                    "durability",
                    self.name,
                )
            self._wal = WalLog(
                os.path.join(wal_dir, f"replica_{name_key(self.name)}"),
                fsync_mode=fsync_mode,
                segment_bytes=segment_bytes,
            )
        self.tree_depth = tree_depth
        self.num_buckets = 1 << tree_depth
        self.levels_per_round = levels_per_round
        self.transport = transport or default_transport()
        self.clock = clock or Clock()
        # The reference's outstanding_syncs slot is cleared only by an ack
        # or a DOWN (causal_crdt.ex:82-84,127-145) — safe on the BEAM's
        # reliable links, but a lost message would stall the edge forever
        # on a lossy transport. In-flight slots therefore expire.
        self.sync_timeout = (
            sync_timeout if sync_timeout is not None else max(10 * sync_interval, 2.0)
        )

        #: observability plane (ISSUE 9): ``obs=True`` resolves to the
        #: process-wide plane, an :class:`~delta_crdt_ex_tpu.runtime.
        #: metrics.Observability` is used as-is, ``None``/``False``
        #: disables it — the ``has_handlers`` guards then keep disabled
        #: telemetry at a lock check on every hot path. The flight
        #: recorder is the per-replica black box (bounded ring of
        #: structured events, dumped on :meth:`crash`); the lag tracer
        #: samples local commits so peers' watermark advances yield
        #: per-peer convergence-lag histograms with zero wire changes.
        self._obs = metrics_mod.resolve_obs(obs)
        self.flight = (
            self._obs.recorder(self.name) if self._obs is not None else None
        )
        #: where :meth:`crash` additionally dumps the flight ring as
        #: JSONL (``None`` = logger only) — chaos runs keep the black
        #: box after the process dies
        self.flight_dump_path = flight_dump_path
        self._lag = self._obs.lag if self._obs is not None else None
        self._loop_ts = time.monotonic()
        #: active only inside a ``process_pending`` drain pass: SYNC_DONE
        #: emissions append ``(fetch, emit)`` pairs here instead of
        #: reading the kernel's keys-updated accounting immediately —
        #: per-group device readbacks mid-drain block the host on each
        #: group's merge chain AND each pay a fixed transfer dispatch.
        #: The flush fetches every pending accounting pytree with ONE
        #: ``jax.device_get`` and then emits, in order. The list is
        #: swapped in/out and appended to under ``_lock``; the flush
        #: runs lock-free after the drain loop.
        self._telemetry_defer: list | None = None

        self.eager_deltas = eager_deltas
        self._lock = threading.RLock()
        #: state cell behind the ``state`` property: ``_state`` is the
        #: materialised per-replica pytree, or None while the
        #: authoritative copy is a lane of a fleet's stacked batch
        #: result (``_fleet_src = (stacked, lane)`` — materialised
        #: lazily on first access). ``_state_version`` bumps on every
        #: assignment: the fleet's batched dispatch is optimistic, and a
        #: version that moved between staging and commit means the
        #: batch read a stale state and must be replayed solo.
        self._state: Any = None
        self._fleet_src: "tuple | None" = None
        self._state_version = 0
        #: serving-plane read publication (ISSUE 14): the immutable
        #: ``(version, state, fleet_src, payloads)`` triple the front
        #: door's lock-free snapshot reads pin. Swapped ATOMICALLY
        #: (one attribute store) by ``_publish_serve`` at commit
        #: boundaries — points where the device state and the host
        #: payload dict agree — and read by ``runtime/serve.py``
        #: WITHOUT the replica lock. The payload dict referenced by a
        #: publication is append-only for its generation's lifetime
        #: (``gc`` REPLACES the dict, never prunes it in place), so a
        #: pinned snapshot keeps resolving its winners forever.
        self._serve_pub: "tuple | None" = None
        #: the replica's cached Frontdoor (``frontdoor()``); closed on
        #: stop/crash so the admission worker never outlives the replica
        self._frontdoor = None
        #: fleet participation counters (stats()["fleet"], mirroring
        #: the ingress coalescing surface): batched dispatches this
        #: replica rode, messages merged in them, and solo fallbacks
        #: (growth/gap/stale-version/device-plane reroutes)
        self._fleet_dispatches = 0
        self._fleet_messages = 0
        self._fleet_fallbacks = 0
        #: set by Fleet on membership: the fleet owns this replica's
        #: event loop, so start() must refuse (two drains would race)
        self._in_fleet = False
        self._pending: list[tuple[str, Any, Any]] = []  # (op, key_term, value)
        #: per-neighbour per-bucket own counter already pushed (Almeida's
        #: delta mode); soft state — reset on restart, pushes re-cover
        self._push_cursor: dict[Any, np.ndarray] = {}
        #: host cache of ctx_max[:, self_slot]; invalidated when local
        #: mutations mint dots (idle sync ticks then do no device work)
        self._own_ctr_cache: np.ndarray | None = None
        #: removes/clears don't mint dots, so interval pushes can't carry
        #: them; rows touched by local removes get a monotone sequence
        #: stamp and are pushed as full-row state slices instead
        self._row_touch_seq = np.zeros(self.num_buckets, np.int64)
        self._touch_seq = 0
        self._rm_cursor: dict[Any, int] = {}
        # dot (gid, bucket, ctr) -> (key_term, value); counters are
        # per-(writer, bucket) sequences, so the bucket is part of identity
        self._payloads: dict[tuple[int, int, int], tuple[Any, Any]] = {}
        self._key_terms: dict[int, Any] = {}
        #: garbage pressure (payload inserts + merge kills) since the
        #: last gc(); ``_maybe_gc`` prunes the host dicts when it passes
        #: max(``gc_interval_ops``, half the post-gc dict size) — the
        #: interval is a floor, the live-size term amortises gc cost
        self.gc_interval_ops = int(gc_interval_ops)
        self._gc_pressure = 0
        self._gc_floor = 0  # len(_payloads) right after the last gc
        self._neighbours: list[Any] = []
        self._monitors: set[Any] = set()
        self._outstanding: dict[Any, int] = {}
        #: hierarchical anti-entropy (ISSUE 15): with ``tree_gossip``
        #: on, sync edges are the replica's links in a deterministic
        #: membership-derived spanning tree (runtime/treesync.py) —
        #: leaves sync only their parent, relays coalesce inbound
        #: children's deltas and re-emit ONE merged slice per link per
        #: epoch (``_relay_flush``). Every replica derives the SAME
        #: tree from the sorted member set + ``tree_seed`` (no
        #: coordinator); ``Down``/rejoin/``set_neighbours`` invalidate
        #: and re-derive, and past ``tree_degrade_ratio`` locally-down
        #: members the replica degrades to flat gossip outright.
        self.tree_gossip = bool(tree_gossip)
        self.tree_fanout = int(tree_fanout)
        if self.tree_gossip and self.tree_fanout < 2:
            # fail HERE, not in the background loop's first derivation
            raise ValueError(
                f"tree_fanout must be >= 2, got {tree_fanout!r}"
            )
        self.tree_seed = int(tree_seed)
        self.tree_degrade_ratio = float(tree_degrade_ratio)
        #: tier-0 cluster key (``treesync.group_of``): a fleet stamps
        #: its members with one shared key so they form a single
        #: bottom-tier subtree whose captain alone gossips outward
        self.tree_group = tree_group
        self._tree_topo: "treesync.TreeTopology | None" = None
        self._tree_down: set[Any] = set()
        self._tree_degraded = False
        self._tree_probe_ts = 0.0
        #: REVERSE links: peers not in our tree view that keep opening
        #: sync rounds toward us — evidence THEIR view has us as a link
        #: (transiently divergent trees mid-churn, e.g. a re-parented
        #: member whose new parent never observed the Down that moved
        #: it). We sync back toward them (monitor + push + walk) until
        #: they stop, which makes every view-edge bidirectional and
        #: guarantees convergence without a membership gossip round;
        #: entries expire ``addr -> monotonic deadline`` when the peer
        #: goes quiet (its view caught up, or it left)
        self._tree_reverse: dict[Any, float] = {}
        #: relay coalescing state, all under ``_lock``: per-link ordered
        #: pending bucket rows (dict used as an ordered set) awaiting
        #: the next re-emission, per-link inbound messages folded since
        #: that link last flushed, and inbound slice bytes accumulated
        #: since the last flush (the rx side of the per-tier counters).
        #: ``_relay_defer`` parks each merge's (sources, buckets,
        #: kernel-count accessor) until the flush, which fetches every
        #: parked count pytree with ONE batched ``device_get`` and
        #: stamps pending rows only for messages that actually CHANGED
        #: state — a no-op merge relays nothing, which is what bounds
        #: the cascade when transiently divergent tree views form a
        #: cycle (and what keeps redundant walk transfers from
        #: triggering whole-subtree re-sweeps).
        self._relay_defer: list = []
        self._relay_pending: dict[Any, dict[int, None]] = {}
        self._relay_fold: dict[Any, int] = {}
        self._relay_rx_pending = 0
        self._relay_reemits = 0
        self._relay_msgs_folded = 0
        self._relay_entries_emitted = 0
        self._relay_rows_emitted = 0
        self._relay_tx_bytes = 0
        self._relay_rx_bytes = 0
        self._relay_depth_hist: dict[int, int] = {}
        #: ingress coalescing (ISSUE 3): the event loop drains a bounded
        #: batch of queued messages and joins compatible EntriesMsg
        #: groups with ONE grouped fan-in kernel dispatch instead of one
        #: dispatch per message — the bench-proven grouped-merge
        #: amortisation on the live hot path. ``max_coalesce`` bounds
        #: group depth (compile-shape tiers), ``ingress_batch`` bounds
        #: one drain.
        self.ingress_coalesce = bool(ingress_coalesce)
        self.max_coalesce = int(max_coalesce)
        self.ingress_batch = int(ingress_batch)
        #: coalescing observability: depth histogram (group size →
        #: dispatch count) and message/dispatch totals, served by
        #: :meth:`stats` — the batching win must be visible in
        #: production, not just in bench
        self._coalesce_depths: dict[int, int] = {}
        self._ingress_messages = 0
        self._ingress_dispatches = 0
        self._ingress_gap_fallbacks = 0
        self._ingress_gap_partitions = 0
        #: membership-driven WAL compaction (ROADMAP): per monitored
        #: neighbour, the highest local ``_seq`` that peer is known to
        #: have fully observed (an acked sync round that opened at that
        #: seq found the trees equal). Segment reclaim never passes the
        #: minimum watermark of the monitored set — a known-but-lagging
        #: peer keeps its catch-up records; once every monitored peer
        #: acks past a segment it is reclaimed aggressively (the normal
        #: snapshot-covered path).
        self.membership_compaction = bool(membership_compaction)
        #: retention BOUND for the ack gate: a monitored peer that never
        #: acks (e.g. a pure fan-in aggregator — its tree always differs
        #: from a single writer's, so walk-equality acks never fire)
        #: must not pin segment reclaim forever. At most this many
        #: records of history are retained past the ack floor; a peer
        #: lagging further falls back to the digest walk, exactly the
        #: "past compaction horizons" contract of the log-shipping plan.
        self.membership_retain = (
            int(membership_retain)
            if membership_retain is not None
            else 4 * self.compact_every
        )
        self._ack_seq: dict[Any, int] = {}
        self._sync_open_seq: dict[Any, int] = {}
        #: log-shipping catch-up (ISSUE 4): a rejoining/lagging peer's
        #: divergence is exactly the suffix of the originator's delta
        #: log past the peer's last fully observed seq, so catch-up
        #: requests WAL record ranges (``GetLogMsg``) and replays the
        #: shipped row slices through the grouped entries path instead
        #: of walking the digest tree. ``_applied_seq`` is this
        #: replica's watermark of each PEER's history (learned from
        #: walk-equality acks — every ``DiffMsg`` stamps the sender's
        #: seq — and advanced by applied chunks; persisted in snapshots
        #: so a restart resumes log-shipped instead of walking).
        #: ``_catchup`` tracks the one in-flight request per peer
        #: (requester-paced: the server stays stateless).
        self.log_shipping = bool(log_shipping)
        self.catchup_chunk_rows = int(catchup_chunk_rows)
        #: past-horizon mode threshold (ROADMAP follow-up (a)): engage a
        #: horizon-clamped catch-up stream only when the opener's
        #: servable suffix (seq − horizon) is at least this many times
        #: the walk-bound prefix (horizon − watermark); otherwise skip
        #: the suffix chunks — the prefix walk heals everything anyway
        self.catchup_suffix_ratio = float(catchup_suffix_ratio)
        self._applied_seq: dict[Any, int] = {}
        self._catchup: dict[Any, dict] = {}
        #: per-peer "walk first" floor: a horizon-marked chunk told us
        #: the span through that seq is unservable by the peer's log
        #: (compacted, or a serving barrier) — openers must take the
        #: classic walk until our watermark passes it, or every round
        #: would re-request the same unservable range
        self._catchup_walk_floor: dict[Any, int] = {}
        #: catch-up observability (stats() + telemetry). Lane/entry
        #: counts quantify the transfer-padding overhead per store
        #: backend (ISSUE 8 satellite: the PR 4 "chunk bytes ~2× the
        #: walk's" finding is padding — the binned store ships whole
        #: bin-tier rows, the hash store ships dense content-sized
        #: slices; chunk_fill_ratio makes the difference observable).
        self._catchup_chunks_served = 0
        self._catchup_chunks_applied = 0
        self._catchup_bytes_shipped = 0
        self._catchup_lanes_shipped = 0
        self._catchup_entries_shipped = 0
        self._catchup_rows_applied = 0
        self._catchup_horizon_fallbacks = 0
        self._catchup_last_duration = 0.0
        self._tree: _LazyLevels | None = None
        #: full-read result cache, maintained INCREMENTALLY by local
        #: flushes whenever it is complete (not None): a local op's
        #: effect on the read map is exact — add kills every observed
        #: same-key dot and inserts the sole winner (remove-delta ⊔
        #: add-delta, ``aw_lww_map.ex:99-112``), remove/clear kill all
        #: observed dots — so replaying the batch onto the dict equals
        #: the device result, and a cold full read is a dict copy, not
        #: an O(map) winner pass. Only a remote merge changes keys the
        #: host didn't see: it invalidates the cache, and the next full
        #: read rebuilds it through the vectorized winner pass.
        #:
        #: Soundness guard: a Python dict collapses ``==``-equal key
        #: terms the CRDT keys distinctly (1 vs True vs 1.0 have
        #: different canonical hashes). ``_read_cache_kh`` maps each
        #: cached term to its canonical hash; a local op touching an
        #: ``==``-equal term with a DIFFERENT hash invalidates the cache
        #: (rare: lazily detected, O(1) per op), and a rebuild that
        #: collapsed terms (fewer dict slots than winners) sets it to
        #: None, which blocks maintenance until a clean rebuild.
        self._read_cache: dict | None = {}
        self._read_cache_kh: dict | None = {}
        self._seq = 0
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

        # register under the bare name; self-identify to peers with the
        # transport's canonical (routable-from-anywhere) address — the
        # {name, node} analog (causal_crdt_test.exs:68-78)
        self.addr = self.transport.canonical_addr(self.name)

        #: jax device this replica's state is pinned to (None = default
        #: placement). Peer replicas pinned to devices of one mesh get
        #: their sync slices moved device↔device (ICI on real hardware)
        #: instead of through host pickle — SURVEY §5.8's hybrid: host
        #: control plane, device data plane.
        self.device = device

        t_recover = time.perf_counter()
        wal_header, wal_records = (
            self._wal.recover() if self._wal is not None else (None, [])
        )
        snap = self.storage_module.read(self.name) if self.storage_module else None
        if snap is not None:
            self._rehydrate(snap)
            if wal_header is not None and int(wal_header["node_id"]) != self.node_id:
                raise ValueError(
                    f"WAL for {self.name!r} belongs to node "
                    f"{wal_header['node_id']} but the snapshot is node "
                    f"{self.node_id} — mixed histories in one wal_dir"
                )
        elif wal_header is not None:
            # crash landed before the first compaction snapshot: fresh
            # arrays, but the WAL header preserves the dot namespace —
            # and an explicit conflicting node_id is the same
            # mixed-history misconfiguration the snapshot branch rejects
            if node_id is not None and node_id != int(wal_header["node_id"]):
                raise ValueError(
                    f"WAL for {self.name!r} belongs to node "
                    f"{wal_header['node_id']} but node_id={node_id} was "
                    "requested — mixed histories in one wal_dir"
                )
            self._init_fresh(int(wal_header["node_id"]), capacity, replica_capacity)
        else:
            self._init_fresh(
                node_id if node_id is not None else (secrets.randbits(63) | 1),
                capacity,
                replica_capacity,
            )
        if device is not None:
            # commit the state to the device: every jitted kernel over it
            # then runs (and allocates its outputs) there
            self.state = _TR_STATE_PLACE.put(self.state, device)
        if wal_records:
            # snapshot + replay: records past the snapshot's sequence
            # number re-apply through the normal idempotent flush/merge
            # paths, reproducing the pre-crash state exactly
            self._wal_replay(wal_records, t_recover)
        if self._wal is not None:
            self._wal.bind(self.node_id)

        self.transport.register(self.name, self)
        self._warmup()
        if self._obs is not None:
            # last: the plane's scrape-time collector polls stats(), so
            # every field it reads must already exist
            self._obs.register_replica(self)

    @property
    def state(self) -> BinnedStore:
        """The device-resident lattice state. For a fleet member the
        authoritative copy may be a lane of the fleet's stacked batch
        result (:meth:`fleet_commit`); the lane materialises as a solo
        pytree on first access and is cached — fleet members whose
        state is only ever merged by batched dispatches never pay a
        per-replica unstack on the hot path. (The RLock is reentrant:
        callers inside a locked region pay one no-op re-acquire.)"""
        with self._lock:
            if self._state is None:
                stacked, lane = self._fleet_src
                self._state = transition.index_state(stacked, lane)
                self._fleet_src = None
            return self._state

    @state.setter
    def state(self, value) -> None:
        with self._lock:
            self._state = value
            self._fleet_src = None
            self._state_version += 1

    def _store_columns(self) -> tuple:
        """Snapshot ARRAY column set of this replica's store backend
        (static metadata fields — e.g. the hash store's probe window —
        snapshot as plain ints, see ``_store_meta``)."""
        meta = self._store_meta()
        return tuple(
            f.name
            for f in dataclasses.fields(self.model.Store)
            if f.name not in meta
        )

    def _store_meta(self) -> tuple:
        return getattr(self.model, "STORE_META", ())

    def _geometry(self) -> tuple:
        """The model's batch-compatibility key (backend tag + state
        geometry — each backend declares its own, ISSUE 8 satellite)
        without forcing a fleet-held lane to materialise (the fleet's
        shape bucketing must stay free of device work)."""
        if self._state is not None:
            return self.model.geometry(self._state)
        stacked, _lane = self._fleet_src
        return self.model.geometry_stacked(stacked)

    def _warmup(self) -> None:
        """Pre-trigger the jit compile of the single-op mutate tier so the
        first user mutate doesn't pay it (compile caches are process-wide:
        only the first replica of a given tier compiles)."""
        self.model.row_apply(
            self.state,
            jnp.int32(self.self_slot),
            jnp.full(1, -1, jnp.int32),
            jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.uint64),
            jnp.zeros((1, 1), jnp.uint32),
            jnp.zeros((1, 1), jnp.int64),
        )

    # ------------------------------------------------------------------
    # rehydrate / persist (reference causal_crdt.ex:216-250)

    def _init_fresh(self, node_id: int, capacity: int, replica_capacity: int) -> None:
        self.node_id = node_id
        bin_cap = _pow2(max(capacity // self.num_buckets, 1), floor=4)
        state = self.model.new(self.num_buckets, bin_cap, replica_capacity)
        # claim slot 0 of the context table for our own gid
        state = dataclasses.replace(
            state, ctx_gid=state.ctx_gid.at[0].set(jnp.uint64(self.node_id))
        )
        self.state = state
        self.self_slot = 0

    def _rehydrate(self, snap: Snapshot) -> None:
        # NB: __dict__.get, not getattr — a legacy pickle missing the field
        # would otherwise read the dataclass *default* (== CURRENT_LAYOUT)
        # and sail past the guard into an opaque KeyError below
        require_layout(
            snap.__dict__.get("layout", "<untagged>"), f"snapshot for {self.name!r}"
        )
        # snapshots record their store backend (ISSUE 8): arrays of one
        # layout cannot rehydrate the other — cross-backend migration
        # goes through extraction (MIGRATING.md), never a cast
        snap_store = snap.__dict__.get("store", "binned")
        if snap_store != self.model.backend:
            raise ValueError(
                f"snapshot for {self.name!r} was written by the "
                f"{snap_store!r} dot store but this replica runs "
                f"{self.model.backend!r} — cross-backend restore goes "
                "through extraction (see MIGRATING.md), or delete the "
                "stored snapshot to start fresh"
            )
        self.node_id = snap.node_id
        self._seq = snap.sequence_number
        self.state = self.model.Store(
            **{c: jnp.asarray(snap.arrays[c]) for c in self._store_columns()},
            **{m: int(snap.arrays[m]) for m in self._store_meta()},
        )
        gids = snap.arrays["ctx_gid"]
        slots = np.nonzero(gids == np.uint64(self.node_id))[0]
        assert len(slots) == 1, "rehydrated state must contain our node id"
        self.self_slot = int(slots[0])
        self._payloads = dict(snap.payloads)
        self._key_terms = dict(snap.key_terms)
        self.clock.observe(snap.last_ts)
        # per-peer applied watermarks: restoring them lets the restarted
        # replica resume log-shipping catch-up where it left off (sound:
        # recovery replays state at least as far as the snapshot, so the
        # restored state still covers everything the watermark claims).
        # __dict__.get, not getattr — legacy pickles lack the field.
        self._applied_seq = dict(snap.__dict__.get("peer_seqs") or {})
        # the snapshot's read map is unknown until a full pass rebuilds it
        self._read_cache = None
        self._read_cache_kh = None

    def _snapshot(self) -> Snapshot:
        state = self.state
        # contractual crossing: durability serialises on host by design
        # — one audited batched fetch of the full column set
        host = _TR_SNAPSHOT.get({c: getattr(state, c) for c in self._store_columns()})
        # column order, not device_get's sorted pytree order: snapshot
        # bytes are a durability format
        arrays = {c: np.asarray(host[c]) for c in self._store_columns()}
        for m in self._store_meta():
            # crdtlint: allow[TRANSFER001] STORE_META fields are static Python ints on the store pytree, not device scalars
            arrays[m] = int(getattr(state, m))
        return Snapshot(
            node_id=self.node_id,
            sequence_number=self._seq,
            arrays=arrays,
            payloads=dict(self._payloads),
            key_terms=dict(self._key_terms),
            last_ts=self.clock._last,
            peer_seqs=dict(self._applied_seq),
            store=self.model.backend,
        )

    def _persist(self) -> None:
        if self.storage_module is not None and self.storage_mode == "every_op":
            # crdtlint: allow[LOCK003] every_op durability IS the contract:
            # the write must capture state under the lock, and callers opted
            # into blocking-on-durability per mutation
            self.storage_module.write(self.name, self._snapshot())

    def _wal_arrays_host(self, a: dict) -> dict:
        """Host numpy image of an EntriesMsg column dict for a WAL
        record. Durability is host-side by definition, so a
        device-plane slice is copied back exactly ONCE here — the
        contractual crossing the ledger prices under
        ``replica.wal_entries``; host-plane images pass through with no
        crossing counted."""
        if isinstance(a["key"], np.ndarray):
            return {c: np.asarray(v) for c, v in a.items()}
        # rebuild in the message's column order: device_get flattens the
        # dict as a pytree and hands back SORTED keys, and a WAL record
        # pickles dict insertion order into its bytes
        got = _TR_WAL_ENTRIES.get(a)
        return {c: np.asarray(got[c]) for c in a}

    def _durable(self, record_fn: Callable[[], dict]) -> None:
        """One durability point per applied batch/slice. With a WAL this
        is an O(delta) record append + group commit (``fsync_mode``
        cadence); without, the reference's ``every_op`` full-image
        write. ``record_fn`` is lazy so the non-WAL path never builds a
        record. Replay must not re-log what it is replaying."""
        if self._replaying:
            return
        faultpoint("replica.durable")
        if self._wal is None:
            return self._persist()
        t0 = time.perf_counter()
        try:
            # crdtlint: allow[LOCK003] group commit IS the durability point:
            # the record must be staged+fsynced (per fsync_mode) before the
            # apply is acknowledged, and WalLog is replica-lock-serialised by
            # contract ("not thread-safe by itself")
            n_bytes = self._wal.append(record_fn())
            self._wal.commit()  # crdtlint: allow[LOCK003] group commit (see above)
        except BaseException:
            # failed commit: drop the staged record — the caller rolls
            # the seq back, and a stale staged record would otherwise
            # flush alongside the retry's re-minted seq (duplicate-seq
            # logs are corruption to recovery, by design)
            self._wal.abort()
            raise
        self._wal_unc += 1
        if telemetry.has_handlers(telemetry.WAL_APPEND):
            telemetry.execute(
                telemetry.WAL_APPEND,
                {
                    "bytes": n_bytes,
                    "records": 1,
                    "duration_s": time.perf_counter() - t0,
                },
                {"name": self.name},
            )
        if self._wal_unc >= self.compact_every:
            self._compact_wal()

    def _commit_abort(self, exc: BaseException) -> None:
        """Shared tail of every failed durability point: roll the seq
        back (it must keep naming the last durable record — recovery
        replays a contiguous prefix) and leave a black-box trace, so a
        post-mortem of a crash-after-abort shows WHICH commit died and
        why (the FAULT002 discipline: failure paths re-raise AND
        record)."""
        self._seq -= 1
        self._flight("commit_abort", seq=self._seq, error=repr(exc))

    def _durable_batch(self, batch: list, ts) -> None:
        """Durability point for one local mutation batch — the single
        definition of the ``batch`` record schema (both flush paths)."""
        if not self._replaying:
            faultpoint("replica.commit.batch")
        if self._lag is not None and not self._replaying:
            # sample THIS local commit for replication-lag tracing (the
            # tracer keeps every sample_every-th seq; replay re-applies
            # history, it does not commit fresh writes)
            self._lag.note_commit(self.addr, self._seq)
        self._durable(
            lambda: {
                "kind": "batch",
                "seq": self._seq,
                "ops": [tuple(b) for b in batch],
                "ts": ts.tolist(),
            }
        )

    def _ack_floor(self) -> int:
        """Membership compaction gate (ROADMAP open item): the highest
        seq every MONITORED peer is known to have observed. Segments
        above it may still be a lagging peer's cheapest catch-up feed
        (log shipping serves record ranges, digest walks are the
        fallback), so reclaim stops there; once all monitored peers ack
        past a segment it reclaims aggressively (the plain
        snapshot-covered path). No monitored peers — or the gate
        disabled — means the snapshot alone caps reclaim."""
        if not self.membership_compaction:
            return self._seq
        peers = [n for n in self._monitors if n != self.addr]
        if not peers:
            return self._seq
        return min(self._ack_seq.get(n, 0) for n in peers)

    def _reclaim_floor(self) -> int:
        """The seq WAL segment reclaim may actually proceed to: the ack
        floor, bounded below by the ``membership_retain`` horizon and
        above by the snapshot seq. The ONE definition — compaction and
        the stats/telemetry surfaces must report the same quantity."""
        return min(
            self._seq,
            max(self._ack_floor(), self._seq - self.membership_retain),
        )

    def _compact_wal(self) -> None:
        """Checkpoint a snapshot and reclaim fully-covered segments —
        the snapshot's ``sequence_number`` caps what replay would ever
        need, so every record ≤ it is dead weight for RECOVERY; the
        membership ack floor (:meth:`_ack_floor`) may keep up to
        ``membership_retain`` records of them alive for lagging
        monitored peers (bounded: a peer that never acks must not grow
        the log forever).

        Segments are only DELETED when the checkpoint store is known
        disk-backed (it exposes an ``fsync`` attribute, as
        ``FileStorage`` does): deleting fsynced records covered only by
        a volatile snapshot (e.g. ``MemoryStorage``) would silently
        trade committed data for process lifetime."""
        t0 = time.perf_counter()
        # crdtlint: allow[LOCK003] compaction checkpoint: the snapshot must
        # be consistent with (and fsynced before reclaiming) the records it
        # covers, all of which only hold still under the replica lock
        self.storage_module.write(self.name, self._snapshot())
        floor = self._reclaim_floor()
        if getattr(self.storage_module, "fsync", None) is not None:
            # crdtlint: allow[LOCK003] segment reclaim deletes fsynced
            # records — it must not race the appends it is covering
            deleted, freed = self._wal.compact(floor)
        else:
            deleted, freed = 0, 0
            # crdtlint: allow[LOCK003] segment roll under the lock: the
            # active segment's fd/index is replica-lock-serialised state
            self._wal.rotate()  # still bound the active segment's size
        self._wal_unc = 0
        self._flight(
            "wal_compact", segments_deleted=deleted, bytes_reclaimed=freed,
            ack_floor=floor,
        )
        if telemetry.has_handlers(telemetry.WAL_COMPACT):
            telemetry.execute(
                telemetry.WAL_COMPACT,
                {
                    "segments_deleted": deleted,
                    "bytes_reclaimed": freed,
                    "ack_floor": floor,
                    "duration_s": time.perf_counter() - t0,
                },
                {"name": self.name},
            )

    def _wal_replay(self, records: list, t0: float) -> None:
        """Replay recovered records past the snapshot's sequence number
        through the normal flush/merge paths. Local batches re-mint
        their logged LWW timestamps via :class:`ReplayClock` (dot
        counters then reassign identically from the restored per-bucket
        context), so the replayed state is bit-for-bit the pre-crash
        one; merge idempotence makes any snapshot/record overlap
        harmless. Diff subscribers stay silent — recovery re-applies
        history, it does not re-announce it."""
        base = self._seq
        real_clock, real_diffs = self.clock, self.on_diffs
        self._replaying = True
        self.on_diffs = None
        applied = 0
        max_ts = 0
        try:
            for rec in records:
                seq = int(rec["seq"])
                if seq <= base:
                    continue  # the snapshot already covers this record
                if rec["kind"] == "batch":
                    ts = rec["ts"]
                    self.clock = ReplayClock(ts)
                    self._flush_batch([tuple(op) for op in rec["ops"]])
                    if ts:
                        max_ts = max(max_ts, int(max(ts)))
                elif rec["kind"] == "entries":
                    self._replay_entries(rec)
                else:  # forward-compat: unknown kinds are skipped loudly
                    logger.warning("WAL replay: unknown record kind %r", rec["kind"])
                self._seq = seq  # lockstep even across skipped records
                applied += 1
        finally:
            self.clock, self.on_diffs = real_clock, real_diffs
            self._replaying = False
        # clock continuity: replayed local stamps must not out-rank new
        # writes (the snapshot's last_ts was observed in _rehydrate)
        self.clock.observe(max_ts)
        self._flight(
            "wal_recover", records=applied, bytes=self._wal.recovered_bytes,
        )
        if telemetry.has_handlers(telemetry.WAL_RECOVER):
            telemetry.execute(
                telemetry.WAL_RECOVER,
                {
                    "records": applied,
                    "bytes": self._wal.recovered_bytes,
                    "duration_s": time.perf_counter() - t0,
                },
                {"name": self.name},
            )

    def _replay_entries(self, rec: dict) -> None:
        a = rec["arrays"]
        sl = self.model.RowSlice(
            rows=jnp.asarray(a["rows"]),
            key=jnp.asarray(a["key"]),
            valh=jnp.asarray(a["valh"]),
            ts=jnp.asarray(a["ts"]),
            node=jnp.asarray(a["node"]),
            ctr=jnp.asarray(a["ctr"]),
            alive=jnp.asarray(a["alive"]),
            ctx_rows=jnp.asarray(a["ctx_rows"]),
            ctx_lo=jnp.asarray(a["ctx_lo"]),
            ctx_gid=jnp.asarray(a["ctx_gid"]),
        )
        self._payloads.update(rec["payloads"])
        for _dot, (key_term, _val) in rec["payloads"].items():
            self._key_terms[key_hash64(key_term)] = key_term
        try:
            res = self._merge_with_growth(sl)
        except CtxGapError:
            # pre-crash this slice merged cleanly, so a gap here means
            # the log lost an earlier record (e.g. a truncated torn
            # tail ahead of it — impossible by construction, but never
            # crash a recovery): skip and let anti-entropy repair
            logger.warning(
                "WAL replay: gapped entries record seq %s skipped", rec["seq"]
            )
            # the payloads above went in without a merge — they must
            # still count toward the gc cadence (same reasoning as the
            # live CtxGapError path in _handle_entries_inner)
            self._gc_pressure += len(rec["payloads"])
            return
        self._note_state_changed(
            # default-arg capture of JUST the two count scalars: a
            # closure over ``res`` parks the whole MergeRowsResult —
            # including ``res.state`` — in the drain's deferral
            # window, pinning every superseded store generation and
            # defeating XLA's input-buffer reuse on each subsequent
            # merge (a full-store copy per dispatch)
            lambda ins=res.n_inserted, kill=res.n_killed: (ins, kill)
        )
        self._gc_pressure += len(rec["payloads"]) + int(_TR_INGEST_COUNTS.get(res.n_killed))
        self._maybe_gc()

    def checkpoint(self) -> None:
        """Explicit snapshot (for storage_mode="interval"); with a WAL
        this is a compaction point — the snapshot covers the log, so
        covered segments are reclaimed."""
        with self._lock:
            if self.storage_module is None:
                return
            if self._wal is not None:
                self._compact_wal()
            else:
                # crdtlint: allow[LOCK003] explicit snapshot: state must
                # hold still while the image is written
                self.storage_module.write(self.name, self._snapshot())

    # ------------------------------------------------------------------
    # public API (facade parity: delta_crdt.ex:97-137)

    def _acquire(self, timeout: float | None, what: str) -> None:
        """GenServer.call timeout semantics (``delta_crdt.ex:117-137``):
        the call blocks on the replica's serialisation lock for at most
        ``timeout`` seconds, then raises. The timeout bounds *queueing*
        (a busy sync thread); once acquired, the operation runs to
        completion like a received GenServer call."""
        if not self._lock.acquire(timeout=-1 if timeout is None else timeout):
            raise TimeoutError(
                f"{what} timed out after {timeout}s waiting for replica {self.name!r}"
            )

    def mutate(self, f: str, args: list, timeout: float | None = None) -> None:
        self._acquire(timeout, f"mutate {f!r}")
        try:
            self._enqueue(f, args)
            self._flush()
        finally:
            self._lock.release()

    def mutate_async(self, f: str, args: list) -> None:
        with self._lock:
            self._enqueue(f, args)
        self.notify()

    def mutate_batch(self, f: str, items: list, timeout: float | None = None) -> None:
        """Bulk synchronous mutation: one ``f`` op per entry of ``items``
        (each an args list as ``mutate`` takes). The whole batch enqueues
        under one lock acquisition and flushes once — the TPU-native
        load shape: all-adds batches take the vectorized flush path, so
        this beats a ``mutate_async`` loop by the per-op lock/notify
        overhead on top of it. No reference analog (``mutate/4`` is
        per-op, ``delta_crdt.ex:117-120``); semantics are identical to
        issuing the ops in order. Delegates to :meth:`apply_ops` — THE
        one grouped-commit implementation, shared with the serving
        plane's write admission (ISSUE 14: two batched write entrances
        must not drift; parity is pinned in ``tests/test_serve.py``)."""
        self.apply_ops([(f, args) for args in items], timeout)

    def apply_ops(self, ops: list, timeout: float | None = None) -> None:
        """THE grouped-commit entrance: apply ``ops`` — ``(f, args)``
        pairs, possibly mixed kinds — in order as ONE batch under one
        lock acquisition and one flush (one vectorised kernel pass per
        clear-free segment, one WAL group commit for batches within
        ``MAX_BATCH``). Both batched write entrances route through
        here: ``mutate_batch`` (bulk loads) and the serving plane's
        admission worker (``runtime/serve.py``), so WAL record bytes
        and state bits are bit-for-bit identical for identical op
        sequences regardless of the entrance."""
        self._acquire(timeout, "apply_ops")
        try:
            pre = len(self._pending)
            try:
                for f, args in ops:
                    self._enqueue(f, args)
            except Exception:
                # a rejected batch must not partially commit later: drop
                # the prefix this call enqueued before re-raising
                del self._pending[pre:]
                raise
            self._flush()
        finally:
            self._lock.release()

    def _enqueue(self, f: str, args: list) -> None:
        ops = self.model.OPS
        if f not in ops:
            raise ValueError(f"unknown operation {f!r}; available: {sorted(ops)}")
        _, arity = ops[f]
        if len(args) != arity:
            raise ValueError(f"{f} expects {arity} argument(s), got {len(args)}")
        if f == "add":
            # value-less models (e.g. AWSet, arity 1) store the constant
            # True — present-ness is the value, and a non-None value keeps
            # the `add k, nil ⇒ remove` diff rule map-only
            value = args[1] if arity == 2 else True
            self._pending.append(("add", args[0], value))
        elif f == "remove":
            self._pending.append(("remove", args[0], None))
        else:
            self._pending.append(("clear", None, None))

    def flush(self) -> None:
        """Apply queued async mutations now (without reading)."""
        with self._lock:
            self._flush()

    def read(self, timeout: float | None = None) -> "dict | set":
        # AWLWWMap -> dict; value-less models (AWSet) -> set (read_view)
        self._acquire(timeout, "read")
        try:
            self._flush()
            if self._read_cache is None:
                self._read_cache = self._rebuild_read_cache()
            return self.model.read_view(dict(self._read_cache))
        finally:
            self._lock.release()

    def read_keys(self, key_terms: list) -> "dict | set":
        """Partial read (reference ``AWLWWMap.read/2``, ``aw_lww_map.ex:
        218-224``) — a dict for map models, the member subset for AWSet."""
        with self._lock:
            self._flush()
            hashes = [key_hash64(k) for k in key_terms]
            k = _wire(max(len(hashes), 1))
            arr = np.zeros(k, np.uint64)
            arr[: len(hashes)] = hashes
            w = self.model.winners_for_keys(self.state, jnp.asarray(arr))
            # one audited batched fetch instead of three implicit ones
            found, gid, ctr = _TR_READ_KEYS.get((w.found, w.gid, w.ctr))
            out = {}
            mask = self.num_buckets - 1
            for i, term in enumerate(key_terms):
                if found[i]:
                    dot = (int(gid[i]), int(hashes[i]) & mask, int(ctr[i]))
                    out[term] = self._payloads[dot][1]
            return self.model.read_view(out)

    def set_neighbours(self, neighbours: list) -> None:
        """One-way sync edges (reference ``{:set_neighbours, …}``,
        ``causal_crdt.ex:147-178``): prunes monitors/in-flight slots for
        removed peers, then syncs immediately."""
        addrs = [n.addr if isinstance(n, Replica) else n for n in neighbours]
        with self._lock:
            removed = set(self._monitors) - set(addrs)
            for addr in removed:
                self.transport.demonitor(self.addr, addr)
            self._neighbours = list(addrs)
            self._monitors &= set(addrs)
            self._outstanding = {a: v for a, v in self._outstanding.items() if a in addrs}
            self._push_cursor = {
                a: c for a, c in self._push_cursor.items() if a in addrs
            }
            self._rm_cursor = {a: c for a, c in self._rm_cursor.items() if a in addrs}
            # removed peers stop gating WAL segment reclaim immediately
            self._ack_seq = {a: s for a, s in self._ack_seq.items() if a in addrs}
            self._sync_open_seq = {
                a: s for a, s in self._sync_open_seq.items() if a in addrs
            }
            self._catchup = {a: s for a, s in self._catchup.items() if a in addrs}
            if self.tree_gossip:
                # membership moved: re-derive the spanning tree (every
                # replica fed the same member list lands on the same
                # topology), and forget failure/relay state for members
                # that left
                self._tree_topo = None
                self._tree_down &= set(addrs)
                self._relay_pending = {
                    a: p for a, p in self._relay_pending.items() if a in addrs
                }
                self._relay_fold = {
                    a: c for a, c in self._relay_fold.items() if a in addrs
                }
                self._tree_reverse = {
                    a: t for a, t in self._tree_reverse.items() if a in addrs
                }
            # the sync below opens a round toward every (re)gained peer;
            # its opener carries our seq + log horizon, and a peer whose
            # watermark is within the horizon answers with GetLogMsg —
            # the set_neighbours/rejoin catch-up trigger, riding the
            # normal one-way opener so sync stays originator → peer
            self.sync_to_all()

    # ------------------------------------------------------------------
    # local mutation batch

    #: largest mutation batch applied in one kernel call (diffs bundle per
    #: chunk, consistent with the reference's per-sync-round bundling)
    MAX_BATCH = 1024

    def _flush(self) -> None:
        while self._pending:
            batch = self._pending[: self.MAX_BATCH]
            self._pending = self._pending[self.MAX_BATCH :]
            with tracing.annotate("crdt.flush"):
                self._flush_batch(batch)

    def _flush_batch(self, batch: list) -> None:
        n = len(batch)
        if (
            n >= 64
            and self.on_diffs is None
            and all(f == "add" for f, _t, _v in batch)
        ):
            # the bulk-load shape: one vectorized pass instead of five
            # per-op Python loops (~3x on the 1M-key load matrix row)
            return self._flush_batch_adds(batch)
        key = np.zeros(n, np.uint64)
        valh = np.zeros(n, np.uint32)
        op = np.full(n, OP_PAD, np.int32)
        ts = np.zeros(n, np.int64)
        any_clear = False
        batch_hashes = None
        if n >= 32:
            # one native call hashes the whole batch (keys and values)
            batch_hashes = (
                key_hash64_batch([t for _f, t, _v in batch]),
                value_hash32_batch([v for _f, _t, v in batch]),
            )
        for i, (f, key_term, value) in enumerate(batch):
            if f == "add":
                op[i] = OP_ADD
                key[i] = batch_hashes[0][i] if batch_hashes else key_hash64(key_term)
                valh[i] = batch_hashes[1][i] if batch_hashes else value_hash32(value)
            elif f == "remove":
                op[i] = OP_REMOVE
                key[i] = batch_hashes[0][i] if batch_hashes else key_hash64(key_term)
            else:
                op[i] = OP_CLEAR
                any_clear = True
            ts[i] = self.clock.next()
            if f != "clear":
                self._key_terms[key[i].item()] = key_term

        # touched keys for the diff/callback: the batch keys (clear implies
        # every currently-present key; the full-map pass below covers it)
        touched: dict[int, Any] = {}
        for i, (f, key_term, _v) in enumerate(batch):
            if f != "clear":
                touched[int(key[i])] = key_term

        # the before/after winner passes exist only to feed the diff
        # callback (and clear's full-map diff); without a subscriber the
        # kernel's own changed-key count serves telemetry
        need_winners = self.on_diffs is not None or any_clear
        w_before = self._batch_winner_records(touched, any_clear) if need_winners else {}

        # apply segments split at clears (clear is a full-state kernel)
        n_changed = 0
        ctr_of_op = np.zeros(n, np.uint32)
        seg_start = 0
        for i in range(n + 1):
            if i == n or op[i] == OP_CLEAR:
                if i > seg_start:
                    sl = slice(seg_start, i)
                    n_changed += self._apply_segment(
                        op[sl], key[sl], valh[sl], ts[sl], ctr_of_op[sl]
                    )
                if i < n:  # the clear itself
                    n_cleared = int(self.state.num_alive())
                    self.state = self.model.clear_all(self.state)
                    n_changed += n_cleared
                seg_start = i + 1
        self._seq += 1
        if any_clear:
            # a clear kills every row; stamp them all for the full-row push
            self._stamp_rows(np.arange(self.num_buckets, dtype=np.int64))

        # register payloads for surviving adds (host mirror of the kernel's
        # shadowing: last op per key wins, a clear shadows everything
        # before it). Keyed by key hash: terms may be unhashable.
        # Dot identity is (writer gid, bucket, counter) — counters are
        # per-bucket sequences (ops/binned.py row_apply).
        survivor: dict[int, int] = {}
        blocked = False
        for i in range(n - 1, -1, -1):
            f, key_term, value = batch[i]
            if f == "clear":
                blocked = True
            elif not blocked and int(key[i]) not in survivor:
                survivor[int(key[i])] = i if f == "add" else -1
        for kh, i in survivor.items():
            if i >= 0:
                _f, key_term, value = batch[i]
                dot = (self.node_id, kh & (self.num_buckets - 1), int(ctr_of_op[i]))
                self._payloads[dot] = (key_term, value)

        # maintain the full-read cache in place when it is complete (see
        # __init__): replay the batch in order — identical shadowing to
        # the device kernel's last-op-wins + observed-remove semantics
        maintained = self._read_cache is not None and self._read_cache_kh is not None
        if maintained:
            cache, ckh = self._read_cache, self._read_cache_kh
            try:
                for i, (f, key_term, value) in enumerate(batch):
                    if f == "clear":
                        cache.clear()
                        ckh.clear()
                        continue
                    kh = int(key[i])
                    prev = ckh.get(key_term)
                    if prev is not None and prev != kh:
                        # ==-equal term with a different canonical key
                        # (1 vs True): the dict would collapse what the
                        # CRDT keeps distinct — fall back to full passes
                        self._read_cache = None
                        self._read_cache_kh = None
                        maintained = False
                        break
                    if f == "add":
                        cache[key_term] = value
                        ckh[key_term] = kh
                    else:
                        cache.pop(key_term, None)
                        ckh.pop(key_term, None)
            except TypeError:
                # unhashable key term: dict reads are impossible for this
                # map anyway (read() raises; read_items() is the API)
                self._read_cache = None
                self._read_cache_kh = None
                maintained = False

        # durability happens-before publication (crdtlint FAULT003): a
        # crash between the two may lose only *unpublished* work — never
        # publish state a recovery cannot replay. A failed append rolls
        # the seq back so it still names the last durable record.
        try:
            self._durable_batch(batch, ts)
        except BaseException as e:
            self._commit_abort(e)
            raise
        if need_winners:
            w_after = self._batch_winner_records(touched, any_clear)
            touched_all = dict(touched)
            for kh in set(w_before) | set(w_after):
                touched_all.setdefault(kh, self._key_terms.get(kh))
            self._emit_diffs(touched_all, w_before, w_after, maintained)
        else:
            self._note_state_changed(lambda: n_changed, maintained)
        # every op can kill/replace a previously-live entry, stranding its
        # payload in the host dict until the next prune
        self._gc_pressure += n
        self._maybe_gc()

    def _flush_batch_adds(self, batch: list) -> None:
        """All-adds fast path of ``_flush_batch`` (no clears, no diff
        subscriber): semantics are identical — native batch hashing, one
        bulk clock call, C-level dict updates for key terms / payloads /
        the read cache, and the same ``_apply_segment`` kernel (which
        stamps kill-touched rows and invalidates push cursors)."""
        n = len(batch)
        terms = [t for _f, t, _v in batch]
        values = [v for _f, _t, v in batch]
        key = np.asarray(key_hash64_batch(terms), np.uint64)
        valh = np.asarray(value_hash32_batch(values), np.uint32)
        ts = self.clock.next_n(n)
        op = np.full(n, OP_ADD, np.int32)
        kh_list = key.tolist()
        self._key_terms.update(zip(kh_list, terms))

        ctr_of_op = np.zeros(n, np.uint32)
        n_changed = self._apply_segment(op, key, valh, ts, ctr_of_op)
        self._seq += 1

        # survivors = the LAST add per key hash (dict keeps the last)
        last_idx = dict(zip(kh_list, range(n)))
        mask = self.num_buckets - 1
        b_l = (key & np.uint64(mask)).astype(np.int64).tolist()
        c_l = ctr_of_op.tolist()
        node_id = self.node_id
        self._payloads.update(
            ((node_id, b_l[i], c_l[i]), (terms[i], values[i]))
            for i in last_idx.values()
        )

        # read-cache maintenance, batch-granular (see _flush_batch): the
        # in-order dict update IS last-add-wins; the alias guard compares
        # slot counts instead of per-op hash checks
        maintained = self._read_cache is not None and self._read_cache_kh is not None
        if maintained:
            try:
                d_kh = dict(zip(terms, kh_list))
                if len(d_kh) < len(set(kh_list)):
                    maintained = False  # ==-equal terms, distinct keys
                else:
                    ckh = self._read_cache_kh
                    for t in ckh.keys() & d_kh.keys():
                        if ckh[t] != d_kh[t]:
                            maintained = False  # cross-batch alias
                            break
            except TypeError:
                maintained = False  # unhashable terms: no dict reads
            if maintained:
                self._read_cache.update(zip(terms, values))
                self._read_cache_kh.update(d_kh)
            else:
                self._read_cache = None
                self._read_cache_kh = None

        # durability happens-before publication (FAULT003, see
        # _flush_batch); roll the seq back if the append fails
        try:
            self._durable_batch(batch, ts)
        except BaseException as e:
            self._commit_abort(e)
            raise
        self._note_state_changed(lambda: n_changed, maintained)
        self._gc_pressure += n
        self._maybe_gc()

    def _apply_segment(self, op, key, valh, ts, ctr_out) -> int:
        """Apply one clear-free batch segment; fills ``ctr_out`` with the
        dot counter assigned to each op. Returns the changed-key count."""
        g = self.model.group_batch(self.num_buckets, op, key, valh, ts)
        while True:
            res = self.model.row_apply(
                self.state,
                jnp.int32(self.self_slot),
                *map(jnp.asarray, (g.rows, g.op, g.key, g.valh, g.ts)),
            )
            if bool(res.ok):
                # post_apply is the backend's load advisory (the hash
                # store's load-factor rehash rides the result's counts)
                self.state = self.model.post_apply(
                    res.state, res, on_grow=self._grown_telemetry
                )
                break
            self._grow_bin()
        self._own_ctr_cache = None  # fresh own dots: push cursors lag
        # rows that lost a pre-batch entry (removes AND overwriting adds)
        # cannot converge via the interval push alone — stamp them for the
        # full-row push leg
        killed_mask, ctr_assigned, n_keys_changed = _TR_APPLY_COUNTS.get(
            (res.row_killed, res.ctr_assigned, res.n_keys_changed)
        )
        self._stamp_rows(g.rows[killed_mask & (g.rows >= 0)])
        urow, cols = g.index
        ctr_out[:] = ctr_assigned[urow, cols]
        return int(n_keys_changed)

    def _stamp_rows(self, rows: np.ndarray) -> None:
        """Mark rows as needing a full-row push, each with a UNIQUE
        monotone stamp — uniqueness lets a truncated push advance its
        cursor to exactly the last pushed row (no livelock on ties)."""
        if len(rows) == 0:
            return
        rows = np.unique(rows)
        k = len(rows)
        self._row_touch_seq[rows] = np.arange(
            self._touch_seq + 1, self._touch_seq + 1 + k, dtype=np.int64
        )
        self._touch_seq += k

    def _grow_bin(self) -> None:
        # backend-owned overflow escape: bin tier ×2 (binned) or a
        # whole-table rehash (hash — THE growth event, ISSUE 8)
        self.state = self.model.grow_for_apply(self.state)
        self._grown_telemetry(self.state)

    def grow_store_advised(self) -> None:
        """Fleet post-commit growth advisory (ISSUE 8): the vmapped
        merge reported this member's hot probe window near overflow, so
        grow the store off the batch path before it overflows and
        escapes mid-batch. Re-checks under the lock — a concurrent
        mutate may already have grown the table between the fleet's
        readback and here — and commits through the state internals
        rather than the self-locking property setter, so the whole
        check-then-grow is ONE critical section the lock analysis can
        see (the property's per-access locks would not make the
        read-modify-write atomic on their own)."""
        with self._lock:
            st = self.state
            if self.model.store_load_high(st):
                self._state = self.model.grow_for_apply(st)
                self._fleet_src = None
                self._state_version += 1
                # growth preserves content but swaps the store pytree:
                # republish so readers pin the live generation
                self._publish_serve()
                self._grown_telemetry(self._state)

    def _grown_telemetry(self, state) -> None:
        self._flight("growth", capacity=int(state.capacity))
        if telemetry.has_handlers(telemetry.CAPACITY_GROWN):
            telemetry.execute(
                telemetry.CAPACITY_GROWN,
                {"capacity": state.capacity, "replica_capacity": state.replica_capacity},
                {"name": self.name},
            )

    def _flight(self, kind: str, **fields) -> None:
        """Record one structured event in the per-replica flight
        recorder (no-op without an observability plane): the bounded
        black box :meth:`crash` dumps and chaos/soak tests query."""
        if self.flight is not None:
            self.flight.record(kind, **fields)

    # ------------------------------------------------------------------
    # diffs, callback, telemetry (reference causal_crdt.ex:344-404)

    def _batch_winner_records(self, touched: dict[int, Any], full: bool) -> dict[int, tuple]:
        """Winner records for a mutation batch's diff. Key-targeted batches
        use the row-gather winners; a batch containing ``clear`` touches
        every key, so it uses the full-map pass instead."""
        if full:
            return self._winner_records_rows(None)
        if not touched:
            return {}
        tkeys = np.zeros(_wire(max(len(touched), 1)), np.uint64)
        tkeys[: len(touched)] = list(touched.keys())
        w = self.model.winners_for_keys(self.state, jnp.asarray(tkeys))
        found, gid, ctr, valh, ts = _TR_DIFF_WINNERS.get(
            (w.found, w.gid, w.ctr, w.valh, w.ts)
        )
        out = {}
        for i, kh in enumerate(touched):
            if found[i]:
                out[kh] = (int(gid[i]), int(ctr[i]), int(valh[i]), int(ts[i]))
        return out

    def _winner_arrays_rows(
        self, rows: np.ndarray | None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """LWW winner entries within the given bucket rows (``None`` = the
        whole map, chunked) as flat numpy columns ``(key, gid, ctr, valh,
        ts)`` — array form so the 1M-key full read never runs a per-entry
        Python loop (each key appears once: winners are per-key unique and
        key sets of distinct rows are disjoint)."""
        if rows is None:
            # whole map: one full-table device pass (no row gather), one
            # batched device→host transfer, one nonzero + 5 flat gathers
            w = self.model.winner_all(self.state)
            win, key, gid, ctr, valh, ts = _TR_WINNER_ALL.get(w)
            u_idx, b_idx = np.nonzero(win)
            return tuple(
                a[u_idx, b_idx] for a in (key, gid, ctr, valh, ts)
            )  # type: ignore[return-value]
        cols: list[tuple] = []
        CHUNK = 4096
        for s in range(0, len(rows), CHUNK):
            chunk = rows[s : s + CHUNK]
            padded = np.full(_pow2(len(chunk)), -1, np.int32)  # constant-shape chunk: exact tier
            padded[: len(chunk)] = chunk
            w = self.model.winner_rows(self.state, jnp.asarray(padded))
            win, key, gid, ctr, valh, ts = _TR_WINNER_ROWS.get(
                (w.win, w.key, w.gid, w.ctr, w.valh, w.ts)
            )
            u_idx, b_idx = np.nonzero(win)
            cols.append(
                tuple(a[u_idx, b_idx] for a in (key, gid, ctr, valh, ts))
            )
        if not cols:  # empty rows (e.g. an all-padding EntriesMsg)
            return (
                np.zeros(0, np.uint64),
                np.zeros(0, np.uint64),
                np.zeros(0, np.uint32),
                np.zeros(0, np.uint32),
                np.zeros(0, np.int64),
            )
        return tuple(np.concatenate(c) for c in zip(*cols))  # type: ignore[return-value]

    def _winner_records_rows(self, rows: np.ndarray | None) -> dict[int, tuple]:
        """Winner records keyed by key hash (dict form, for diff compare)."""
        key, gid, ctr, valh, ts = self._winner_arrays_rows(rows)
        return dict(
            zip(
                key.tolist(),
                zip(gid.tolist(), ctr.tolist(), valh.tolist(), ts.tolist()),
            )
        )

    def canonical_state(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Topology-independent canonical projection of the CRDT state:
        the sorted per-key LWW winner records plus the causal context
        re-keyed by writer gid (writer-slot assignment order and entry
        lane placement are arrival-order artifacts — two replicas that
        merged the same dot set in different orders agree on THIS
        projection bit-for-bit). Returns ``(winners, gids, ctx)``:
        ``uint64[n, 5]`` rows of (key hash, writer gid, ctr, value
        hash, ts) in ascending order, the contributing writers' gids
        ascending (``uint64[g]``), and their ``uint32[L, g]`` context
        columns."""
        with self._lock:
            self._flush()
            key, gid, ctr, valh, ts = self._winner_arrays_rows(None)
            order = np.lexsort((ts, valh, ctr, gid, key))
            winners = np.stack(
                [
                    key[order].astype(np.uint64),
                    gid[order].astype(np.uint64),
                    ctr[order].astype(np.uint64),
                    valh[order].astype(np.uint64),
                    ts[order].astype(np.uint64),
                ],
                1,
            )
            st = self.state
            gids, ctx = _TR_CANONICAL_STATE.get((st.ctx_gid, st.ctx_max))
            # writers with an all-zero context column are arrival
            # artifacts (a slice's first-appearance-unioned writer table
            # registers its SOURCE's gid even when no dot of that writer
            # rode along — how many such slots exist depends on who you
            # happened to sync with), so the canonical context keeps
            # only writers that contributed coverage
            live = np.nonzero((gids != 0) & ctx.any(axis=0))[0]
            g_order = live[np.argsort(gids[live], kind="stable")]
            return winners, gids[g_order], ctx[:, g_order]

    def canonical_state_bytes(self) -> bytes:
        """:meth:`canonical_state` as one byte string — the parity gate
        hierarchical anti-entropy's tree-vs-flat legs assert in-run
        (``bench.py --tree``, ``tests/test_tree_sync.py``)."""
        winners, gids, ctx = self.canonical_state()
        return winners.tobytes() + gids.tobytes() + ctx.tobytes()

    def _note_state_changed(
        self, count_fn: Callable[[], int], keep_read_cache: bool = False
    ) -> None:
        """Invalidate read/tree caches and emit ``SYNC_DONE`` telemetry.
        ``count_fn`` runs only when a handler is attached and may return
        either a host int or a tuple of (device or host) scalars to sum
        — callers holding device accounting MUST pass the raw device
        values, not ``int()`` them: the mid-drain deferral window
        fetches every parked value with ONE batched ``device_get``, and
        a per-callback ``int()`` would serialise one sync round trip
        per dispatch group instead (measured ~90 ms/drain at depth-38
        coalesce fan-in — the cost that made the obs plane look 25%
        expensive). ``keep_read_cache`` is set by the local flush path
        when it already maintained the cache in place."""
        self._tree = None
        if not keep_read_cache:
            self._read_cache = None
            self._read_cache_kh = None
        # commit boundary: device state and host payload dict agree here
        # (payloads are registered before every path that reaches this),
        # so the serving plane's lock-free readers may pin it
        self._publish_serve()
        if telemetry.has_handlers(telemetry.SYNC_DONE):
            name = self.name

            def emit(n):
                if isinstance(n, tuple):
                    n = sum(int(c) for c in n)
                telemetry.execute(
                    telemetry.SYNC_DONE,
                    {"keys_updated_count": int(n)},
                    {"name": name},
                )
            if self._telemetry_defer is not None:
                # mid-drain: park the readback; process_pending flushes
                # after every group has dispatched (same events, same
                # per-replica order, no pipeline stall)
                self._telemetry_defer.append((count_fn, emit))
            else:
                emit(count_fn())

    def _emit_diffs(
        self,
        touched: dict[int, Any],
        before: dict,
        after: dict,
        keep_read_cache: bool = False,
    ) -> None:
        """Reference emission rules (``causal_crdt.ex:344-381``): telemetry
        counts internal (dot-level) changes; the user callback compares
        read values, so no-op re-adds are silent and a present-but-``None``
        value emits a remove diff."""
        internal_changed = 0
        diffs = []
        mask = self.num_buckets - 1
        for kh, term in touched.items():
            b, a = before.get(kh), after.get(kh)
            if b != a:
                internal_changed += 1
            old_rec = self._payloads.get((b[0], kh & mask, b[1])) if b else None
            new_rec = self._payloads.get((a[0], kh & mask, a[1])) if a else None
            old_val = old_rec[1] if old_rec else None
            new_val = new_rec[1] if new_rec else None
            if old_val == new_val:
                continue
            if new_val is None:
                diffs.append(("remove", term))
            else:
                diffs.append(("add", term, new_val))

        self._note_state_changed(lambda: internal_changed, keep_read_cache)
        if diffs and self.on_diffs is not None:
            if isinstance(self.on_diffs, tuple):
                fn, extra = self.on_diffs
                fn(*extra, diffs)
            else:
                self.on_diffs(diffs)

    def _read_all(self) -> dict:
        return self._read_pairs()[0]

    def _rebuild_read_cache(self) -> dict:
        """Full winner pass priming the incremental cache: the canonical-
        hash map enables maintenance only when no terms collapsed."""
        out, kh_map = self._read_pairs()
        self._read_cache_kh = kh_map
        return out

    def _read_pairs(self) -> "tuple[dict, dict | None]":
        # payload records are (key_term, value) pairs, so the winning
        # dots' records feed dict() directly — one C-level pass (bulk
        # __getitem__ via map) instead of a Python loop with a second
        # per-key _key_terms lookup (VERDICT r3 weak #5: 1M-key read)
        key, gid, ctr, _valh, ts = self._winner_arrays_rows(None)

        def build(k, g, c):
            bucket = (k & np.uint64(self.num_buckets - 1)).astype(np.int64)
            dots = zip(g.tolist(), bucket.tolist(), c.tolist())
            try:
                return dict(map(self._payloads.__getitem__, dots))
            except TypeError:
                for term, _value in self._payloads.values():
                    try:
                        hash(term)
                    except TypeError:
                        raise TypeError(
                            f"key term {term!r} is unhashable in Python; use "
                            "read_items() for maps with unhashable keys"
                        ) from None
                raise

        out = build(key, gid, ctr)
        if len(out) == len(key):
            # no ==-collapsed terms: incremental maintenance is sound,
            # and insertion order was irrelevant (all dict keys distinct)
            return out, dict(zip(out.keys(), key.tolist()))
        # ==-equal terms with distinct canonical keys exist (1 vs True):
        # the dict view is lossy. Rebuild inserting in ascending LWW
        # order (ts, gid, ctr) so the collapse deterministically keeps
        # the LWW-greatest write's value on every replica.
        order = np.lexsort((ctr, gid, ts))
        return build(key[order], gid[order], ctr[order]), None

    def _read_all_items(self) -> list[tuple[Any, Any]]:
        key, gid, ctr, _valh, _ts = self._winner_arrays_rows(None)
        bucket = (key & np.uint64(self.num_buckets - 1)).astype(np.int64)
        dots = zip(gid.tolist(), bucket.tolist(), ctr.tolist())
        return list(map(self._payloads.__getitem__, dots))

    def read_items(self) -> list[tuple[Any, Any]]:
        """Read as (key, value) pairs — supports unhashable key terms
        (Python dicts can't key on them; Elixir maps can)."""
        with self._lock:
            self._flush()
            return self._read_all_items()

    # ------------------------------------------------------------------
    # anti-entropy (reference causal_crdt.ex:252-335)

    def _ensure_tree(self) -> "_LazyLevels":
        if self._tree is None:
            self._tree = _LazyLevels(self.model.tree_from_leaves(self.state.leaf))
        return self._tree

    def sync_to_all(self) -> None:
        """One sync round to all monitored neighbours (reference
        ``sync_interval_or_state_to_all``, ``causal_crdt.ex:252-289``):
        first push any own fresh deltas directly (delta mode — the walk
        then usually finds the trees already equal), then open the
        digest-walk round (the repair + transitive-relay path)."""
        with tracing.annotate("crdt.sync_tick"):
            with self._lock:
                self._flush()
                if self.tree_gossip:
                    self._tree_probe_down()
                self._monitor_neighbours()
                self._push_deltas()
                self._open_walks()
            # the tick's relay epoch: everything merged since the last
            # flush re-emits as ONE merged slice per tree link (no-op
            # when flat)
            self._relay_flush()

    def _open_walks(self, send=None) -> None:
        """Open digest-walk rounds toward every monitored neighbour —
        the tail of :meth:`sync_to_all`, factored out so the fleet's
        batched sync tick (which pre-builds trees and pre-extracts
        pushes across members) runs the identical bookkeeping. Caller
        holds the lock."""
        opened = 0
        for n in list(self._monitors):
            if n == self.addr:
                continue
            opened += bool(self._open_walk(n, send))
        if opened:
            self._flight("sync_open", peers=opened, seq=self._seq)
            if self._lag is not None:
                # the origin's propagation-round clock: one round per
                # tick that actually opened walks (lag samples report
                # how many of these they waited through)
                self._lag.note_round(self.addr)

    def _open_walk(self, n, send=None) -> bool:
        """Open one digest-walk round toward ``n`` (the classic
        ``DiffMsg`` opener, factored out so the log-shipping horizon
        fallback can start a walk outside the periodic tick). Respects
        the ≤1-in-flight slot; returns whether a round was opened.
        Caller holds the lock."""
        now = time.monotonic()
        expiry = self._outstanding.get(n)
        if expiry is not None and now < expiry:
            return False  # ≤1 in-flight sync per neighbour
        tree = self._ensure_tree()
        root = np.zeros(1, np.int64)
        blocks = sync_proto.make_blocks(tree, 0, root, self.levels_per_round)
        # openers advertise the log horizon (memoised — no disk read on
        # the tick path) so the peer can choose log-shipped catch-up
        horizon = (
            self._wal.horizon()
            if self.log_shipping and self._wal is not None
            else None
        )
        msg = sync_proto.DiffMsg(
            originator=self.addr, frm=self.addr, to=n, level=0, idx=root,
            blocks=blocks, seq=self._seq, log_horizon=horizon,
        )
        if (self.transport.send if send is None else send)(n, msg):
            self._outstanding[n] = now + self.sync_timeout
            # ack watermark bookkeeping: an eventual AckMsg for
            # this round proves the peer held everything we had
            # when the round OPENED. Expired rounds may overlap
            # in flight; keep the MINIMUM open seq so a late ack
            # from the older round can't claim the newer one's
            # coverage.
            self._sync_open_seq[n] = min(
                self._sync_open_seq.get(n, self._seq), self._seq
            )
            return True
        logger.debug("tried to sync with a dead neighbour: %r", n)
        return False

    def _push_deltas(self, send=None) -> None:
        """Eagerly push this replica's own fresh dots to each neighbour as
        delta-interval slices (Almeida et al.'s delta mode): per neighbour
        a per-bucket cursor tracks the highest own counter already pushed;
        buckets with newer counters ship their ``(cursor, ctx_max]``
        interval directly — O(delta), no walk rounds. A lost push leaves
        the next one non-contiguous at the receiver, which answers with a
        ``GetDiffMsg`` repair (see ``_handle_entries_inner``). Bounded by
        ``max_sync_size`` bucket rows per neighbour per tick.

        Split into plan (``_eager_jobs``) / extract / emit steps so the
        fleet's batched sync tick can run many members' extractions as
        ONE vmapped dispatch — this solo form IS plan+extract+emit in
        sequence, so the two paths share every line of bookkeeping."""
        for job in self._eager_jobs():
            self._emit_push_job(job, self._extract_push_job(job), send)

    def _eager_jobs(self) -> list:
        """Plan this tick's eager-push extractions (caller holds the
        lock): one ``_PushJob`` per neighbour cursor-group — in steady
        state every cursor is identical, so one slice extraction +
        payload gather fans out to all of them — plus the full-row jobs
        for kill-touched rows (removes, clears and overwriting adds —
        kills cannot ride an interval; oldest unique stamps first, so a
        truncated push advances the cursor to exactly the last pushed
        row)."""
        jobs: list = []
        if not self.eager_deltas:
            return jobs
        if self._own_ctr_cache is None:
            self._own_ctr_cache = _TR_OWN_CTR_CACHE.get(
                self.state.ctx_max[:, self.self_slot]
            )
        own = self._own_ctr_cache
        limit = int(min(self.max_sync_size, self.num_buckets))

        groups: dict[bytes, list] = {}
        for n in list(self._monitors):
            if n == self.addr:
                continue
            cur = self._push_cursor.get(n)
            if cur is None:
                cur = np.zeros(self.num_buckets, np.uint32)
                self._push_cursor[n] = cur
            groups.setdefault(cur.tobytes(), []).append((n, cur))
        for members in groups.values():
            cur0 = members[0][1]
            pending = np.nonzero(own > cur0)[0]
            if len(pending) == 0:
                continue
            pending = pending[:limit]
            rows = np.full(_wire(max(len(pending), 1)), -1, np.int32)
            rows[: len(pending)] = pending
            lo = np.zeros(len(rows), np.uint32)
            lo[: len(pending)] = cur0[pending]
            # the cursor targets are pinned at plan time: a concurrent
            # flush between a batched extract and the emit can only ADD
            # dots, and an advance to the planned values undershoots —
            # the next tick re-covers (idempotent), never overshoots
            jobs.append(
                _PushJob("delta", rows, lo, pending, members,
                         advance=own[pending].copy())
            )

        rm_groups: dict[int, list] = {}
        for n in list(self._monitors):
            if n == self.addr:
                continue
            rm_groups.setdefault(self._rm_cursor.get(n, 0), []).append(n)
        for rc, members in rm_groups.items():
            pend = np.nonzero(self._row_touch_seq > rc)[0]
            if len(pend) == 0:
                continue
            order = np.argsort(self._row_touch_seq[pend], kind="stable")
            pend = pend[order][:limit]
            new_cursor = int(self._row_touch_seq[pend[-1]])
            rows = np.full(_wire(max(len(pend), 1)), -1, np.int32)
            rows[: len(pend)] = pend
            jobs.append(
                _PushJob("rows", rows, None, pend, members,
                         new_cursor=new_cursor)
            )
        return jobs

    def _extract_push_job(self, job: "_PushJob"):
        """Solo (per-replica) extraction of one planned push job — the
        fleet substitutes the matching lane of one vmapped extraction,
        bit-for-bit the same slice."""
        with tracing.annotate("crdt.slice_extract"):
            if job.kind == "delta":
                return self.model.extract_own_delta(
                    self.state,
                    jnp.asarray(job.rows),
                    jnp.int32(self.self_slot),
                    jnp.uint64(self.node_id),
                    jnp.asarray(job.lo),
                )
            return self.model.extract_rows(self.state, jnp.asarray(job.rows))

    def _emit_push_job(self, job: "_PushJob", sl, send=None) -> None:
        """Fan one extracted push slice out to the job's peers and
        advance their cursors on successful sends — THE shared emission
        tail of the solo and fleet egress paths (caller holds the
        lock). ``sl`` may be device-resident (solo) or an already-
        fetched host-form slice (fleet batched)."""
        send = self.transport.send if send is None else send
        if job.kind == "delta":
            peers = [n for n, _cur in job.peers]
        else:
            peers = job.peers
        bodies, payloads = self._slice_bodies(sl, job.rows, peers)
        buckets = job.pending.astype(np.int64)
        for p in job.peers:
            n = p[0] if job.kind == "delta" else p
            msg = sync_proto.EntriesMsg(
                originator=self.addr,
                frm=self.addr,
                to=n,
                buckets=buckets,
                arrays=bodies[n],
                payloads=payloads,
            )
            if send(n, msg):
                if job.kind == "delta":
                    p[1][job.pending] = job.advance
                else:
                    self._rm_cursor[n] = job.new_cursor

    def _monitor_neighbours(self) -> None:
        topo = self._tree_refresh()
        if topo is None:
            targets = list(self._neighbours)
        else:
            links = topo.links(self.addr)
            now = time.monotonic()
            for a in [
                a for a, t in self._tree_reverse.items() if t <= now
            ]:
                # the peer stopped syncing us: its view caught up (or it
                # left) — retire the reverse edge
                del self._tree_reverse[a]
                if a not in links and a in self._monitors:
                    self.transport.demonitor(self.addr, a)
                    self._monitors.discard(a)
            targets = links + [
                a for a in self._tree_reverse if a not in links
            ]
        for n in targets:
            if n in self._monitors:
                continue
            if self.transport.monitor(self.addr, n):
                # covers Down-then-up rejoins too: the caller
                # (sync_to_all) opens a round toward every monitor right
                # after this, and the opener's seq + log horizon lets the
                # rejoined peer choose log-shipped catch-up over the walk
                self._monitors.add(n)
                if n in self._tree_down:
                    # a tree link came back: re-derive so the rejoined
                    # member regains its deterministic slot
                    self._tree_down.discard(n)
                    self._tree_topo = None
            else:
                logger.debug("tried to monitor a dead neighbour: %r", n)
                if topo is not None and n != self.addr:
                    # an unmonitorable TREE LINK is a down observation:
                    # re-derive now instead of stalling this edge until
                    # a Down message that may never come (we were not
                    # monitoring yet) — the deterministic mid-epoch
                    # re-parent path
                    self._tree_down.add(n)
                    self._tree_topo = None

    # -- hierarchical anti-entropy (ISSUE 15 tentpole) -------------------
    #
    # Tree mode re-points the EXISTING sync machinery at the replica's
    # spanning-tree links instead of the whole neighbour set: the
    # monitors (and through them _eager_jobs / _open_walks / the
    # full-row push) only ever cover links, so own deltas ride the
    # unchanged delta-interval path up/down one edge. What's new is the
    # RELAY: merged inbound slices are re-emitted onward (coalesced —
    # one merged extraction per link per epoch, not N forwarded
    # frames), which is what turns a tree of bounded-degree edges into
    # whole-fleet propagation without per-generation walk latency.

    def _tree_refresh(self) -> "treesync.TreeTopology | None":
        """The current spanning tree, derived lazily and memoised until
        membership/failure state moves — or ``None`` when this replica
        gossips flat (tree mode off, or degraded past
        ``tree_degrade_ratio`` locally-observed down members). Caller
        holds the lock."""
        if not self.tree_gossip:
            return None
        members = set(self._neighbours) | {self.addr}
        down = self._tree_down & members
        if treesync.too_damaged(
            len(members), len(down), self.tree_degrade_ratio
        ):
            if not self._tree_degraded:
                self._tree_degraded = True
                self._tree_topo = None
                self._flight(
                    "tree_degrade", down=len(down), members=len(members)
                )
                self._tree_telemetry(None, len(members), len(down))
            return None
        if self._tree_degraded:
            # membership recovered: re-derive out of flat fallback
            self._tree_degraded = False
            self._tree_topo = None
        topo = self._tree_topo
        if topo is not None:
            return topo
        transport = self.transport
        topo = treesync.derive_tree(
            members,
            fanout=self.tree_fanout,
            seed=self.tree_seed,
            down=down,
            group_key=lambda a: treesync.group_of(transport, a),
        )
        self._tree_topo = topo
        # monitors narrow to the new links (+ live reverse edges); a
        # dropped link must not keep feeding _eager_jobs/_open_walks
        # (stale cursors stay — soft state, keyed per addr, re-covered
        # if the edge ever returns)
        links = set(topo.links(self.addr)) | set(self._tree_reverse)
        for a in [m for m in self._monitors if m not in links]:
            self.transport.demonitor(self.addr, a)
            self._monitors.discard(a)
            self._outstanding.pop(a, None)
        self._flight(
            "tree_epoch", epoch=topo.epoch, role=topo.role(self.addr),
            tier=int(topo.tier.get(self.addr, 0)), depth=topo.depth,
        )
        self._tree_telemetry(topo, len(members), len(down))
        return topo

    _TREE_ROLE_CODE = {"leaf": 0, "relay": 1, "root": 2}

    def _tree_telemetry(self, topo, members: int, down: int) -> None:
        if telemetry.has_handlers(telemetry.TREE_TOPOLOGY):
            telemetry.execute(
                telemetry.TREE_TOPOLOGY,
                {
                    "depth": 0 if topo is None else topo.depth,
                    "fanout": self.tree_fanout,
                    "tier": (
                        0 if topo is None
                        else int(topo.tier.get(self.addr, 0))
                    ),
                    "role": (
                        0 if topo is None
                        else self._TREE_ROLE_CODE[topo.role(self.addr)]
                    ),
                    "members": members,
                    "down": down,
                    "degraded": int(topo is None),
                },
                {"name": self.name},
            )

    def _tree_probe_down(self) -> None:
        """Throttled liveness probe of locally-down NON-link members (a
        link rejoin is observed by ``_monitor_neighbours`` directly):
        without this, a down member that never re-enters our links would
        stay excluded from the tree forever. Caller holds the lock."""
        if not self._tree_down:
            return
        now = time.monotonic()
        if now < self._tree_probe_ts + max(2 * self.sync_interval, 1.0):
            return
        self._tree_probe_ts = now
        rejoined = [a for a in self._tree_down if self.transport.alive(a)]
        if rejoined:
            self._tree_down.difference_update(rejoined)
            self._tree_topo = None

    def _relay_note_merge(self, msgs: list, counts_fn, offsets=None) -> None:
        """Record one committed merge for later relay stamping: each
        message's (source, bucket rows) park with the kernel's raw
        insert/kill count accessor until the next ``_relay_flush``,
        which fetches every parked accounting pytree in ONE batched
        ``device_get`` and stamps pending rows toward every tree link
        EXCEPT the source edge — and ONLY for messages whose merge
        actually changed state. The changed-only gate is load-bearing,
        not an optimisation: a no-op merge relays nothing, so a cycle
        formed by transiently divergent tree views (mid-churn, before
        every replica observed the same Down) terminates as soon as the
        content stops being news. ``counts_fn`` must hand back the raw
        device values (never ``int()`` them here — that would serialise
        a sync round trip per dispatch group, the exact cost class the
        drain's deferral window exists to batch). Caller holds the
        lock."""
        if not self.tree_gossip or self._replaying:
            return
        topo = self._tree_refresh()
        if topo is None or not topo.links(self.addr):
            return
        metas = []
        for m in msgs:
            rows = [int(b) for b in np.asarray(m.buckets).tolist()]
            nbytes = sum(
                int(v.nbytes)
                for v in m.arrays.values()
                if hasattr(v, "nbytes")
            )
            metas.append((m.frm, rows, nbytes))
        self._relay_defer.append((metas, counts_fn, offsets))

    @staticmethod
    def _relay_changed_per_msg(data, offsets, depth: int) -> list:
        """Per-message changed-entry counts from one fetched accounting
        pytree: whole-slice scalars for a solo merge, per-row arrays +
        member offsets for a grouped dispatch."""
        ins, kill = data
        if offsets is None:
            return [int(np.asarray(ins)) + int(np.asarray(kill))]
        tot = np.cumsum(np.asarray(ins, np.int64) + np.asarray(kill, np.int64))
        out = []
        for lo, hi in offsets[:depth]:
            if hi > lo:
                out.append(int(tot[hi - 1]) - (int(tot[lo - 1]) if lo else 0))
            else:
                out.append(0)
        return out

    def _relay_stamp_deferred(self, topo) -> None:
        """Drain the parked merges into per-link pending rows (caller
        holds the lock): one batched transfer for every parked count
        pytree, then host-only stamping."""
        defer, self._relay_defer = self._relay_defer, []
        if not defer:
            return
        links = topo.links(self.addr)
        fetched = _TR_RELAY_ACCOUNTING.get([fn() for _m, fn, _o in defer])
        for (metas, _fn, offsets), data in zip(defer, fetched):
            changed = self._relay_changed_per_msg(data, offsets, len(metas))
            for (frm, rows, nbytes), n_changed in zip(metas, changed):
                if not rows or not n_changed:
                    continue
                self._relay_rx_pending += nbytes
                for a in links:
                    if a == frm:
                        continue
                    pend = self._relay_pending.setdefault(a, {})
                    for b in rows:
                        pend[b] = None
                    self._relay_fold[a] = self._relay_fold.get(a, 0) + 1

    def _relay_flush(self, send=None) -> int:
        """Re-emit pending relayed rows: for each group of links whose
        pending window is identical (in steady fan-in that is every
        non-source link), extract the union of touched buckets from the
        MERGED state ONCE (``extract_rows`` — the walk's own idempotent
        full-row transfer shape, so a lost re-emission heals like any
        lost walk transfer) and fan the slice out — N inbound children
        frames become one merged re-emission upward/downward per epoch,
        PR 3's fan-in coalescing generalised from one mailbox to
        multi-hop. Bounded by ``max_sync_size`` rows per link per
        flush; the remainder stays pending. Returns messages emitted."""
        if not self.tree_gossip:
            return 0
        faultpoint("replica.relay.flush")
        with self._lock:
            if not self._relay_pending and not self._relay_defer:
                return 0
            topo = self._tree_refresh()
            if topo is None:
                # degraded to flat: every member hears writers directly
                # again, and the periodic walks heal anything in flight
                self._relay_defer.clear()
                self._relay_pending.clear()
                self._relay_fold.clear()
                self._relay_rx_pending = 0
                return 0
            self._relay_stamp_deferred(topo)
            if not self._relay_pending:
                return 0
            t0 = time.perf_counter()
            links = set(topo.links(self.addr))
            for a in [a for a in self._relay_pending if a not in links]:
                self._relay_pending.pop(a, None)
                self._relay_fold.pop(a, None)
            limit = int(min(self.max_sync_size, self.num_buckets))
            groups: dict[tuple, list] = {}
            for a, pend in self._relay_pending.items():
                batch = tuple(list(pend)[:limit])
                if batch:
                    groups.setdefault(batch, []).append(a)
            if not groups:
                return 0
            send = self.transport.send if send is None else send
            emitted: list[dict] = []
            for batch, peers in groups.items():
                rows = np.full(_wire(max(len(batch), 1)), -1, np.int32)
                rows[: len(batch)] = batch
                sl = self.model.extract_rows(self.state, jnp.asarray(rows))
                bodies, payloads = self._slice_bodies(sl, rows, peers)
                buckets = np.asarray(batch, np.int64)
                for a in peers:
                    msg = sync_proto.EntriesMsg(
                        originator=self.addr,
                        frm=self.addr,
                        to=a,
                        buckets=buckets,
                        arrays=bodies[a],
                        payloads=payloads,
                    )
                    if not send(a, msg):
                        continue
                    pend = self._relay_pending.get(a)
                    drained = False
                    if pend is not None:
                        for b in batch:
                            pend.pop(b, None)
                        if not pend:
                            self._relay_pending.pop(a, None)
                            drained = True
                    # fold accounting is per COMPLETED window: a
                    # max_sync_size-truncated flush leaves the link's
                    # fold count in place (new inbound keeps adding to
                    # it) and this continuation emission contributes no
                    # depth sample — attributing the whole count to the
                    # first partial emission would skew the coalesce-
                    # depth histogram with one inflated and K spurious
                    # zero samples
                    folded = self._relay_fold.pop(a, 0) if drained else None
                    tx = sum(
                        int(v.nbytes)
                        for v in bodies[a].values()
                        if hasattr(v, "nbytes")
                    )
                    self._relay_reemits += 1
                    self._relay_entries_emitted += len(payloads)
                    self._relay_rows_emitted += len(batch)
                    self._relay_tx_bytes += tx
                    meas = {
                        "entries": len(payloads),
                        "buckets": len(batch),
                        "tx_bytes": tx,
                        "rx_bytes": 0,
                        "duration_s": 0.0,
                    }
                    if folded is not None:
                        self._relay_msgs_folded += folded
                        self._relay_depth_hist[folded] = (
                            self._relay_depth_hist.get(folded, 0) + 1
                        )
                        meas["depth"] = folded
                    emitted.append(meas)
            if not emitted:
                return 0
            rx, self._relay_rx_pending = self._relay_rx_pending, 0
            self._relay_rx_bytes += rx
            if telemetry.has_handlers(telemetry.TREE_RELAY):
                # flush-level quantities ride the first message's row
                # (the batch fold sums them; per-message histograms stay
                # exact either way)
                emitted[0]["rx_bytes"] = rx
                emitted[0]["duration_s"] = time.perf_counter() - t0
                telemetry.execute_many(
                    telemetry.TREE_RELAY,
                    emitted,
                    {
                        "name": self.name,
                        "tier": str(int(topo.tier.get(self.addr, 0))),
                    },
                )
            return len(emitted)

    # -- message handlers ------------------------------------------------

    def handle(self, msg) -> None:
        with self._lock:
            if isinstance(msg, sync_proto.DiffMsg):
                self._handle_diff(msg)
            elif isinstance(msg, sync_proto.GetDiffMsg):
                self._handle_get_diff(msg)
            elif isinstance(msg, sync_proto.EntriesMsg):
                self._handle_entries(msg)
            elif isinstance(msg, sync_proto.GetLogMsg):
                self._handle_get_log(msg)
            elif isinstance(msg, sync_proto.LogChunkMsg):
                self._handle_log_chunk(msg)
            elif isinstance(msg, sync_proto.AckMsg):
                self._outstanding.pop(msg.clear_addr, None)
                # trees were equal when the acked round's walk ran, so
                # the peer covers at least our state at round open — the
                # membership watermark WAL compaction reclaims up to
                open_seq = self._sync_open_seq.pop(msg.clear_addr, None)
                if open_seq is not None:
                    self._ack_seq[msg.clear_addr] = max(
                        self._ack_seq.get(msg.clear_addr, 0), open_seq
                    )
            elif isinstance(msg, sync_proto.FleetFrameMsg):
                self._handle_fleet_frame(msg)
            elif isinstance(msg, Down):
                self._monitors.discard(msg.addr)
                self._outstanding.pop(msg.addr, None)
                if self.tree_gossip:
                    # deterministic mid-epoch re-parent: every replica
                    # that observed this Down derives the same tree over
                    # the surviving members on its next refresh (or
                    # degrades to flat gossip past the damage threshold)
                    self._tree_down.add(msg.addr)
                    self._tree_topo = None
                    self._relay_pending.pop(msg.addr, None)
                    self._relay_fold.pop(msg.addr, None)
                    self._tree_reverse.pop(msg.addr, None)
                # a dead peer must not gate segment reclaim forever
                self._ack_seq.pop(msg.addr, None)
                self._sync_open_seq.pop(msg.addr, None)
                # a catch-up stream dies with its server: applied chunks
                # were ordinary idempotent merges, so aborting mid-stream
                # leaves us consistent — the watermark stands at the last
                # fully applied chunk, and when the peer rejoins its
                # next round opener restarts the stream from there
                self._catchup.pop(msg.addr, None)
            else:
                raise TypeError(f"unknown message: {msg!r}")

    def _handle_fleet_frame(self, msg: sync_proto.FleetFrameMsg) -> None:
        """Fan a fleet egress envelope out (ISSUE 10). The TCP transport
        decodes ``_FLEETF`` frames before delivery, so this arm is the
        fallback for transports that hand the envelope to a mailbox
        whole: entries addressed to this replica dispatch through the
        normal ladder (the RLock makes the recursive :meth:`handle`
        re-entry a no-op acquire), everything else forwards unopened —
        REGROUPED per next-hop endpoint into one rewritten envelope
        each (ISSUE 15: an intermediate hop rewrites ``entries`` in
        place, inner messages untouched) when the transport can frame,
        with the per-member send as the renegotiated-down/legacy
        fallback."""
        def local(to, m) -> bool:
            if to == self.addr or to == self.name:
                self.handle(m)
                return True
            return False

        forward_fleet_entries(self.transport, msg.entries, local)

    def _handle_diff(self, msg: sync_proto.DiffMsg) -> None:
        with tracing.annotate("crdt.walk"):
            self._handle_diff_inner(msg)

    def _handle_diff_inner(self, msg: sync_proto.DiffMsg) -> None:
        if (
            self.tree_gossip
            and msg.frm != self.addr
            and msg.originator == msg.frm
        ):
            # ORIGINATOR frames only (openers + the originator's deeper
            # blocks): those prove the peer's own view has us as a sync
            # target. Mid-walk replies in rounds WE originated must not
            # qualify — our own polling of a reverse peer would then
            # refresh its deadline forever, turning every transient
            # view divergence into a permanent extra flat edge.
            topo = self._tree_refresh()
            if topo is not None and msg.frm not in topo.links(self.addr):
                # a non-link peer syncing us: ITS tree view has us as a
                # link (divergent views mid-churn) — sync back toward it
                # until it stops, so every view-edge is bidirectional
                # and mixed-epoch topologies still converge
                self._tree_reverse[msg.frm] = time.monotonic() + max(
                    6 * self.sync_interval, 3.0
                )
        self._flush()
        tree = self._ensure_tree()
        end_level, end_idx = sync_proto.walk(
            tree, msg.level, msg.idx, msg.blocks, self.max_sync_size
        )
        if len(end_idx) == 0:
            # trees agree under every compared node ({:ok, []} path).
            # For a ROUND OPENER that is a whole-tree proof: digest
            # equality ⇒ content equality ⇒ we cover the sender's state
            # at its stamped seq — the applied watermark log-shipping
            # resumes from. (A walk can only end empty at a genuine
            # match: differing parents imply differing children in a
            # hash tree, so truncation never fakes an equality.)
            # Mid-walk frames re-verify only the FRONTIER subtrees: the
            # rest was proven against the sender's state at ROUND OPEN,
            # so claiming a later frame's stamp would over-claim any
            # non-frontier writes the sender applied mid-round — those
            # frames teach us nothing watermark-safe, like the ack path
            # whose _sync_open_seq bookkeeping bounds claims at round
            # open for exactly this reason.
            if (
                msg.level == 0
                and msg.originator == msg.frm
                and msg.seq > self._applied_seq.get(msg.frm, 0)
            ):
                self._note_applied_seq(msg.frm, int(msg.seq))
            cleared = self.addr if msg.originator != self.addr else msg.frm
            self.transport.send(msg.originator, sync_proto.AckMsg(clear_addr=cleared))
            return
        # log-shipping mode decision (ISSUE 4): on a DIVERGING round
        # opener from a log-capable originator, a peer whose applied
        # watermark sits within the advertised horizon answers with a
        # GetLogMsg — the divergence is exactly the originator's log
        # suffix past the watermark, so one streamed replay replaces the
        # level walk (the stream's completion ack clears the round's
        # in-flight slot). Every mid-walk frame continues the classic
        # walk unchanged.
        #
        # PAST the horizon (watermark < log_horizon) the walk must heal
        # the compacted prefix regardless — and a digest walk heals
        # every difference it finds, suffix included, so suffix chunks
        # on top of it are only worth their round trips when the
        # servable suffix DWARFS the walk-bound prefix (ROADMAP
        # follow-up (a)): then the chunks collapse many truncated
        # walk-transfer rounds into a few big streamed ones and the
        # walk is left a short prefix. Otherwise the peer skips the
        # suffix chunks entirely and goes straight to the walk — the
        # chunks-plus-walk shape measured ~0.8x against the pure walk.
        if (
            self.log_shipping
            and msg.level == 0
            and msg.originator == msg.frm
            and msg.originator != self.addr
            and msg.log_horizon is not None
            and msg.seq > self._applied_seq.get(msg.frm, 0)
            and self._applied_seq.get(msg.frm, 0)
            >= self._catchup_walk_floor.get(msg.frm, 0)
        ):
            # (the strict `seq > watermark` leg matters: divergence with
            # a watermark at-or-past the opener's seq means the sender
            # REGRESSED (recovered with loss) or we hold more than it —
            # its log has nothing for us, so the classic walk must carry
            # the edge; an empty catch-up stream would just false-ack)
            watermark = self._applied_seq.get(msg.frm, 0)
            if watermark >= msg.log_horizon or (
                msg.seq - msg.log_horizon
                >= self.catchup_suffix_ratio * (msg.log_horizon - watermark)
            ):
                self._request_catchup(msg.frm)
                return
        if end_level == self.tree_depth:
            buckets = end_idx[: int(min(self.max_sync_size, len(end_idx)))]
            if msg.originator == self.addr:
                # walk ended at the originator: ship entries directly
                self._send_entries(to=msg.frm, buckets=buckets, originator=self.addr)
                self._outstanding.pop(msg.frm, None)
            else:
                self.transport.send(
                    msg.originator,
                    sync_proto.GetDiffMsg(
                        originator=msg.originator, frm=self.addr, to=msg.originator, buckets=buckets
                    ),
                )
            return
        # continue the ping-pong with our own digests beneath the frontier
        blocks = sync_proto.make_blocks(tree, end_level, end_idx, self.levels_per_round)
        self.transport.send(
            msg.frm,
            sync_proto.DiffMsg(
                originator=msg.originator,
                frm=self.addr,
                to=msg.frm,
                level=end_level,
                idx=end_idx,
                blocks=blocks,
                seq=self._seq,
            ),
        )

    def _handle_get_diff(self, msg: sync_proto.GetDiffMsg) -> None:
        self._flush()
        self._send_entries(to=msg.frm, buckets=msg.buckets, originator=msg.originator)
        self._outstanding.pop(msg.frm, None)

    def _slice_payload_host(self, sl, rows: np.ndarray):
        """Host copies of the narrow slice columns plus the payload dict
        of every alive dot in the slice (needed on every plane: arbitrary
        Python terms live off-device). One numpy pass + a batched tolist
        beats per-entry scalar indexing ~10x on big slices (VERDICT r2
        weak #4); ``device_get`` on the tuple starts all four copies
        before blocking — one device sync per slice."""
        node_h, ctr_h, alive_h, gid_h = _TR_SLICE_PAYLOAD_DOTS.get(
            (sl.node, sl.ctr, sl.alive, sl.ctx_gid)
        )
        u_idx, b_idx = np.nonzero(alive_h)
        gid_l = gid_h[node_h[u_idx, b_idx]].tolist()
        row_l = rows[u_idx].tolist()
        ctr_l = ctr_h[u_idx, b_idx].tolist()
        pay = self._payloads
        payloads = {dot: pay[dot] for dot in zip(gid_l, row_l, ctr_l)}
        host = {"node": node_h, "ctr": ctr_h, "alive": alive_h, "ctx_gid": gid_h}
        return host, payloads

    def _slice_arrays(self, sl, host: dict, target_device, rows: np.ndarray) -> dict:
        """The EntriesMsg column dict for one data plane (SURVEY §5.8
        hybrid):

        - ``target_device=None`` — host plane: columns become numpy
          (pickleable for cross-host transports), reusing the host
          copies the payload build already made.
        - ``target_device=<jax device>`` — device plane: one pytree
          ``device_put`` places all columns directly on the receiver's
          device (rides ICI between chips; a same-device put is free),
          never round-tripping through host buffers.
        """
        cols = {c: getattr(sl, c) for c in _SLICE_COLUMNS}
        cols["ctx_rows"], cols["ctx_lo"], cols["ctx_gid"] = sl.ctx_rows, sl.ctx_lo, sl.ctx_gid
        if target_device is None:
            # one audited batched fetch of the columns the payload pass
            # did not already host-copy (key order preserved)
            got = _TR_SLICE_WIRE.get(
                {c: v for c, v in cols.items() if c not in host}
            )
            arrays = {c: host[c] if c in host else got[c] for c in cols}
        else:
            arrays = _TR_SLICE_PLACE.put(cols, target_device)
        arrays["rows"] = rows  # row indices are control metadata: numpy
        return arrays

    def _slice_wire(self, sl, rows: np.ndarray, target_device=None) -> tuple[dict, dict]:
        """Single-plane wire form of a RowSlice: the column arrays
        (context rows for exactly the shipped buckets — bucket-atomic
        sync: coverage never outruns content) plus the payload dict."""
        with tracing.annotate("crdt.slice_wire"):
            host, payloads = self._slice_payload_host(sl, rows)
            return self._slice_arrays(sl, host, target_device, rows), payloads

    def _slice_bodies(self, sl, rows: np.ndarray, peers) -> tuple[dict, dict]:
        """Fan-out wire bodies: ONE arrays dict per distinct pinned
        device among ``peers`` (None = host plane), shared payloads.
        Mixed-placement clusters keep the device plane per group —
        a 64-neighbour fan-out across 8 devices builds 8 bodies, not 64
        and not a host fallback for everyone (VERDICT r3 weak #4).
        Returns ``({peer: arrays}, payloads)``."""
        with tracing.annotate("crdt.slice_wire"):
            host, payloads = self._slice_payload_host(sl, rows)
            device_of = getattr(self.transport, "device_of", None)
            groups: dict[Any, list] = {}
            for n in peers:
                d = device_of(n) if device_of is not None else None
                groups.setdefault(d, []).append(n)
            by_peer: dict[Any, dict] = {}
            for dev, members in groups.items():
                arrays = self._slice_arrays(sl, host, dev, rows)
                for n in members:
                    by_peer[n] = arrays
            return by_peer, payloads

    def _extract_rows_wire(self, buckets: np.ndarray, device) -> tuple[dict, dict]:
        """Extract the given bucket rows as one wire-tier-padded entries
        body for ``device``'s data plane — THE row-transfer shape,
        shared by walk entries transfers and log-shipping chunks so the
        padding convention cannot drift between them."""
        rows = np.full(_wire(max(len(buckets), 1)), -1, np.int32)
        rows[: len(buckets)] = np.asarray(buckets, np.int32)
        with tracing.annotate("crdt.slice_extract"):
            sl = self.model.extract_rows(self.state, jnp.asarray(rows))
        return self._slice_wire(sl, rows, device)

    def _device_of(self, peer):
        device_of = getattr(self.transport, "device_of", None)
        return device_of(peer) if device_of is not None else None

    def _send_entries(self, to, buckets: np.ndarray, originator) -> bool:
        arrays, payloads = self._extract_rows_wire(buckets, self._device_of(to))
        return self.transport.send(
            to,
            sync_proto.EntriesMsg(
                originator=originator,
                frm=self.addr,
                to=to,
                buckets=np.asarray(buckets, np.int64),
                arrays=arrays,
                payloads=payloads,
            ),
        )

    def _handle_entries(self, msg: sync_proto.EntriesMsg) -> None:
        with tracing.annotate("crdt.merge"):
            self._handle_entries_inner(msg)

    def _handle_entries_inner(self, msg: sync_proto.EntriesMsg) -> None:
        self._flush()
        t0 = time.perf_counter()
        a = msg.arrays
        ctx_rows = jnp.asarray(a["ctx_rows"])
        sl = self.model.RowSlice(
            rows=jnp.asarray(a["rows"]),
            key=jnp.asarray(a["key"]),
            valh=jnp.asarray(a["valh"]),
            ts=jnp.asarray(a["ts"]),
            node=jnp.asarray(a["node"]),
            ctr=jnp.asarray(a["ctr"]),
            alive=jnp.asarray(a["alive"]),
            ctx_rows=ctx_rows,
            # walk-located transfers ship full-row state slices (lo = 0);
            # eager delta pushes carry their exact interval lower bounds
            ctx_lo=jnp.asarray(a["ctx_lo"]),
            ctx_gid=jnp.asarray(a["ctx_gid"]),
        )
        rows_np = a["rows"]

        # the before/after winner passes are an O(U·B²) device compare per
        # synced bucket set — they exist only to feed the on_diffs callback
        # (reference: diff work feeds the callback, causal_crdt.ex:344-381);
        # without a subscriber, telemetry is fed from the merge kernel's own
        # insert/kill counts instead
        want_diffs = self.on_diffs is not None
        keys_b = self._winner_records_rows(rows_np[rows_np >= 0]) if want_diffs else {}
        # payloads first: diff values for incoming winners must resolve
        with tracing.annotate("crdt.payloads"):
            self._register_slice_payloads(msg.payloads)

        try:
            res = self._merge_with_growth(sl)
        except CtxGapError:
            # a delta-interval push is not contiguous with our context (an
            # earlier push was lost): ask the sender for the full rows —
            # the get_diff repair path (``causal_crdt.ex:112-123``)
            logger.debug(
                "delta push from %r gapped; requesting full rows", msg.frm
            )
            self._flight(
                "gap_repair", peer=str(msg.frm), buckets=int(len(msg.buckets))
            )
            self.transport.send(
                msg.frm,
                sync_proto.GetDiffMsg(
                    originator=self.addr,
                    frm=self.addr,
                    to=msg.frm,
                    buckets=np.asarray(msg.buckets),
                ),
            )
            # the payloads above went in without a merge — they must still
            # count toward the gc cadence, or a lossy link strands dead
            # payload entries the pressure counter never sees. (No
            # _maybe_gc here: the repair EntriesMsg re-ships payloads, so
            # pruning now would only churn.)
            self._gc_pressure += len(msg.payloads)
            return

        self._seq += 1
        # durability happens-before publication (crdtlint FAULT003): log
        # the merged slice before diffs/serve-pub see it, rolling the
        # seq back if the append fails so it still names the last
        # durable record
        try:
            self._durable(
                lambda: {
                    "kind": "entries",
                    "seq": self._seq,
                    "arrays": self._wal_arrays_host(a),
                    "payloads": dict(msg.payloads),
                }
            )
        except BaseException as e:
            self._commit_abort(e)
            raise
        # relay bookkeeping (ISSUE 15): the merged rows park for the
        # next flush's changed-only stamping toward every tree link
        # except the source edge — default-arg capture of JUST the two
        # count scalars (closing over ``res`` would pin the whole
        # MergeRowsResult, state included, across the relay window)
        self._relay_note_merge(
            [msg], lambda ins=res.n_inserted, kill=res.n_killed: (ins, kill)
        )
        if want_diffs:
            keys_a = self._winner_records_rows(rows_np[rows_np >= 0])
            touched: dict[int, Any] = {}
            for kh in set(keys_b) | set(keys_a):
                term = self._key_terms.get(kh)
                if term is not None:
                    touched[kh] = term
            self._emit_diffs(touched, keys_b, keys_a)
        else:
            # dot-level changed count (may count a key twice when a merge
            # both inserts a winner and kills a superseded entry — a
            # documented approximation of the reference's per-key diff count)
            self._note_state_changed(
            # default-arg capture of JUST the two count scalars: a
            # closure over ``res`` parks the whole MergeRowsResult —
            # including ``res.state`` — in the drain's deferral
            # window, pinning every superseded store generation and
            # defeating XLA's input-buffer reuse on each subsequent
            # merge (a full-store copy per dispatch)
            lambda ins=res.n_inserted, kill=res.n_killed: (ins, kill)
        )
        if telemetry.has_handlers(telemetry.SYNC_ROUND):
            telemetry.execute(
                telemetry.SYNC_ROUND,
                {
                    "duration_s": time.perf_counter() - t0,
                    "buckets": int(len(msg.buckets)),
                    # one payload per alive dot in the slice (_slice_wire
                    # builds the dict from np.nonzero(alive)), so this counts
                    # shipped entries from host data — the device-plane alive
                    # column is never reduced/read back just for telemetry
                    "entries": len(msg.payloads),
                },
                {
                    "name": self.name,
                    # which data plane carried the slice (observability for
                    # mixed-plane clusters); metadata, not measurements —
                    # measurements stay numeric/aggregatable
                    "plane": "host" if isinstance(a["key"], np.ndarray) else "device",
                },
            )
        # received payloads stick in the host dict even when the merge
        # superseded them, and every KILLED entry strands its payload —
        # a mass-remove wave carries near-zero payloads, so kills must
        # count too or the dict sits at peak size until enough inserts
        # arrive. (Runs only after the merge: pruning between the payload
        # update and the merge would drop dots about to become alive.)
        self._gc_pressure += len(msg.payloads) + int(_TR_INGEST_COUNTS.get(res.n_killed))
        self._maybe_gc()

    def _register_slice_payloads(self, payloads: dict) -> None:
        """Host bookkeeping for an accepted (or about-to-merge) slice's
        payload dict — idempotent, so grouped ingest may register a whole
        group up front and still fall back to per-slice handling."""
        self._payloads.update(payloads)
        for _dot, (key_term, _val) in payloads.items():
            self._key_terms[key_hash64(key_term)] = key_term

    # -- log-shipping catch-up (ISSUE 4 tentpole) ------------------------
    #
    # A rejoining or lagging peer's divergence has a KNOWN shape: the
    # suffix of this replica's delta log past the peer's last fully
    # observed seq. Serving that suffix replaces the O(rounds ×
    # max_sync_size) digest walk with a requester-paced stream of
    # full-row slices — one round trip per bounded chunk, landing on the
    # grouped-ingest fast path. The WAL range is used as a CHANGED-
    # BUCKET INDEX, not replayed literally: re-applying another writer's
    # ``batch`` ops here would re-mint dots under the wrong writer and
    # counters (our context may already be ahead via transitive
    # delivery) and a replayed remove would kill concurrent adds the
    # original never observed — breaking add-wins. Full-row slices
    # extracted from current state are the walk's own transfer shape,
    # so chunk replay is idempotent and bit-comparable with a walk.

    #: watermarks survive Down and set_neighbours churn ON PURPOSE (the
    #: rejoin is exactly when they pay off), so the dicts need a size
    #: bound instead of lifecycle pruning: beyond this many peers the
    #: least-recently-advanced watermark is evicted (that peer's next
    #: catch-up degrades to a walk — safe, just slower)
    MAX_PEER_WATERMARKS = 4096

    def _note_applied_seq(self, peer, seq: int) -> None:
        """Advance (never regress) the applied watermark for ``peer``,
        keeping the dict LRU-ordered and bounded; a watermark passing
        the peer's walk floor retires the floor (the walk has healed the
        unservable span the floor guarded)."""
        d = self._applied_seq
        cur = d.pop(peer, 0)  # pop+reinsert: insertion order ≈ recency
        d[peer] = max(cur, int(seq))
        if self._lag is not None and d[peer] > cur:
            # dot-provenance lag trace, zero wire changes: the watermark
            # advance is keyed on fields already on the wire (the
            # originator address + seq of the round opener / log chunk),
            # so every sampled commit of `peer` at-or-below it is now
            # visible HERE — the per-(origin, peer) convergence-lag and
            # propagation-round histograms fill from exactly this event
            self._lag.note_visible(self.addr, peer, d[peer])
        while len(d) > self.MAX_PEER_WATERMARKS:
            d.pop(next(iter(d)))
        floor = self._catchup_walk_floor
        if floor and d[peer] >= floor.get(peer, 0):
            floor.pop(peer, None)
        while len(floor) > self.MAX_PEER_WATERMARKS:
            floor.pop(next(iter(floor)))

    def _request_catchup(self, peer) -> None:
        """Open (or refresh) the one in-flight log-shipping catch-up
        stream toward ``peer``, resuming from our applied watermark of
        its history. Normally invoked as the peer-side answer to a
        diverging round opener (``_handle_diff``), so data keeps flowing
        originator → peer; callable directly for deterministic drives.
        Caller holds the lock."""
        if not self.log_shipping or peer == self.addr:
            return
        now = time.monotonic()
        st = self._catchup.get(peer)
        if st is not None and now < st["expiry"]:
            return  # requester-paced: ≤1 outstanding request per peer
        last = int(self._applied_seq.get(peer, 0))
        msg = sync_proto.GetLogMsg(
            frm=self.addr, to=peer, last_seq=last, applied_seq=last
        )
        if self.transport.send(peer, msg):
            self._flight("catchup_request", peer=str(peer), last_seq=last)
            self._catchup[peer] = {
                "t0": now,
                "expiry": now + self.sync_timeout,
                "chunks": 0,
                "horizon": False,
                # correlates chunks to THIS stream: a chunk answering an
                # older (timed-out, superseded) request has seq_lo below
                # the last request's cursor and must not pace follow-ups
                "last_req": last,
            }

    def _iter_log_records(self, lo: int, hi: int):
        """WAL records with ``lo < seq ≤ hi`` in seq order, pulled
        through the bounded range cursor (so one huge lag never loads
        the whole log into memory at once)."""
        cursor = lo
        while cursor < hi:
            records, next_seq, exhausted = self._wal.read_range(cursor, hi)
            yield from records
            if exhausted or next_seq == cursor:
                return
            cursor = next_seq

    def _scan_log_rows(self, lo: int, hi: int) -> tuple[int, set, int, bool, int | None]:
        """Consume records in ``(lo, hi]`` accumulating the touched-
        bucket set until the chunk row budget fills. Whole records only:
        the chunk's ``seq_hi`` becomes the peer's watermark, so a chunk
        must cover EVERY bucket its seq range touched. Records whose row
        effects cannot be served bounded-and-indexed are BARRIERS — an
        unknown kind (written by a newer build: effects unknowable
        here), or a ``clear`` touching more buckets than the hard row
        cap (shipping the whole keyspace in one frame would break the
        every-message-is-bounded invariant). The scan stops BEFORE a
        barrier; when the barrier is the first record, its seq is
        returned so the server can answer "walk through here, log-ship
        after" (an explicit horizon at the barrier). Returns
        ``(n_records, touched_rows, seq_hi, more, barrier_seq)``."""
        mask = self.num_buckets - 1
        hard_cap = 4 * self.catchup_chunk_rows
        touched: set[int] = set()
        n_rec = 0
        seq_hi = lo
        more = False
        barrier_seq: int | None = None
        for rec in self._iter_log_records(lo, hi):
            if len(touched) >= self.catchup_chunk_rows:
                more = True  # budget full: this record opens the next chunk
                break
            kind = rec.get("kind")
            rec_rows: set[int] | None = None
            if kind == "batch":
                rec_rows = set()
                for f, key_term, _v in rec["ops"]:
                    if f == "clear":
                        # a clear touches every bucket (the kill must
                        # reach rows now empty on both sides too); past
                        # the hard cap it is a barrier — classify it
                        # WITHOUT materializing the full keyspace set
                        rec_rows = (
                            set(range(self.num_buckets))
                            if self.num_buckets <= hard_cap
                            else None
                        )
                        break
                    rec_rows.add(int(key_hash64(key_term)) & mask)
            elif kind == "entries":
                rows = np.asarray(rec["arrays"]["rows"])
                rec_rows = set(rows[rows >= 0].tolist())
            # the union-size test short-circuits: the exact (allocating)
            # union only runs when the cheap count bound says it might
            # actually exceed the cap
            if rec_rows is None or (
                len(touched) + len(rec_rows) > hard_cap
                and len(touched | rec_rows) > hard_cap
            ):
                # barrier: stop before it; first-record barriers are
                # reported so the serve can point the walk at them
                if n_rec == 0:
                    barrier_seq = int(rec["seq"])
                else:
                    more = True
                break
            touched |= rec_rows
            n_rec += 1
            seq_hi = int(rec["seq"])
        return n_rec, touched, seq_hi, more, barrier_seq

    def _extract_catchup_slices(self, rows_sorted: np.ndarray, device) -> list:
        """Full-row entry slices (the walk's transfer shape, on the
        peer's data plane like every other entries transfer) for the
        touched buckets. Normally ONE slice per chunk — a whole chunk
        then merges in a single kernel dispatch, the ship-the-stream
        amortisation — splitting only when a record (e.g. a ``clear``)
        pushed the chunk past the row budget; the pow4 wire tiers keep
        the distinct extraction/merge compiles to a handful either way
        (small chunks land on the exact tiers walk transfers already
        compiled)."""
        limit = self.catchup_chunk_rows
        slices = []
        for s in range(0, len(rows_sorted), limit):
            part = np.asarray(rows_sorted[s : s + limit], np.int64)
            arrays, payloads = self._extract_rows_wire(part, device)
            slices.append({"buckets": part, "arrays": arrays, "payloads": payloads})
        return slices

    def _handle_get_log(self, msg: sync_proto.GetLogMsg) -> None:
        """Serve one bounded catch-up chunk from the WAL window that
        membership-gated compaction retains. A request below the log's
        compaction horizon is clamped: the chunk covers ``(horizon,
        seq_hi]`` with the horizon made explicit, and the pre-horizon
        prefix heals through a classic digest walk opened alongside."""
        self._flush()
        peer = msg.frm
        # the request's applied_seq is the peer's sound claim of how
        # much of OUR history it holds — the same watermark walk acks
        # feed, so membership compaction may advance its reclaim floor
        # on it. (NOT last_seq: that is a resume cursor, which sits
        # past barrier spans the peer never received.) A claim BEYOND
        # our seq is a mixed-history signal (we regressed after
        # recovery with loss, or the peer talked to a previous
        # incarnation): never let it reclaim records the peer cannot
        # have observed (see ROADMAP: an epoch tag would detect this).
        if self._ack_seq.get(peer, 0) < int(msg.applied_seq) <= self._seq:
            self._ack_seq[peer] = int(msg.applied_seq)
        if self._wal is None or not self.log_shipping:
            # nothing servable: everything is "pre-horizon", heal by
            # walk — superseding the round whose opener prompted this
            # request (its slot must not block the fallback walk)
            self.transport.send(
                peer,
                sync_proto.LogChunkMsg(
                    frm=self.addr, to=peer, seq_lo=int(msg.last_seq),
                    seq_hi=int(msg.last_seq), more=False,
                    horizon=self._seq, slices=[],
                ),
            )
            self._outstanding.pop(peer, None)
            self._open_walk(peer)
            return
        t0 = time.perf_counter()
        horizon = self._wal.horizon()
        clamped = int(msg.last_seq) < horizon
        lo = max(int(msg.last_seq), horizon)
        hi = self._wal.last_seq
        n_rec, touched, seq_hi, more, barrier_seq = self._scan_log_rows(lo, hi)
        if barrier_seq is not None:
            # the next record is unservable by log (unknown kind, or a
            # clear touching more than the hard row cap): answer an
            # explicit horizon AT the barrier — the walk covers through
            # it, log shipping resumes after it
            clamped, horizon, more = True, barrier_seq, barrier_seq < hi
        slices = self._extract_catchup_slices(
            np.sort(np.fromiter(touched, np.int64)), self._device_of(peer)
        )
        sent = self.transport.send(
            peer,
            sync_proto.LogChunkMsg(
                frm=self.addr, to=peer, seq_lo=lo, seq_hi=seq_hi,
                more=more, horizon=horizon if clamped else None,
                slices=slices,
            ),
        )
        if sent:
            n_bytes = sum(
                int(v.nbytes)
                for s in slices
                for v in s["arrays"].values()
                if hasattr(v, "nbytes")
            )
            self._catchup_chunks_served += 1
            self._catchup_bytes_shipped += n_bytes
            # per-store padding accounting: shipped entry lanes vs alive
            # entries (payload count == alive dots by construction)
            self._catchup_lanes_shipped += sum(
                int(s["arrays"]["key"].size) for s in slices
            )
            self._catchup_entries_shipped += sum(
                len(s["payloads"]) for s in slices
            )
            if telemetry.has_handlers(telemetry.CATCHUP_CHUNK):
                telemetry.execute(
                    telemetry.CATCHUP_CHUNK,
                    {
                        "records": n_rec,
                        "rows": len(touched),
                        "entries": sum(len(s["payloads"]) for s in slices),
                        "bytes": n_bytes,
                        "duration_s": time.perf_counter() - t0,
                    },
                    {"name": self.name, "role": "server", "peer": peer},
                )
        if clamped:
            # the peer answered our round opener with this request, so
            # that round's in-flight slot is still set — supersede it:
            # the pre-horizon prefix heals by a FRESH walk, now
            self._outstanding.pop(peer, None)
            self._open_walk(peer)

    def _handle_log_chunk(self, msg: sync_proto.LogChunkMsg) -> None:
        """Apply one catch-up chunk: every slice enters as a synthetic
        ``EntriesMsg`` through the normal idempotent merge path — the
        grouped fan-in dispatch coalesces a whole chunk into few kernel
        calls — then the stream either continues (requester-paced
        ``GetLogMsg`` from ``seq_hi``) or completes. Bounded work per
        chunk, one request in flight: catch-up cannot starve sync ticks
        or fsync duties."""
        peer = msg.frm
        st = self._catchup.get(peer)
        # a chunk belongs to the CURRENT stream only when it answers our
        # latest request (its served range starts at-or-above the last
        # requested cursor). Chunks from a superseded, timed-out request
        # still APPLY (idempotent merges — the data is already here) but
        # must not pace follow-ups or complete the stream, or each
        # timeout would fork another full stream re-shipping the suffix.
        current = st is not None and int(msg.seq_lo) >= int(st["last_req"])
        t0 = time.perf_counter()
        ems = [
            sync_proto.EntriesMsg(
                originator=peer,
                frm=peer,
                to=self.addr,
                buckets=np.asarray(s["buckets"], np.int64),
                arrays=s["arrays"],
                payloads=s["payloads"],
            )
            for s in msg.slices
        ]
        # one merge dispatch per slice: slices are already chunk-sized
        # (up to ``catchup_chunk_rows`` rows), so a chunk is a handful
        # of dispatches at most. Concat-grouping them (the ingest path's
        # amortisation for MANY SMALL pushes) would round the combined
        # row count up a pow4 wire tier — up to 4× padded kernel work
        # for zero dispatch savings.
        for em in ems:
            self._handle_entries(em)
        # full-row slices never gap (ctx_lo = 0), so the chunk's range
        # (seq_lo, seq_hi] is now covered — but the watermark may only
        # advance when that range CONNECTS to it (watermark ≥ seq_lo): a
        # horizon-clamped chunk serves above the compaction horizon and
        # claiming the unshipped (watermark, horizon] prefix would
        # silently disable the very walk that heals it. Never regress
        # either (an unsolicited stale chunk must not rewind).
        if (
            self._applied_seq.get(peer, 0) >= int(msg.seq_lo)
            and int(msg.seq_hi) > self._applied_seq.get(peer, 0)
        ):
            self._note_applied_seq(peer, int(msg.seq_hi))
        self._catchup_chunks_applied += 1
        self._catchup_rows_applied += sum(len(s["buckets"]) for s in msg.slices)
        if msg.horizon is not None:
            self._catchup_horizon_fallbacks += 1
            if st is not None:
                st["horizon"] = True
            # the span through msg.horizon is unservable by this peer's
            # log: take the classic walk on future openers until our
            # watermark passes it (a walk equality does exactly that)
            self._catchup_walk_floor[peer] = max(
                self._catchup_walk_floor.get(peer, 0), int(msg.horizon)
            )
        if telemetry.has_handlers(telemetry.CATCHUP_CHUNK):
            telemetry.execute(
                telemetry.CATCHUP_CHUNK,
                {
                    "records": 0,
                    "rows": sum(len(s["buckets"]) for s in msg.slices),
                    "entries": sum(len(s["payloads"]) for s in msg.slices),
                    "bytes": sum(
                        int(v.nbytes)
                        for s in msg.slices
                        for v in s["arrays"].values()
                        if hasattr(v, "nbytes")
                    ),
                    "duration_s": time.perf_counter() - t0,
                },
                {"name": self.name, "role": "client", "peer": peer},
            )
        if msg.more:
            if not current:
                return  # a superseded stream's chunk: applied, not paced
            st["chunks"] += 1
            st["expiry"] = time.monotonic() + self.sync_timeout
            # resume past any barrier horizon: a chunk that stopped AT a
            # record the log cannot serve (seq_hi == seq_lo, horizon at
            # the barrier) continues above it — the walk covers the
            # barrier itself, and the watermark gate above keeps the
            # skipped span out of our coverage claim
            nxt = max(int(msg.seq_hi), int(msg.horizon or 0))
            st["last_req"] = nxt
            if not self.transport.send(
                peer,
                sync_proto.GetLogMsg(
                    frm=self.addr, to=peer, last_seq=nxt,
                    # resume cursor ≠ coverage claim: only the applied
                    # watermark may move the server's compaction floor
                    applied_seq=int(self._applied_seq.get(peer, 0)),
                ),
            ):
                self._catchup.pop(peer, None)  # server died mid-stream
        else:
            if current:
                dur = time.monotonic() - st["t0"]
                self._catchup_last_duration = dur
                self._flight(
                    "catchup_done", peer=str(peer), chunks=st["chunks"] + 1,
                    horizon_fallback=bool(st["horizon"]),
                )
                if telemetry.has_handlers(telemetry.CATCHUP_DONE):
                    telemetry.execute(
                        telemetry.CATCHUP_DONE,
                        {
                            "chunks": st["chunks"] + 1,
                            "duration_s": dur,
                            "horizon_fallback": int(st["horizon"]),
                        },
                        {"name": self.name, "peer": peer},
                    )
                if not st["horizon"]:
                    # an unclamped stream covered everything up to the
                    # server's seq_hi ≥ its round-open seq — exactly
                    # what a walk-equality ack claims, so the same ack
                    # clears the server's in-flight slot and advances
                    # its membership-compaction watermark for us. A
                    # clamped stream left the pre-horizon prefix to the
                    # walk: no ack, the slot expires and the next round
                    # walks the remainder.
                    self.transport.send(
                        peer, sync_proto.AckMsg(clear_addr=self.addr)
                    )
                # only the CURRENT stream's completion retires the
                # bookkeeping — a superseded stream's final chunk must
                # not kill the live stream it was replaced by
                self._catchup.pop(peer, None)

    # -- ingress coalescing (ISSUE 3 tentpole) ---------------------------

    @staticmethod
    def _coalescible(msg) -> "tuple | None":
        """``(bucket-row set, entry-lane tier)`` when the message may
        join a grouped fan-in merge; ``None`` forces the per-slice path.
        Device-plane slices are excluded: combining happens on host, and
        pulling tensor columns off the device to batch them would trade
        the data plane for the dispatch win."""
        a = msg.arrays
        if not isinstance(a["key"], np.ndarray):
            return None
        rows = a["rows"]
        return frozenset(rows[rows >= 0].tolist()), a["key"].shape[1]

    def _coalesce_groups(self, run: list) -> list:
        """Partition a consecutive run of ``EntriesMsg``s (arrival
        order) into groups that are safe to join in ONE kernel call:
        host-plane slices with EQUAL entry-lane tiers and pairwise
        DISJOINT bucket rows, at most ``max_coalesce`` deep.

        - Equal lane tiers keep the grouped row-compact sort width
          identical to per-message merges (bit-for-bit parity, even in
          dead slots).
        - Disjoint rows make the grouped join decompose per row —
          ``merge_rows`` is row-local — so merging the group equals
          merging its members sequentially.
        - Greedy in arrival order: a conflicting message CLOSES the
          current group, so groups merge in arrival order and
          per-sender slice order is preserved (each sender's
          delta-interval contiguity is checked in sequence; the
          ``CtxGapError`` repair still fires per source).
        """
        groups: list = []
        cur: list = []
        cur_rows: set = set()
        cur_s = -1
        for m in run:
            info = self._coalescible(m)
            if info is None:
                if cur:
                    groups.append(cur)
                cur, cur_rows, cur_s = [], set(), -1
                groups.append([m])
                continue
            rows, s = info
            if (
                cur
                and s == cur_s
                and len(cur) < self.max_coalesce
                and not (rows & cur_rows)
            ):
                cur.append(m)
                cur_rows |= rows
            else:
                if cur:
                    groups.append(cur)
                cur, cur_rows, cur_s = [m], set(rows), s
        if cur:
            groups.append(cur)
        return groups

    def _count_dispatch(self, depth: int, messages: int) -> None:
        self._ingress_dispatches += 1
        self._ingress_messages += messages
        self._coalesce_depths[depth] = self._coalesce_depths.get(depth, 0) + 1

    def _handle_entries_group(self, msgs: list, partition: bool = True) -> None:
        """Drain-and-coalesce ingress: join a group of compatible
        ``EntriesMsg``s with ONE grouped fan-in kernel dispatch
        (``merge_group_into``) instead of one ``merge_rows_into``
        dispatch per message, then emit WAL records, payload updates and
        telemetry per ORIGINAL message — observable protocol behaviour
        is unchanged from sequential handling (bit-for-bit, see
        ``tests/test_ingest_coalesce.py``).

        Per-slice fallbacks: singleton groups (nothing to amortise) and
        a diff subscriber (the before/after winner compare is defined
        per slice). A mid-group ``CtxGapError`` PARTITIONS instead of
        falling back whole: the kernel's per-row gap mask names the
        offending member slices, so only the gapped senders replay solo
        (each answering with its ``GetDiffMsg`` repair) while the clean
        members retry as one grouped dispatch — merges of disjoint rows
        commute and the gapped slices merge nothing either way, so the
        result is bit-identical to sequential handling."""
        if len(msgs) == 1 or self.on_diffs is not None:
            for m in msgs:
                self._count_dispatch(1, 1)
                self._handle_entries(m)
            return
        self._flush()
        t0 = time.perf_counter()
        # payloads first, whole group: the merged winners' values must
        # resolve; idempotent, so the gap fallback below re-registers
        # harmlessly
        with tracing.annotate("crdt.payloads"):
            for m in msgs:
                self._register_slice_payloads(m.payloads)
        try:
            with tracing.annotate("crdt.merge_group"):
                self.state, res, offsets = self.model.merge_group_into(
                    self.state,
                    [m.arrays for m in msgs],
                    on_grow=self._grown_telemetry,
                )
        except CtxGapError as err:
            gapped = err.gapped_members
            if partition and gapped and 0 < len(gapped) < len(msgs):
                # coalesce across the gap repair (ROADMAP follow-up):
                # clean senders stay one grouped dispatch; only the
                # gapped senders' slices replay solo, where the normal
                # per-slice catcher answers each with GetDiffMsg.
                # partition=False on the retry: the clean subgroup
                # re-evaluates gaps against the same state, so a second
                # gap means the mask lied — full per-slice is the only
                # safe answer then.
                self._ingress_gap_partitions += 1
                self._flight(
                    "gap_partition", depth=len(msgs), gapped=len(gapped)
                )
                clean = [m for i, m in enumerate(msgs) if i not in gapped]
                self._handle_entries_group(clean, partition=False)
                for i in sorted(gapped):
                    self._count_dispatch(1, 1)
                    self._handle_entries(msgs[i])
                return
            # gap location unknown (or everything gapped): replay the
            # group per slice (merges are idempotent), which isolates
            # the gapped sources and answers each with the GetDiffMsg
            # repair exactly as sequential handling would
            self._ingress_gap_fallbacks += 1
            self._flight("gap_fallback", depth=len(msgs))
            for m in msgs:
                self._count_dispatch(1, 1)
                self._handle_entries(m)
            return
        depth = len(msgs)
        self._count_dispatch(depth, depth)
        dt = time.perf_counter() - t0
        # cache invalidation once (sequential invalidates per message —
        # same end state); SYNC_DONE stays per message via the kernel's
        # per-row counts summed over each message's row range
        self._tree = None
        self._read_cache = None
        self._read_cache_kh = None
        self._commit_entries_group(
            msgs,
            offsets,
            # raw device arrays: the consumer transfers them (batched
            # with every other parked readback when inside a drain).
            # Default-arg capture of JUST the two count arrays: closing
            # over ``res`` would park the whole MergeRowsResult —
            # including ``res.state`` — in the deferral window, pinning
            # every superseded store generation and defeating XLA's
            # input-buffer reuse on each subsequent merge (a full-store
            # copy per dispatch, ~40% of ingest wall time at depth 64)
            lambda ins=res.n_ins_row, kill=res.n_kill_row: (ins, kill),
            dt,
        )
        if telemetry.has_handlers(telemetry.INGEST_COALESCE):
            telemetry.execute(
                telemetry.INGEST_COALESCE,
                {
                    "depth": depth,
                    # crdtlint: allow[TRANSFER001] offsets is the host list of (lo, hi) member row ranges from combine_entry_arrays, not a device array
                    "rows": int(offsets[-1][1]),
                    "entries": sum(len(m.payloads) for m in msgs),
                    "duration_s": dt,
                },
                {"name": self.name},
            )
        self._gc_pressure += sum(len(m.payloads) for m in msgs) + int(_TR_INGEST_COUNTS.get(res.n_killed))
        self._maybe_gc()

    def _commit_entries_group(self, msgs: list, offsets, counts_fn, dt: float) -> None:
        """Per-message bookkeeping for one grouped entries dispatch —
        THE shared tail of the in-replica grouped path and the fleet's
        cross-replica batched path, so sequence numbering, SYNC_DONE /
        SYNC_ROUND streams, and WAL record bytes cannot drift between
        them (the fleet-vs-solo bit-for-bit parity contract).
        ``counts_fn`` lazily yields the kernel's per-row (insert, kill)
        count arrays, device or host — a readback only SYNC_DONE
        handlers pay for (batched with the drain pass's other parked
        readbacks when one is active). Caller holds the lock, has
        stored the merged state, and has invalidated the tree/read
        caches."""
        with tracing.annotate("crdt.commit"):
            self._commit_entries_group_inner(msgs, offsets, counts_fn, dt)

    def _commit_entries_group_inner(self, msgs: list, offsets, counts_fn, dt: float) -> None:
        # durability happens-before publication (crdtlint FAULT003): the
        # whole group's WAL records land before the serving plane or
        # telemetry can observe the merge. A failed append rolls the seq
        # back to the last record that DID land, so a recovering replica
        # replays a contiguous prefix of the group.
        for m in msgs:
            self._seq += 1
            a, payloads = m.arrays, m.payloads
            try:
                faultpoint("replica.commit.entries")
                self._durable(
                    lambda a=a, payloads=payloads: {
                        "kind": "entries",
                        "seq": self._seq,
                        "arrays": self._wal_arrays_host(a),
                        "payloads": dict(payloads),
                    }
                )
            except BaseException as e:
                self._commit_abort(e)
                raise
        # commit boundary for the grouped paths (solo grouped + fleet
        # batched): state stored, payloads registered — publish for the
        # serving plane's lock-free readers
        self._publish_serve()
        # relay bookkeeping (ISSUE 15) shares this tail too, so the
        # grouped solo path and the fleet batched path park their relay
        # stamps identically (the singleton path parks in
        # _handle_entries_inner); counts_fn is the same raw-device
        # accessor the SYNC_DONE deferral consumes — calling it twice
        # just hands back the same arrays
        self._relay_note_merge(msgs, counts_fn, offsets)
        depth = len(msgs)
        want_done = telemetry.has_handlers(telemetry.SYNC_DONE)
        want_round = telemetry.has_handlers(telemetry.SYNC_ROUND)
        if want_done:
            name = self.name

            def emit_done(counts, offsets=offsets, depth=depth):
                ins_row, kill_row = counts
                # one vectorised prefix sum, then O(1) per message —
                # per-message ``[lo:hi].sum()`` slices cost more than
                # the bridge's whole handler chain at coalesce depth 16
                tot = np.cumsum(
                    np.asarray(ins_row, np.int64) + np.asarray(kill_row, np.int64)
                )
                meas: list = []
                for lo, hi in offsets[:depth]:
                    if hi > lo:
                        n = int(tot[hi - 1]) - (int(tot[lo - 1]) if lo else 0)
                    else:
                        n = 0  # empty member slice
                    meas.append({"keys_updated_count": n})
                # one batch emission: plain handlers still see the exact
                # per-message stream; the bridge folds it in one call
                telemetry.execute_many(
                    telemetry.SYNC_DONE, meas, {"name": name}
                )

            if self._telemetry_defer is not None:
                # mid-drain: the per-row accounting readback waits until
                # every group in this drain pass has dispatched (the
                # per-message SYNC_DONE stream is emitted then, in order,
                # off ONE batched transfer)
                self._telemetry_defer.append((counts_fn, emit_done))
            else:
                emit_done(counts_fn())
        if want_round:
            # one batch emission for the whole group (shared meta, the
            # per-slice duration split evenly): plain handlers still see
            # the per-message stream; the bridge folds it in one call
            per_msg_dt = dt / depth
            telemetry.execute_many(
                telemetry.SYNC_ROUND,
                [
                    {
                        "duration_s": per_msg_dt,
                        "buckets": int(len(m.buckets)),
                        "entries": len(m.payloads),
                    }
                    for m in msgs
                ],
                {"name": self.name, "plane": "host"},
            )

    # -- batched replica fleets (ISSUE 6 tentpole) -----------------------
    #
    # A fleet (runtime/fleet.py) drains many replicas' mailboxes per
    # tick and joins their coalesce groups with ONE vmapped kernel
    # dispatch over a leading replica axis (runtime/transition.py).
    # These hooks are the replica's side of that contract — the
    # cross-class API the fleet drives, public-named so the lock
    # analysis treats them as externally-entered units: staging is
    # optimistic (no lock held across the batched dispatch), and the
    # commit replays through the same bookkeeping tail as the solo
    # grouped path — observable behaviour (state bits, WAL bytes, seq,
    # acks) is identical to handling the messages without a fleet.

    def fleet_prepare(self, msgs: list) -> "tuple | None":
        """Stage one coalesce group for a fleet batched dispatch: flush
        pending local ops, register the group's payloads (idempotent —
        the solo fallback re-registers harmlessly), and combine the
        group into one host-form slice. Returns ``(slice, offsets,
        state_version, geometry)`` or ``None`` to demand the
        per-replica fallback — a diff subscriber (the before/after
        winner compare is defined per slice) or device-plane slices
        (combining happens on host), exactly the solo grouped path's
        exclusions."""
        if self.on_diffs is not None:
            return None
        for m in msgs:
            if not isinstance(m.arrays["key"], np.ndarray):
                return None
        with self._lock:
            self._flush()
            for m in msgs:
                self._register_slice_payloads(m.payloads)
            sl, offsets = self.model.combine_entry_arrays(
                [m.arrays for m in msgs], to_device=False
            )
            return sl, offsets, self._state_version, self._geometry()

    def fleet_handle_group(self, msgs: list) -> None:
        """Per-replica fallback for one fleet group: the solo grouped
        dispatch under this replica's own lock — growth tiers, the
        ``CtxGapError`` partition/repair, and singleton handling all
        behave exactly as without a fleet."""
        with self._lock:
            self._fleet_fallbacks += 1
            self._flight("fleet_fallback", depth=len(msgs))
            self._handle_entries_group(msgs)

    def fleet_commit(
        self,
        msgs: list,
        offsets,
        stacked,
        lane: int,
        counts_fn,
        n_killed: int,
        dt: float,
        version: int,
    ) -> "int | None":
        """Adopt lane ``lane`` of a fleet batched dispatch's stacked
        result and fan out the per-message bookkeeping (seq, telemetry,
        WAL records, gc pressure). Returns the NEW state version (the
        one at which ``stacked[lane]`` is this replica's state — the
        fleet's residency cache must record exactly this version, not a
        later re-read that could mask a concurrent mutation), or
        ``None`` — leaving this replica untouched, the fleet replays
        the group solo — when the state moved since
        :meth:`fleet_prepare` staged it (the batched merge then read a
        stale state)."""
        with self._lock:
            if self._state_version != version:
                return None
            self._state = None
            self._fleet_src = (stacked, lane)
            self._state_version += 1
            committed_version = self._state_version
            self._tree = None
            self._read_cache = None
            self._read_cache_kh = None
            # the adopted lane's ctx_max can include own-gid counters the
            # cache predates (a peer relaying our dots back after a
            # WAL-less restart reused our node id), and unlike the solo
            # merge path the batched dispatch swaps the WHOLE state cell
            # — drop the cursor-source cache so the next egress tick
            # plans from the adopted lane, never a stale own column
            self._own_ctr_cache = None
            self._fleet_dispatches += 1
            self._fleet_messages += len(msgs)
            self._commit_entries_group(msgs, offsets, counts_fn, dt)
            self._gc_pressure += sum(len(m.payloads) for m in msgs) + n_killed
            self._maybe_gc()
            return committed_version

    # -- serving plane (ISSUE 14) ----------------------------------------

    def _publish_serve(self) -> None:
        """Publish the current commit for the serving plane's lock-free
        snapshot readers (caller holds the lock, at a commit boundary:
        every alive dot of the current state has its payload in
        ``_payloads``). One tuple build + one atomic attribute store —
        the entire hot-path cost of read publication."""
        self._serve_pub = (
            self._state_version, self._state, self._fleet_src, self._payloads,
        )

    def publish_read_snapshot(self) -> tuple:
        """Force a publication of the current state (the serving
        plane's priming/refresh hook — e.g. before the first read, or
        after a stale-read race) and return the published triple."""
        with self._lock:
            self._publish_serve()
            return self._serve_pub

    def frontdoor(self, **opts):
        """This replica's serving front door (ISSUE 14), created on
        first use and cached: lock-free snapshot reads, coalesced write
        admission, backpressure/shedding — see
        :class:`delta_crdt_ex_tpu.runtime.serve.Frontdoor`. Closed
        automatically on :meth:`stop`/:meth:`crash`."""
        from delta_crdt_ex_tpu.runtime.serve import Frontdoor

        with self._lock:
            if self._frontdoor is None:
                self._frontdoor = Frontdoor(self, **opts)
            elif opts:
                raise ValueError(
                    f"front door for {self.name!r} already exists; options "
                    "are fixed at first creation"
                )
            return self._frontdoor

    def _close_frontdoor(self) -> None:
        """Detach and close the cached front door (stop/crash teardown).
        The close itself — which joins the admission worker — runs
        OUTSIDE the replica lock (LOCK003: never join a thread that may
        be blocked on the lock we hold)."""
        with self._lock:
            fd, self._frontdoor = self._frontdoor, None
        if fd is not None:
            fd.close()

    def _merge_with_growth(self, sl):
        # row-granular merge: runtime slices are ≤ max_sync_size rows,
        # where whole-row math costs the same as element scatters but
        # needs no kill-budget or insert tiers (fewer recompiles; the
        # only escapes left are genuine bin/gid growth)
        self.state, res = self.model.merge_rows_into(
            self.state, sl, on_grow=self._grown_telemetry
        )
        return res

    # ------------------------------------------------------------------
    # bench parity helpers (reference BenchmarkHelper, benchmark_helper.ex:
    # 2-14 — :hibernate forces GC-like state compaction before timing, :ping
    # round-trips the mailbox)

    def hibernate(self) -> str:
        """Quiesce before timing: flush, prune host dicts, drain device."""
        with self._lock:
            self._flush()
            self.gc()
            state = self.state
        # device drain OUTSIDE the lock (crdtlint LOCK003): waiting out
        # a whole in-flight merge pipeline must not freeze concurrent
        # mutators/readers on the replica lock — the state reference
        # captured under the lock is the quiesce point either way
        jax.block_until_ready(state)
        return "ok"

    def ping(self) -> str:
        with self._lock:
            # mailbox round-trip parity: a GenServer ``:ping`` call is
            # served after every queued cast, so pending async mutations
            # must be applied before the pong
            self._flush()
            return "ok"

    # ------------------------------------------------------------------
    # payload GC (host dictionaries must track device alive masks)

    def gc(self) -> None:
        """Prune host payload/key dictionaries to currently-alive dots.

        Fully vectorized (one nonzero + three gathers + batched tolist);
        runs automatically from the mutation/merge paths once garbage
        pressure (payload inserts + merge kills) reaches
        max(``gc_interval_ops``, half the post-gc dict size) — see
        ``_maybe_gc`` — so a long-running replica with remove churn keeps
        ``_payloads``/``_key_terms`` proportional to live entries
        (VERDICT r2 weak #3) at amortized O(1) per op."""
        with tracing.annotate("crdt.gc"), self._lock:
            # store-layout-agnostic (ISSUE 8): a dot's bucket is a pure
            # function of its key, so derive it instead of reading the
            # binned row index — the same pass serves the [L, B] rows
            # and the flat hash table
            st = self.state
            # one audited batched fetch of the five scan columns (the
            # host indexing below is unchanged — bit-identical result)
            alive, node_h, gid_h, ctr_h, key_h = _TR_GC_SCAN.get(
                (st.alive, st.node, st.ctx_gid, st.ctr, st.key)
            )
            idx = np.nonzero(alive)
            node_sel = node_h[idx]
            gid_l = gid_h[node_sel].tolist()
            ctr_l = ctr_h[idx].tolist()
            keys = key_h[idx]
            bucket = (keys & np.uint64(self.num_buckets - 1)).astype(np.int64)
            live = set(zip(gid_l, bucket.tolist(), ctr_l))
            self._payloads = {d: p for d, p in self._payloads.items() if d in live}
            keep_keys = set(keys.tolist())
            self._key_terms = {h: t for h, t in self._key_terms.items() if h in keep_keys}
            self._gc_pressure = 0
            self._gc_floor = len(self._payloads)
            # republish with the pruned dict (same version, same state:
            # every published winner is a live dot, so all survive the
            # prune) — without this, the serving plane's pinned triple
            # keeps the pre-gc dict alive until the next commit
            self._publish_serve()

    def _maybe_gc(self) -> None:
        """Called (under the lock) after payload-inserting paths.

        The trigger scales with the POST-GC dict size (``_gc_floor``):
        gc costs O(live + capacity readback), so running it every
        ``gc_interval_ops`` inserts regardless of size made a 1M-key
        bulk load pay ~244 full-state scans (measured 7× throughput
        loss). Requiring pressure ≥ half the last post-gc size amortises
        gc to O(1) per op while bounding the dict at ~1.5× live + the
        interval. The floor must be the post-gc size, not the current
        ``len(_payloads)``: after a mass-remove wave the dict is mostly
        dead entries, and a threshold keyed on the bloated size would
        defer the very gc that shrinks it."""
        if self._gc_pressure >= max(self.gc_interval_ops, self._gc_floor >> 1):
            self.gc()

    # ------------------------------------------------------------------
    # threaded event loop (the reference's GenServer process analog)

    def notify(self) -> None:
        # unthreaded replicas have no event loop to wake; skipping the
        # Event.set saves ~4 µs on every mutate_async of a bulk load
        if self._thread is not None:
            self._wake.set()

    def process_pending(self) -> int:
        """Deterministic drive: handle all queued messages now.

        The mailbox drains in bounded batches (``drain_nowait(addr,
        max_n)``); with ``ingress_coalesce`` on, consecutive runs of
        ``EntriesMsg``s inside a batch are partitioned into compatible
        groups and each group merges with ONE grouped fan-in kernel
        dispatch (``_handle_entries_group``) instead of one dispatch per
        message — the replica hot-path half of the bench's grouped-merge
        win.

        Bounded per call: under SUSTAINED ingress (every drain coming
        back full) at most ``8 × ingress_batch`` messages are handled
        before returning, so the threaded event loop's periodic duties
        (sync ticks, checkpoints, interval-mode WAL fsync) cannot be
        starved by fan-in load — the senders' ``notify()`` has already
        set the wake event, so the loop re-enters without sleeping and
        drains the remainder next iteration."""
        drain = getattr(self.transport, "drain_nowait", None)
        obs = self._obs
        t0 = time.perf_counter() if obs is not None else 0.0
        n = 0
        with self._lock:
            # open a SYNC_DONE deferral window for this drain pass (see
            # _telemetry_defer): nested/concurrent passes reuse the
            # outermost window, which owns the flush
            top = self._telemetry_defer is None
            if top:
                self._telemetry_defer = []
        # the crdt.drain span opens at the first non-empty batch, so an
        # idle loop iteration emits none
        with contextlib.ExitStack() as span:
            try:
                for _ in range(8):
                    if drain is not None:
                        batch = drain(self.addr, self.ingress_batch)
                    else:  # transports predating the batch-receive API
                        batch = self.transport.drain(self.addr)
                    if not batch:
                        break
                    if not n:
                        span.enter_context(tracing.annotate("crdt.drain"))
                    n += len(batch)
                    self._handle_batch(batch)
                    if drain is None or len(batch) < self.ingress_batch:
                        break
                # end-of-drain relay epoch: everything this pass merged
                # re-emits as ONE coalesced slice per tree link, so
                # propagation cascades hop-by-hop through relays instead
                # of waiting a sync interval per tree level
                self._relay_flush()
            finally:
                if top:
                    with self._lock:
                        deferred, self._telemetry_defer = self._telemetry_defer, None
                    if deferred:
                        # ONE transfer for every parked accounting pytree
                        # (device_get passes already-host values through)
                        fetched = _TR_DRAIN_ACCOUNTING.get([f() for f, _e in deferred])
                        for (_f, emit), data in zip(deferred, fetched):
                            emit(data)
        if obs is not None and n:
            # drain-lag accounting: one registry update per drain PASS
            # (never per message — the hot path stays amortised)
            obs.record_drain(self.name, n, time.perf_counter() - t0)
        return n

    def _handle_batch(self, msgs: list) -> None:
        """Handle one drained batch in arrival order, coalescing
        consecutive runs of ``EntriesMsg``s. Any other message type
        (walk traffic, acks, ``Down``) closes the current run and is
        handled in place — nothing is ever reordered across types, so a
        ``Down`` never passes entries from the same peer. A diff
        subscriber forces the per-slice path anyway (the before/after
        winner compare is defined per slice), so skip the grouping pass
        instead of computing row sets just to discard them."""
        if not self.ingress_coalesce or self.on_diffs is not None:
            for m in msgs:
                self.handle(m)
            return
        run: list = []
        for m in msgs:
            if isinstance(m, sync_proto.EntriesMsg):
                run.append(m)
                continue
            self._drain_entries_run(run)
            self.handle(m)
        self._drain_entries_run(run)

    def _drain_entries_run(self, run: list) -> None:
        """Merge one run of queued entries, group by group. The lock is
        taken per GROUP (not per batch): a grouped dispatch is the unit
        that amortises lock+dispatch overhead, while mutate()/read()
        callers still interleave between groups exactly as they could
        between sequential messages."""
        if not run:
            return
        for group in self._coalesce_groups(run):
            with self._lock:
                self._handle_entries_group(group)
        run.clear()

    def stats(self) -> dict:
        """Observability snapshot (a GenServer-call analog, served under
        the replica lock like ``ping``). ``ingress`` surfaces the
        coalescing win in production: the coalesce-depth histogram
        (group size → dispatches) and the merges-per-dispatch ratio over
        the batch-drain path; ``wal`` includes the membership ack floor
        gating segment reclaim."""
        with self._lock:
            dispatches = self._ingress_dispatches
            messages = self._ingress_messages
            out = {
                "name": self.name,
                "node_id": self.node_id,
                "sequence_number": self._seq,
                "neighbours": list(self._neighbours),
                "outstanding_syncs": len(self._outstanding),
                "payloads": len(self._payloads),
                "ingress": {
                    "messages": messages,
                    "dispatches": dispatches,
                    "merges_per_dispatch": (
                        round(messages / dispatches, 3) if dispatches else 0.0
                    ),
                    "coalesce_depth_hist": dict(
                        sorted(self._coalesce_depths.items())
                    ),
                    "gap_fallbacks": self._ingress_gap_fallbacks,
                    "gap_partitions": self._ingress_gap_partitions,
                },
                "fleet": {
                    "dispatches": self._fleet_dispatches,
                    "batched_messages": self._fleet_messages,
                    "fallbacks": self._fleet_fallbacks,
                },
                "catchup": {
                    "store": self.model.backend,
                    "chunks_served": self._catchup_chunks_served,
                    "chunks_applied": self._catchup_chunks_applied,
                    "rows_applied": self._catchup_rows_applied,
                    "bytes_shipped": self._catchup_bytes_shipped,
                    "lanes_shipped": self._catchup_lanes_shipped,
                    "entries_shipped": self._catchup_entries_shipped,
                    # alive entries per shipped lane: 1.0 = dense (the
                    # hash store's extraction), low = bin-tier padding
                    "chunk_fill_ratio": (
                        round(
                            self._catchup_entries_shipped
                            / self._catchup_lanes_shipped,
                            4,
                        )
                        if self._catchup_lanes_shipped
                        else 0.0
                    ),
                    "horizon_fallbacks": self._catchup_horizon_fallbacks,
                    "in_flight": len(self._catchup),
                    "last_duration_s": round(self._catchup_last_duration, 6),
                },
                # device↔host boundary ledger (ISSUE 17): PROCESS-WIDE
                # absolute per-site crossing/byte totals, not this
                # replica's share — the ledger registry is global, like
                # the jitcache audit it mirrors
                "transfers": transfers.snapshot(),
                "wal": None,
            }
            if self.tree_gossip:
                topo = self._tree_refresh()
                reemits = self._relay_reemits
                out["tree"] = {
                    "degraded": topo is None,
                    "epoch": None if topo is None else topo.epoch,
                    "role": (
                        "flat" if topo is None else topo.role(self.addr)
                    ),
                    "tier": (
                        0 if topo is None
                        else int(topo.tier.get(self.addr, 0))
                    ),
                    "depth": 0 if topo is None else topo.depth,
                    "fanout": self.tree_fanout,
                    "members": (
                        0 if topo is None else len(topo.members)
                    ),
                    "down": len(self._tree_down),
                    "links": (
                        [] if topo is None
                        else [str(a) for a in topo.links(self.addr)]
                    ),
                    "reemits": reemits,
                    "msgs_folded": self._relay_msgs_folded,
                    "folds_per_reemit": (
                        round(self._relay_msgs_folded / reemits, 3)
                        if reemits
                        else 0.0
                    ),
                    "entries_reemitted": self._relay_entries_emitted,
                    "rows_reemitted": self._relay_rows_emitted,
                    "tx_bytes": self._relay_tx_bytes,
                    "rx_bytes": self._relay_rx_bytes,
                    "depth_hist": dict(sorted(self._relay_depth_hist.items())),
                    "pending_links": len(self._relay_pending),
                    "pending_rows": sum(
                        len(p) for p in self._relay_pending.values()
                    ),
                }
            if self._wal is not None:
                out["wal"] = {
                    "uncompacted_records": self._wal_unc,
                    "ack_floor": self._reclaim_floor(),
                    "segments": len(self._wal.segment_paths()),
                    # below this seq log-shipping cannot serve: requests
                    # under it fall back to the digest walk for the prefix
                    "horizon": self._wal.horizon(),
                }
            return out

    # -- observability plane sources (ISSUE 9) ---------------------------

    def wal_size_bytes(self) -> int:
        """On-disk WAL footprint (segments + staged append buffer);
        0 without a WAL. Scrape-time observability — never on a hot path."""
        with self._lock:
            if self._wal is None:
                return 0
            return self._wal.size_bytes()

    def obs_varz(self) -> dict:
        """This replica's ``/varz`` stanza: the UNCHANGED :meth:`stats`
        dict under a typed envelope (the additive-surface contract,
        MIGRATING.md)."""
        out = {"kind": "replica", "stats": self.stats()}
        if self.flight is not None:
            out["flight_events"] = self.flight.events_recorded()
        return out

    def health(self) -> dict:
        """Liveness/readiness for ``/healthz``: the event loop is
        responsive (fresh heartbeat when threaded; fleet members are
        covered by the fleet's tick check), the WAL directory is
        writable, and every configured neighbour is reachable per the
        existing monitor/heartbeat state (an unmonitorable neighbour is
        exactly what the transport's Down/ping machinery reported dead)."""
        with self._lock:
            loop_ok = True
            if self._thread is not None:
                loop_ok = self._thread.is_alive() and (
                    time.monotonic() - self._loop_ts
                    < max(5 * self.sync_interval, 2.0)
                )
            wal_ok = self._wal is None or os.access(self._wal.directory, os.W_OK)
            # tree mode: readiness is about OUR sync edges (the tree
            # links), not the whole membership — a leaf monitoring only
            # its parent is healthy by design
            topo = self._tree_refresh()
            targets = self._neighbours if topo is None else topo.links(self.addr)
            neighbours = [n for n in targets if n != self.addr]
            unreachable = [n for n in neighbours if n not in self._monitors]
        return {
            "ok": loop_ok and wal_ok and not unreachable,
            "loop_responsive": loop_ok,
            "wal_writable": wal_ok,
            "neighbours": len(neighbours),
            "neighbours_unreachable": [str(n) for n in unreachable],
        }

    def start(self) -> "Replica":
        """Run the periodic anti-entropy loop in a background thread
        (reference: ``send_after(self(), :sync, interval)``,
        ``causal_crdt.ex:180-186``; first sync fires immediately, ``:46``)."""
        if self._in_fleet:
            raise ValueError(
                f"replica {self.name!r} is a fleet member; the fleet owns "
                "its event loop (two drains of one mailbox would race)"
            )
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            next_sync = time.monotonic()  # immediate first sync
            next_ckpt = time.monotonic() + self.checkpoint_interval
            while not self._stop.is_set():
                faultpoint("replica.loop")
                self.process_pending()
                with self._lock:
                    # health heartbeat: a wedged loop (stuck merge, dead
                    # thread) goes stale and /healthz flips unready
                    self._loop_ts = time.monotonic()
                    if self._pending:
                        self._flush()
                now = time.monotonic()
                if now >= next_sync:
                    self.sync_to_all()
                    next_sync = now + self.sync_interval
                if (
                    self.storage_mode == "interval"
                    and self.storage_module is not None
                    and now >= next_ckpt
                ):
                    # async-cadence snapshot — the TPU-sane alternative to
                    # the reference's write-through-per-op (SURVEY §5.4)
                    self.checkpoint()
                    next_ckpt = now + self.checkpoint_interval
                with self._lock:
                    # interval-fsync deferred syncs reach disk even when
                    # the replica goes idle right after a commit (the
                    # None check sits under the lock too: WalLog is not
                    # thread-safe by itself, and crash/stop close it
                    # concurrently — crdtlint LOCK001)
                    if self._wal is not None:
                        # crdtlint: allow[LOCK003] deferred interval-mode
                        # fsync: bounded by fsync_interval cadence, and the
                        # fd is replica-lock-serialised state
                        self._wal.maybe_sync()
                self._wake.wait(timeout=max(0.0, min(next_sync - time.monotonic(), 0.05)))
                self._wake.clear()

        self._thread = threading.Thread(target=loop, name=f"crdt-{self.name}", daemon=True)
        self._thread.start()
        return self

    def crash(self) -> None:
        """Fault injection: die WITHOUT the terminate-path goodbye sync.

        The node-loss simulation (the reference's tests kill the owning
        process, ``causal_crdt_test.exs:87-102``): the event loop stops
        mid-flight, nothing is flushed or synced beyond what
        ``storage_mode`` already persisted, and deregistration fires
        ``Down`` at monitoring peers. A later ``start_link`` with the
        same name + storage rehydrates with node-id continuity."""
        self._close_frontdoor()
        if self._thread is not None:
            self._stop.set()
            self._wake.set()
            self._thread.join(timeout=5)
            self._thread = None
        if self.flight is not None:
            # the black box: a crashing replica's recent structured
            # events go out through the logger for the post-mortem —
            # and, with ``flight_dump_path``, to a JSONL file that
            # outlives the process (the chaos runs' black-box knob)
            self.flight.dump(path=self.flight_dump_path)
        if self._obs is not None:
            self._obs.unregister_replica(self)
        with self._lock:
            # under the replica lock: WalLog is not thread-safe by
            # itself, and a concurrent mutate() mid-append must not race
            # the close (crdtlint LOCK001)
            if self._wal is not None:
                # a crash drops whatever the fsync cadence had not yet
                # committed — the exact durability contract under test
                # crdtlint: allow[LOCK003] terminal close; flush=False never
                # actually fsyncs, and the replica is shutting down
                self._wal.close(flush=False)
        self.transport.unregister(self.name)

    def stop(self) -> None:
        """Terminate: best-effort final sync (reference ``terminate/2``,
        ``causal_crdt.ex:200-204``), then deregister (fires Down at
        monitoring peers)."""
        self._close_frontdoor()
        if self._thread is not None:
            self._stop.set()
            self._wake.set()
            self._thread.join(timeout=5)
            self._thread = None
        if self._obs is not None:
            # a stopped replica must not scrape as a stale last value
            self._obs.unregister_replica(self)
        try:
            self.sync_to_all()
        except Exception:  # best-effort, like the reference's TODO-marked path
            logger.debug("final sync on terminate failed", exc_info=True)
        if self.storage_mode == "interval" and self.storage_module is not None:
            self.checkpoint()
        with self._lock:
            # same closing discipline as crash(): the WAL append path
            # runs under this lock, so its close must too
            if self._wal is not None:
                # crdtlint: allow[LOCK003] terminal flush at stop(): the
                # final records must reach disk before deregistration
                self._wal.close(flush=True)
        self.transport.unregister(self.name)
