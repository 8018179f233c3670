"""Public API facade — parity with ``DeltaCrdt`` (``lib/delta_crdt.ex``).

The reference surface maps 1:1 (``delta_crdt.ex:56,97,117,126,135``):

==========================  ==========================================
reference                   here
==========================  ==========================================
``start_link(module, o)``   :func:`start_link` (spawns the sync thread)
``set_neighbours/2``        :func:`set_neighbours` (one-way edges)
``mutate/4``                :func:`mutate`
``mutate_async/3``          :func:`mutate_async`
``read/2``                  :func:`read`
``child_spec/1``            :func:`child_spec` (supervision metadata)
==========================  ==========================================

Defaults match the reference: ``sync_interval`` 200 ms, ``max_sync_size``
200 (``delta_crdt.ex:31-32``).

TPU-native extensions live on :class:`~delta_crdt_ex_tpu.runtime.replica.
Replica` itself (deterministic ``sync_to_all``/``process_pending`` for
test quiescence, ``read_keys``, ``checkpoint``, ``gc``) and in
:mod:`delta_crdt_ex_tpu.parallel` (batched neighbour sync, mesh gossip).

Example (the reference doctest flow, ``delta_crdt.ex:17-28``)::

    crdt1 = start_link(AWLWWMap, sync_interval=0.003)
    crdt2 = start_link(AWLWWMap, sync_interval=0.003)
    set_neighbours(crdt1, [crdt2])
    set_neighbours(crdt2, [crdt1])
    mutate(crdt1, "add", ["CRDT", "is magic!"])
    ...
    read(crdt2)  # {'CRDT': 'is magic!'}
"""

from __future__ import annotations

from typing import Any

from delta_crdt_ex_tpu.models.binned_map import BinnedAWLWWMap as AWLWWMap
from delta_crdt_ex_tpu.runtime.fleet import Fleet
from delta_crdt_ex_tpu.runtime.replica import Replica


def _resolve_store(crdt_module, store: "str | None"):
    """Map a model class onto the requested dot-store backend
    (ISSUE 8): ``store="hash"`` selects the open-addressing hash-table
    store, ``store="binned"`` the bucket-binned rows (the default).
    Explicit hash model classes pass through unchanged."""
    if store is None:
        return crdt_module
    if store not in ("hash", "binned"):
        raise ValueError(f"unknown store backend {store!r}; use 'hash' or 'binned'")
    backend = getattr(crdt_module, "backend", None)
    if backend == store:
        return crdt_module
    from delta_crdt_ex_tpu.models.binned_map import AWSet, BinnedAWLWWMap
    from delta_crdt_ex_tpu.models.hash_store import HashAWSet, HashAWLWWMap

    mapping = {
        ("hash", BinnedAWLWWMap): HashAWLWWMap,
        ("hash", AWSet): HashAWSet,
        ("binned", HashAWLWWMap): BinnedAWLWWMap,
        ("binned", HashAWSet): AWSet,
    }
    try:
        return mapping[(store, crdt_module)]
    except KeyError:
        raise ValueError(
            f"{crdt_module!r} has no {store!r}-store counterpart; pass a "
            "model class whose backend matches, or omit store="
        ) from None

DEFAULT_SYNC_INTERVAL = 0.2  # seconds (reference: 200 ms, delta_crdt.ex:31)
DEFAULT_MAX_SYNC_SIZE = 200  # items (reference: delta_crdt.ex:32)

DeltaCrdt = Replica  # the handle type users hold


def start_link(
    crdt_module=AWLWWMap,
    *,
    threaded: bool = True,
    store: "str | None" = None,
    **opts,
) -> Replica:
    """Start a replica (reference ``DeltaCrdt.start_link/2``).

    ``store`` selects the dot-store backend (ISSUE 8): ``"binned"``
    (default) is the bucket-binned row engine; ``"hash"`` is the
    device-resident open-addressing hash table — O(1) point upserts
    that reuse killed lanes (steady-state churn never grows the
    table), dense non-padded wire extraction, and a
    window-pressure-advised ×2 rehash as its only growth event (no
    per-tier repacking; fleet batches survive growth, with the
    advisory growing pressured members off the batch path). The two
    backends are protocol-identical
    and bit-for-bit parity-gated (``tests/test_hash_store.py``,
    ``bench.py --hashstore``); snapshots/WALs record their backend, and
    cross-backend restore goes through extraction (MIGRATING.md).

    ``threaded=True`` runs the periodic anti-entropy loop in a background
    thread (the GenServer-process analog). ``threaded=False`` leaves
    driving to the caller (deterministic tests / benches call
    ``sync_to_all()`` + ``transport.pump()``).

    Durability (no reference analog — the reference writes the whole
    replica image through storage on every change,
    ``causal_crdt.ex:402-403``): pass ``wal_dir=<path>`` to switch
    ``storage_mode="every_op"`` from O(state) snapshot writes to an
    O(delta) write-ahead log (:mod:`delta_crdt_ex_tpu.runtime.wal`).
    Knobs ride along to the replica:

    - ``wal_dir`` — directory for segment files (and, when no
      ``storage_module`` is given, compaction snapshots);
    - ``fsync_mode`` — group-commit cadence: ``"record"`` | ``"batch"``
      (default) | ``"interval"`` | ``"none"``;
    - ``segment_bytes`` — roll to a new segment past this size;
    - ``compact_every`` — checkpoint a snapshot and reclaim covered
      segments after this many appended records.

    Recovery is automatic: a restarted replica with the same ``name``
    and ``wal_dir`` loads the newest snapshot and replays the log past
    it (torn tail records are truncated, not crashed on).

    Ingress coalescing (on by default): the replica's event loop
    batch-receives queued sync slices and joins compatible groups with
    one grouped fan-in kernel dispatch — observably identical to
    sequential handling, measurably faster under fan-in load. Knobs:
    ``ingress_coalesce``, ``max_coalesce``, ``ingress_batch``;
    observability via :meth:`Replica.stats`. WAL segment reclaim is
    additionally gated on the monitored-neighbour ack watermark
    (``membership_compaction``) so a lagging peer's catch-up records
    survive compaction — bounded by ``membership_retain`` records
    (default ``4 * compact_every``) so a never-acking peer cannot grow
    the log without limit.

    Log-shipping catch-up (on by default): a replica gaining or
    re-seeing a neighbour requests that peer's delta-log suffix past
    its applied watermark (``GetLogMsg``) instead of paying the full
    digest walk; the server answers bounded ``LogChunkMsg`` runs of
    full-row slices that merge through the grouped-ingest path. Past
    the compaction horizon the peer weighs the servable suffix against
    the walk-bound prefix: a dominant suffix streams as a
    horizon-clamped chunk run (only the prefix walks), a small one
    skips the chunks entirely — the walk must heal it anyway. Knobs:
    ``log_shipping``, ``catchup_chunk_rows``, ``catchup_suffix_ratio``
    (engage the clamped stream when suffix ≥ ratio × prefix, default
    4); observability under ``Replica.stats()["catchup"]``.

    Hierarchical anti-entropy (ISSUE 15, additive — default off):
    ``tree_gossip=True`` replaces flat all-neighbour sync with a
    deterministic membership-derived spanning tree
    (:mod:`delta_crdt_ex_tpu.runtime.treesync`): every replica
    computes the same tree from the sorted member set + ``tree_seed``
    (no coordinator), leaves sync only their parent, and relays
    coalesce inbound children's deltas into ONE merged re-emission per
    link per epoch — O(fanout) links and bytes per member instead of
    O(neighbours), with multi-hop propagation cascading through relays
    instead of waiting a sync interval per generation. Co-located
    members (same process endpoint, same pinned device, or one fleet /
    mesh) cluster as a bottom-tier subtree whose captain alone gossips
    outward. A parent/relay ``Down`` re-parents deterministically on
    every observer; past ``tree_degrade_ratio`` locally-down members
    the replica falls back to flat gossip until membership stabilises.
    Knobs: ``tree_gossip``, ``tree_fanout`` (default 8), ``tree_seed``,
    ``tree_degrade_ratio`` (default 0.25), ``tree_group`` (explicit
    tier-0 cluster key); observability under
    ``Replica.stats()["tree"]`` and the ``crdt_tree_*`` metric family.
    Gated by ``bench.py --tree`` (propagation rounds + bytes-on-wire
    vs flat gossip at 256 simulated peers, bit-for-bit parity).

    Observability plane (ISSUE 9, off by default): ``obs=True`` joins
    the process-wide :class:`~delta_crdt_ex_tpu.runtime.metrics.
    Observability` plane (``obs=<Observability>`` an explicit one) —
    the always-attached metrics bridge folds every telemetry event
    into a Prometheus-style registry, scrape-time collectors poll
    mailbox depth / WAL footprint / transport bytes, a bounded flight
    recorder keeps the replica's recent structured events (dumped on
    :meth:`Replica.crash`), and the dot-provenance lag tracer samples
    local commits so peers' watermark advances yield per-peer
    convergence-lag histograms with ZERO wire changes. Serve
    ``/metrics`` + ``/healthz`` + ``/varz`` with
    ``obs_plane.serve(port=...)``; the existing ``stats()`` dicts are
    unchanged (MIGRATING.md). Disabled (the default) the hot paths pay
    only ``has_handlers`` lock checks.
    """
    opts.setdefault("sync_interval", DEFAULT_SYNC_INTERVAL)
    opts.setdefault("max_sync_size", DEFAULT_MAX_SYNC_SIZE)
    replica = Replica(_resolve_store(crdt_module, store), **opts)
    if threaded:
        replica.start()
    return replica


def start_fleet(
    n: int,
    crdt_module=AWLWWMap,
    *,
    threaded: bool = True,
    names: "list | None" = None,
    min_batch: int = 2,
    store: "str | None" = None,
    mesh=None,
    **opts,
) -> Fleet:
    """Start ``n`` replicas served by ONE batched event loop (ISSUE 6:
    batched replica fleets — no reference analog; the BEAM gives every
    CRDT its own process, which is exactly the per-user-process cost
    this replaces).

    The fleet drains all ``n`` mailboxes per tick and joins compatible
    sync slices across replicas with one ``vmap``-batched kernel
    dispatch over a leading replica axis, instead of one dispatch (and
    one thread) per replica — the served-users-per-host lever
    (``bench.py --fleet``: ≥3× aggregate merges/sec vs per-replica
    loops at 256 replicas, bit-for-bit parity asserted in-run). Sync
    ticks batch the egress half the same way (ISSUE 10): one vmapped
    digest-tree build + one vmapped eager-delta extraction per shape
    bucket serves every due member, and pushes/openers bound for a
    co-located peer process ship as one ``FleetFrameMsg`` TCP frame
    per endpoint per tick (negotiated; legacy peers get per-member
    frames).
    Observable semantics per member are identical to solo replicas:
    WAL records, acks, diffs, and telemetry fan back out per replica
    (``tests/test_fleet.py`` pins state bits, WAL bytes, and ack
    streams against solo runs).

    ``opts`` are per-replica ``start_link`` options (shared by all
    members; pass ``names`` for explicit member names — a shared
    ``wal_dir`` is safe, segments are per-name). Returns the
    :class:`~delta_crdt_ex_tpu.runtime.fleet.Fleet`; its ``.replicas``
    are ordinary :class:`Replica` handles for ``mutate``/``read``/
    ``set_neighbours``. ``threaded=False`` leaves driving to the
    caller (``fleet.tick()`` / ``fleet.drain()`` +
    ``fleet.run_duties()``).

    ``obs=`` wires the whole fleet into ONE observability plane
    (``True`` = the process-wide default, or an explicit
    :class:`~delta_crdt_ex_tpu.runtime.metrics.Observability`): every
    member registers its varz/health/metric sources and the fleet adds
    its own tick-freshness health check plus occupancy / ragged-fill
    gauges — see :func:`start_link` for the plane's full surface.

    ``mesh=`` (ISSUE 13, additive — default off) shards the fleet's
    batched dispatches over a 1-D replica-sharded device mesh: pass a
    ``jax.sharding.Mesh`` over the ``"replicas"`` axis
    (:func:`delta_crdt_ex_tpu.utils.devices.fleet_mesh` builds one), an
    int shard count, or ``True`` for the detected-topology default.
    Hot kernels then run as ``shard_map`` twins, resident stacked
    states stay device-sharded between ticks, and sync-tick messages
    between co-mesh members deliver as device-side ``ppermute``
    rotations (only off-mesh destinations take the TCP/frame path).
    Semantics are bit-for-bit the vmap fleet's — state, WAL bytes,
    acks and wire bytes (``tests/test_mesh_fleet.py``) — on virtual CPU
    devices. On a four-chip TPU v5e that has not held: one run read
    back a wrong map (``python chip_smoke.py --four-chips``; PERF.md).

    ``tree_gossip=True`` members (ISSUE 15) are stamped with ONE
    shared tier-0 cluster key (the mesh plane's, when ``mesh=`` is on)
    so the whole fleet forms a single bottom-tier subtree of the
    gossip spanning tree: intra-fleet hops are local mailbox (or
    ppermute) deliveries and only the captain gossips outward; relay
    re-emissions ride the tick's frame collector / mesh exchange like
    every other sync send."""
    if names is not None and len(names) != n:
        raise ValueError(f"{len(names)} names for {n} replicas")
    opts.setdefault("sync_interval", DEFAULT_SYNC_INTERVAL)
    opts.setdefault("max_sync_size", DEFAULT_MAX_SYNC_SIZE)
    # resolve the obs knob ONCE so every member and the fleet share one
    # plane (obs=True would resolve identically per member, but an
    # explicit instance must not be re-validated N times either way)
    from delta_crdt_ex_tpu.runtime import metrics as _metrics

    obs_plane = _metrics.resolve_obs(opts.pop("obs", None))
    if obs_plane is not None:
        opts["obs"] = obs_plane
    crdt_module = _resolve_store(crdt_module, store)
    replicas = []
    for i in range(n):
        member = dict(opts)
        if names is not None:
            member["name"] = names[i]
        replicas.append(Replica(crdt_module, **member))
    fleet = Fleet(replicas, min_batch=min_batch, obs=obs_plane, mesh=mesh)
    if threaded:
        fleet.start()
    return fleet


def child_spec(opts: dict | None = None) -> dict:
    """Supervision metadata (reference ``child_spec/1``, ``delta_crdt.ex:68-82``)."""
    opts = dict(opts or {})
    crdt = opts.pop("crdt", None)
    if crdt is None:
        raise ValueError(f"must specify 'crdt' in options, got: {opts!r}")
    name = opts.get("name", "DeltaCrdt")
    shutdown = opts.pop("shutdown", 5.0)
    return {
        "id": name,
        "start": (start_link, (crdt,), opts),
        "shutdown": shutdown,
    }


def set_neighbours(crdt: Replica, neighbours: list) -> None:
    """One-way sync edges; call symmetrically for bidirectional sync
    (reference note, ``delta_crdt.ex:84-95``)."""
    crdt.set_neighbours(neighbours)


#: default call timeout. The reference's GenServer.call default is 5s
#: (``delta_crdt.ex:117-137``) — enough on the BEAM where every op is
#: sub-millisecond, but here a replica's FIRST sync merge jit-compiles
#: for seconds while holding the serialisation lock, so a 5s default
#: would flake on cold starts. Pass ``timeout=5.0`` for strict parity.
DEFAULT_TIMEOUT = 30.0


def mutate(crdt: Replica, f: str, args: list, timeout: float = DEFAULT_TIMEOUT) -> None:
    """Synchronous mutation; raises TimeoutError if the replica stays
    busy past ``timeout`` (``DeltaCrdt.mutate/4``)."""
    crdt.mutate(f, args, timeout)


def mutate_async(crdt: Replica, f: str, args: list) -> None:
    crdt.mutate_async(f, args)


def mutate_batch(
    crdt: Replica, f: str, items: list, timeout: float = DEFAULT_TIMEOUT
) -> None:
    """Bulk mutation (TPU-native extension — no reference analog): one
    ``f`` op per ``items`` entry, applied in order in vectorized batch
    kernels. The natural shape for loads/imports."""
    crdt.mutate_batch(f, items, timeout)


def read(crdt: Replica, timeout: float = DEFAULT_TIMEOUT) -> "dict[Any, Any] | set":
    """Resolved read: a dict for map models, a set for ``AWSet``."""
    return crdt.read(timeout)


def frontdoor(crdt, **opts):
    """The serving front door of a replica or fleet (ISSUE 14) —
    created on first use and cached on the target.

    The front door is the client-facing hot path for heavy traffic:

    - **lock-free snapshot reads** — ``fd.read_keys(keys)`` /
      ``fd.read()`` / ``fd.scan(prefix)`` run off an immutable
      published store generation without EVER taking the replica lock,
      so reads never queue behind the event loop's merges.
      ``Replica.read(timeout)`` keeps its flush-then-read semantics as
      the strong-read mode — serving is additive (MIGRATING.md).
    - **coalesced write admission** — ``fd.mutate(f, args)`` /
      ``fd.mutate_async(f, args)`` fold many concurrent clients' ops
      into one grouped commit per admission window through the SAME
      ``Replica.apply_ops`` entrance ``mutate_batch`` uses: N client
      ops cost one vectorised kernel dispatch + one WAL group commit
      instead of N lock/notify round-trips.
    - **backpressure** — past the admission-queue / mailbox /
      transport ``queue_bytes`` / WAL-backlog limits, ops are shed
      with an explicit :class:`~delta_crdt_ex_tpu.runtime.serve.
      Overloaded`; shedding flips the plane's ``/healthz`` check to
      503 and the ``crdt_serve_*`` metrics family records
      admitted/shed/coalesce-depth/latency.

    ``crdt`` may be a :class:`Replica` (→ :class:`~delta_crdt_ex_tpu.
    runtime.serve.Frontdoor`) or a :class:`~delta_crdt_ex_tpu.runtime.
    fleet.Fleet` (→ one front door per member with key-hash routing).
    Options (``max_commit_ops``, ``max_pending_ops``,
    ``max_mailbox_depth``, ``max_queue_bytes``, ``max_wal_backlog``,
    ``shed_health_hold``, ``journal``) are fixed at first creation.
    Gated by ``bench.py --serve`` (open-loop p50/p99 harness)."""
    return crdt.frontdoor(**opts)
