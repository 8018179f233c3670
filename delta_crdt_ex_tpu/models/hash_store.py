"""Open-addressing hash-table dot store — the second dot-store backend.

Layout (ISSUE 8; WarpSpeed-style bucketed probing, PAPERS.md): every
entry lives in ONE flat table of ``H`` lanes, its slot found by probing
a bounded window from its key's group-aligned base
(:mod:`delta_crdt_ex_tpu.ops.hash_map`). Where the binned store's
``[L, B]`` rows pay tier-promotion repacking when a bucket outgrows its
lane tier — and split fleet batch buckets exactly at those tier
boundaries — this table's only growth event is a load-factor-driven ×2
REHASH of the whole table, so fleets of hash-store members stay batched
through growth and wire slices ship dense (content-sized, not
bin-tier-sized).

Slot lanes (all device-resident, [H]):

    key   : uint64   64-bit key hash (probe target)
    valh  : uint32   value content digest
    ts    : int64    LWW timestamp
    node  : int32    writer replica as LOCAL slot into ctx tables
    ctr   : uint32   dot counter (dot = (gid, sync bucket, ctr))
    alive : bool     entry liveness — a dead lane is FREE (lookups scan
                     the whole fixed probe window, so there are no
                     tombstones; update churn reuses the killed dot's
                     lane and steady state never grows the table)
    ehash : uint32   maintained entry content hash (digest term)
    arr   : uint32   per-sync-bucket arrival stamp (extraction order)

Sync-index bookkeeping — the CLUSTER-AGREED geometry, unchanged from
the binned store so the two backends are protocol-identical (same
digest trees, same walk traffic, same per-bucket causal contexts, same
``CtxGapError`` gap semantics):

    leaf    : uint32[L]    maintained leaf digests (wrapping ehash sums)
    rowseq  : uint32[L]    next arrival stamp per sync bucket
    ctx_gid : uint64[R]    slot → global replica id (0 = empty)
    ctx_max : uint32[L, R] per-bucket per-replica max observed counter

``probe_window`` is STATIC metadata (a jit shape key): the lane count a
lookup scans from its base. Growth policy (:func:`grow_table`): double
``H`` when lanes run out; widen the window too when doubling alone
cannot help (more concurrent dots of one key than window lanes).

:class:`HashAWLWWMap` plugs into the same replica-runtime ``crdt_module``
seam as ``BinnedAWLWWMap`` — grouped ingest (``combine_entry_arrays`` /
``merge_group_into`` reuse the shared wire fan-in), WAL snapshot/replay,
log-shipping serving, and vmapped fleet transitions all work unchanged;
``bench.py --hashstore`` and ``tests/test_hash_store.py`` pin the
bit-for-bit read/state/WAL-bytes/ack parity gate against the binned
store.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from delta_crdt_ex_tpu.models.binned import pow2_tier as _pow2
from delta_crdt_ex_tpu.utils import transfers

#: lanes per probe group (window bases are group-aligned)
GROUP = 8
#: default probe window lanes
DEFAULT_PROBE_WINDOW = 32
#: grow ×2 when the FULLEST probe window passes 3/4 of its lanes —
#: window overflow (never global load) is what forces a rehash, so the
#: advisory watches per-window pressure; the threshold margin absorbs a
#: batch of inserts landing in the hot window before the next advisory
LOAD_NUM, LOAD_DEN = 3, 4


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "key", "valh", "ts", "node", "ctr", "alive", "ehash", "arr",
        "leaf", "rowseq", "ctx_gid", "ctx_max",
    ],
    meta_fields=["probe_window"],
)
@dataclasses.dataclass(frozen=True)
class HashStore:
    key: jax.Array  # uint64[H]
    valh: jax.Array  # uint32[H]
    ts: jax.Array  # int64[H]
    node: jax.Array  # int32[H]
    ctr: jax.Array  # uint32[H]
    alive: jax.Array  # bool[H]
    ehash: jax.Array  # uint32[H]
    arr: jax.Array  # uint32[H]
    leaf: jax.Array  # uint32[L]
    rowseq: jax.Array  # uint32[L]
    ctx_gid: jax.Array  # uint64[R]
    ctx_max: jax.Array  # uint32[L, R]
    probe_window: int = DEFAULT_PROBE_WINDOW

    @property
    def table_size(self) -> int:
        return self.key.shape[-1]

    @property
    def capacity(self) -> int:
        return self.key.shape[-1]

    @property
    def num_buckets(self) -> int:
        return self.leaf.shape[-1]

    @property
    def replica_capacity(self) -> int:
        return self.ctx_gid.shape[-1]

    @staticmethod
    def new(
        num_buckets: int = 64,
        bin_capacity: int = 16,
        replica_capacity: int = 8,
        probe_window: int = DEFAULT_PROBE_WINDOW,
    ) -> "HashStore":
        """Empty state. The signature matches ``BinnedStore.new`` so the
        replica's ``model.new(L, bin_cap, R)`` call serves both backends:
        the table sizes to the same total capacity (``L × bin_capacity``
        lanes, pow2, ≥ 2 probe windows)."""
        L, R = num_buckets, replica_capacity
        H = _pow2(max(num_buckets * bin_capacity, 2 * probe_window, 64))
        return HashStore(
            key=jnp.zeros(H, jnp.uint64),
            valh=jnp.zeros(H, jnp.uint32),
            ts=jnp.zeros(H, jnp.int64),
            node=jnp.zeros(H, jnp.int32),
            ctr=jnp.zeros(H, jnp.uint32),
            alive=jnp.zeros(H, bool),
            ehash=jnp.zeros(H, jnp.uint32),
            arr=jnp.zeros(H, jnp.uint32),
            leaf=jnp.zeros(L, jnp.uint32),
            rowseq=jnp.zeros(L, jnp.uint32),
            ctx_gid=jnp.zeros(R, jnp.uint64),
            ctx_max=jnp.zeros((L, R), jnp.uint32),
            probe_window=probe_window,
        )

    def grow(self, replica_capacity: int | None = None) -> "HashStore":
        """Pad the WRITER tables to a larger tier (unknown-gid growth —
        shared semantics with the binned store). Table growth is NOT a
        pad: it is a rehash (:func:`grow_table`). Rank-agnostic like
        ``BinnedStore.grow`` (works on fleet-stacked states)."""
        r_new = replica_capacity or self.replica_capacity
        dr = r_new - self.replica_capacity
        assert dr >= 0
        if not dr:
            return self
        last = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, dr),))
        return dataclasses.replace(
            self, ctx_gid=last(self.ctx_gid), ctx_max=last(self.ctx_max)
        )

    def entry_gid(self) -> jax.Array:
        """uint64[H]: global replica id of each entry's writer."""
        return self.ctx_gid[self.node]

    def global_ctx(self) -> jax.Array:
        return jnp.max(self.ctx_max, axis=0)

    def own_counter(self, slot) -> jax.Array:
        return jnp.max(self.ctx_max[:, slot])

    def num_alive(self) -> jax.Array:
        return jnp.sum(self.alive.astype(jnp.int32))

    def bucket_of(self, key: jax.Array) -> jax.Array:
        return (key & jnp.uint64(self.num_buckets - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# host-side wrappers: growth policy, dense-tier sizing, model class
# (kernels live in ops/hash_map.py — a pure jit-entry-root module; the
# data-dependent control flow below is host code by design)


def _ops():
    # deferred: ops/hash_map imports this module for HashStore
    from delta_crdt_ex_tpu.ops import hash_map

    return hash_map


class _Jit:
    """Lazily-jitted kernel table (import cycle + first-use compile)."""

    _build_lock = threading.Lock()

    def __getattr__(self, name):
        from delta_crdt_ex_tpu.utils.jitcache import named_jit

        # build + cache + audit-register under one lock: two threads
        # first-touching the same kernel concurrently must not register
        # one jit object while dispatch caches the other — the audit's
        # compile counts would silently read 0 for that root forever
        with _Jit._build_lock:
            fn = self.__dict__.get(name)
            if fn is None:
                ops = _ops()
                static = {
                    "extract_rows_packed": ("lanes",),
                    "extract_own_delta_packed": ("lanes",),
                    "winner_rows_packed": ("lanes",),
                    "rehash": ("table_size", "probe_window"),
                }.get(name, ())
                # named_jit: compile-cache audit registration, prefixed
                # so the hash kernels never collide with the binned
                # roots' names
                fn = named_jit(
                    getattr(ops, name),
                    name=f"hash_{name}",
                    static_argnames=static,
                )
                setattr(self, name, fn)
        return fn


jit = _Jit()

#: the merge kernel's escape flags and window fill, read once per
#: kernel call (the merge's blocking wait, counted and spanned)
_TR_MERGE_FLAGS = transfers.register("hash_store.merge_flags")


def grow_table(state: HashStore, on_grow=None) -> HashStore:
    """THE growth event: rehash ×2 (at least — a nearly-empty table
    whose hot window overflowed still doubles, because the alive count
    does not see the pending inserts that overflowed, and an in-place
    rehash would hand the retry the same full windows). A rehash that
    still cannot place every entry (e.g. more concurrent dots of one
    hot key than window lanes) doubles again and, every second attempt,
    widens the probe window."""
    n_alive = int(np.asarray(state.num_alive()))
    w = state.probe_window
    h_new = max(_pow2(max(2 * n_alive, 2 * w, 64)), 2 * state.table_size)
    attempt = 0
    while True:
        st2, ok = jit.rehash(state, table_size=h_new, probe_window=w)
        if bool(ok):
            if on_grow:
                on_grow(st2)
            return st2
        attempt += 1
        if attempt % 2 == 0 and w < h_new:
            w *= 2
        else:
            h_new *= 2


def maybe_rehash(state: HashStore, max_window_fill: int, on_grow=None) -> HashStore:
    """Growth advisory: grow once the fullest probe window passes
    ``LOAD_NUM/LOAD_DEN`` of its lanes — the precise overflow precursor
    at any table size (doubling the table splits every probe group in
    two, halving window pressure). Update churn never raises it: a kill
    frees its lane for the replacing insert — no tombstones."""
    if max_window_fill * LOAD_DEN > LOAD_NUM * state.probe_window:
        return grow_table(state, on_grow=on_grow)
    return state


def merge_rows_into(state: HashStore, sl, on_grow=None):
    """Merge a RowSlice via the open-addressing kernel — the hash
    backend's runtime merge path (the ``merge_rows_into`` contract of
    ``models/binned_map.py``: same escapes, same :class:`CtxGapError`
    semantics, growth handled here on the host). Returns
    ``(new_state, result)``."""
    from delta_crdt_ex_tpu.models.binned_map import CtxGapError, _CTX_GAP_MSG

    while True:
        res = jit.merge_rows(state, sl)
        ok, wfill, ctx_gap, gid_grow, fill_grow = _TR_MERGE_FLAGS.get(
            (res.ok, res.max_window_fill, res.need_ctx_gap,
             res.need_gid_grow, res.need_fill_grow)
        )
        if ok:
            return maybe_rehash(res.state, int(wfill), on_grow=on_grow), res
        if ctx_gap:
            err = CtxGapError(_CTX_GAP_MSG)
            err.gap_rows = np.asarray(res.gap_row)
            raise err
        if gid_grow:
            state = state.grow(replica_capacity=state.replica_capacity * 2)
            if on_grow:
                on_grow(state)
        if fill_grow:
            state = grow_table(state, on_grow=on_grow)


def merge_group_into(state: HashStore, arrays_list: list, on_grow=None):
    """Grouped fan-in merge over the hash table — the
    ``merge_group_into`` contract (one combined slice, one kernel
    dispatch, ``gapped_members`` mapped through member offsets)."""
    from delta_crdt_ex_tpu.models.binned_map import CtxGapError, combine_entry_arrays

    with jax.profiler.TraceAnnotation("crdt.combine"):
        sl, offsets = combine_entry_arrays(arrays_list)
    try:
        new_state, res = merge_rows_into(state, sl, on_grow=on_grow)
    except CtxGapError as err:
        if err.gap_rows is not None:
            err.gapped_members = {
                i
                for i, (lo, hi) in enumerate(offsets)
                if bool(err.gap_rows[lo:hi].any())
            }
        raise
    return new_state, res, offsets


def _dense_lanes(counts) -> int:
    """pow2 wire tier of the fullest requested row — dense slices vary
    by CONTENT, so tiering bounds the distinct merge compiles. pow2 (not
    the pow4 wire tier): lane padding is exactly the byte overhead this
    store exists to shed, and the extra tier count is bounded by
    log2(max bucket fill)."""
    return _pow2(max(int(np.asarray(counts).max(initial=0)), 1), floor=4)


def extract_rows(state: HashStore, rows) -> "object":
    """Dense full-row slice for the wire/WAL/log-ship path: a counting
    pass sizes the pow2 lane tier, the packed gather fills it (two
    dispatches; the binned store reads its static bin tier instead and
    ships the padding — the byte gap ``bench.py --hashstore`` and the
    catch-up stats quantify)."""
    counts = jit.row_counts(state, rows)
    return jit.extract_rows_packed(state, rows, lanes=_dense_lanes(counts))


def extract_own_delta(state: HashStore, rows, self_slot, gid_self, lo):
    counts = jit.own_delta_counts(state, rows, self_slot, lo)
    return jit.extract_own_delta_packed(
        state, rows, self_slot, gid_self, lo, lanes=_dense_lanes(counts)
    )


def winner_rows(state: HashStore, rows):
    counts = jit.row_counts(state, rows)
    return jit.winner_rows_packed(state, rows, lanes=_dense_lanes(counts))


def winners_for_keys(state: HashStore, khash):
    """LWW point lookup: the HBM-resident Pallas probe kernel on TPU, the
    jitted jnp reference on every other platform (the two agree bit for
    bit on found keys, pinned in ``tests/test_hash_store.py``)."""
    if jax.default_backend() == "tpu":
        return jit.probe_winners(state, khash)
    return jit.winners_for_keys(state, khash)


class HashAWLWWMap:
    """Model class: the AWLWWMap op vocabulary over :class:`HashStore` —
    a drop-in ``crdt_module`` for the replica runtime, selected via
    ``api.start_link(..., store="hash")``."""

    from delta_crdt_ex_tpu.ops.apply import OP_ADD as _A, OP_CLEAR as _C, OP_REMOVE as _R

    OPS = {
        "add": (_A, 2),
        "remove": (_R, 1),
        "clear": (_C, 0),
    }

    #: snapshot/batch-compat backend tag (recorded in snapshots;
    #: cross-backend restore must go through extraction — MIGRATING.md)
    backend = "hash"
    #: static (non-array) Store fields — snapshot as plain ints
    STORE_META = ("probe_window",)

    Store = HashStore
    new = staticmethod(HashStore.new)
    merge_rows_into = staticmethod(merge_rows_into)
    merge_group_into = staticmethod(merge_group_into)
    extract_rows = staticmethod(extract_rows)
    extract_own_delta = staticmethod(extract_own_delta)
    winner_rows = staticmethod(winner_rows)

    @staticmethod
    def group_batch(num_buckets, op, key, valh, ts):
        from delta_crdt_ex_tpu.models.binned_map import group_batch

        return group_batch(num_buckets, op, key, valh, ts)

    @staticmethod
    def combine_entry_arrays(arrays_list, to_device: bool = True):
        from delta_crdt_ex_tpu.models.binned_map import combine_entry_arrays

        return combine_entry_arrays(arrays_list, to_device=to_device)

    # raw jitted kernels (tests, fleet lanes, deterministic drives)
    row_apply = staticmethod(lambda *a: jit.row_apply(*a))
    merge_rows = staticmethod(lambda *a: jit.merge_rows(*a))
    clear_all = staticmethod(lambda *a: jit.clear_all(*a))
    compact_rows = staticmethod(lambda *a: jit.compact_rows(*a))
    #: point reads select the Pallas probe kernel where it lowers
    winners_for_keys = staticmethod(winners_for_keys)
    winner_all = staticmethod(lambda *a: jit.winner_all(*a))

    @staticmethod
    def tree_from_leaves(leaf):
        # leaf digests are bit-identical across backends: one tree fold
        from delta_crdt_ex_tpu.models.binned_map import jit_tree_from_leaves

        return jit_tree_from_leaves(leaf)

    # the wire slice shape is SHARED with the binned backend (one
    # protocol: either store merges either store's slices)
    from delta_crdt_ex_tpu.ops.binned import RowSlice  # noqa: F401

    @staticmethod
    def read_view(d: dict):
        return d

    # -- replica/fleet seam: growth + batch-compatibility ---------------

    @staticmethod
    def grow_for_apply(state: HashStore) -> HashStore:
        """Local-mutation overflow escape: rehash ×2 (the binned
        backend grows its bin tier here)."""
        return grow_table(state)

    @staticmethod
    def post_apply(state: HashStore, res, on_grow=None) -> HashStore:
        """Post-commit growth advisory: the apply result already carries
        the max window fill, so the advisory costs no extra device
        readback."""
        return maybe_rehash(
            state, int(np.asarray(res.max_window_fill)), on_grow=on_grow
        )

    @staticmethod
    def load_high(max_window_fill: int, probe_window: int) -> bool:
        """Fleet post-commit advisory (the vmapped merge result carries
        per-lane max window fills): a lane whose hot window nears
        overflow should grow OFF the batch path, before it escapes
        mid-batch."""
        return max_window_fill * LOAD_DEN > LOAD_NUM * probe_window

    @staticmethod
    def store_load_high(state: HashStore) -> bool:
        """The same advisory recomputed from a live state (the
        replica's under-lock re-check before an advised growth)."""
        return bool(
            int(np.asarray(jit.max_window_fill(state))) * LOAD_DEN
            > LOAD_NUM * state.probe_window
        )

    @staticmethod
    def geometry(state: HashStore) -> tuple:
        """Batch-compatibility key (ISSUE 8 satellite: each backend
        declares its own — the fleet buckets hash members by TABLE
        CAPACITY, which moves only on rehash, instead of the binned
        store's per-bucket lane tier that splits batches at every tier
        boundary)."""
        return (
            "hash",
            state.num_buckets,
            state.table_size,
            state.replica_capacity,
            state.probe_window,
        )

    @staticmethod
    def geometry_stacked(stacked) -> tuple:
        """Same key read from a fleet-stacked pytree's SHAPES (must not
        materialise a lane)."""
        return (
            "hash",
            stacked.leaf.shape[-1],
            stacked.key.shape[-1],
            stacked.ctx_gid.shape[-1],
            stacked.probe_window,
        )

    @classmethod
    def fleet_merge_rows(cls, states, slices):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_fleet_hash_merge_rows(states, slices)

    # -- batched fleet egress (ISSUE 10): dense extraction sizes its
    # lane tier by CONTENT, so the bucket runs at the max of the
    # members' own pow2 tiers and each lane trims back to its solo tier
    # (``s_tiers``) — ragged members share one compile, shipped bytes
    # stay bit-for-bit the solo extraction's.

    @classmethod
    def fleet_extract_rows(cls, states, rows):
        from delta_crdt_ex_tpu.runtime import transition

        counts = np.asarray(transition.jit_fleet_hash_row_counts(states, rows))
        tiers = [_dense_lanes(c) for c in counts]
        sl = transition.jit_fleet_hash_extract_rows(
            states, rows, lanes=max(tiers)
        )
        return sl, tiers

    @classmethod
    def fleet_extract_own_delta(cls, states, rows, self_slots, gid_selfs, lo):
        from delta_crdt_ex_tpu.runtime import transition

        counts = np.asarray(
            transition.jit_fleet_hash_own_delta_counts(states, rows, self_slots, lo)
        )
        tiers = [_dense_lanes(c) for c in counts]
        sl = transition.jit_fleet_hash_interval_slices(
            states, rows, self_slots, gid_selfs, lo, lanes=max(tiers)
        )
        return sl, tiers
    # (no fleet_tree_from_leaves seam: leaf digests are bit-identical
    # across backends — the fleet's batched tree build is model-agnostic)

    # -- mesh-sharded fleet seam (ISSUE 13): the dense sizing pass rides
    # the mesh too, and the bucket-wide static lane tier is computed
    # from the gathered counts exactly as the vmap forms do — padding
    # lanes count zero alive entries, so the tier never moves with the
    # shard padding and lane trims stay bit-for-bit the solo tiers.

    @classmethod
    def mesh_fleet_merge_rows(cls, mesh, states, slices):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_mesh_fleet_hash_merge_rows(mesh, states, slices)

    @classmethod
    def mesh_fleet_extract_rows(cls, mesh, states, rows):
        from delta_crdt_ex_tpu.runtime import transition

        counts = np.asarray(
            transition.jit_mesh_fleet_hash_row_counts(mesh, states, rows)
        )
        tiers = [_dense_lanes(c) for c in counts]
        sl = transition.jit_mesh_fleet_hash_extract_rows(
            mesh, states, rows, lanes=max(tiers)
        )
        return sl, tiers

    @classmethod
    def mesh_fleet_extract_own_delta(
        cls, mesh, states, rows, self_slots, gid_selfs, lo
    ):
        from delta_crdt_ex_tpu.runtime import transition

        counts = np.asarray(
            transition.jit_mesh_fleet_hash_own_delta_counts(
                mesh, states, rows, self_slots, lo
            )
        )
        tiers = [_dense_lanes(c) for c in counts]
        sl = transition.jit_mesh_fleet_hash_interval_slices(
            mesh, states, rows, self_slots, gid_selfs, lo, lanes=max(tiers)
        )
        return sl, tiers


class HashAWSet(HashAWLWWMap):
    """Add-wins observed-remove set over the hash store (the
    ``AWSet``/``BinnedAWLWWMap`` relationship, hash backend)."""

    from delta_crdt_ex_tpu.ops.apply import OP_ADD as _A, OP_CLEAR as _C, OP_REMOVE as _R

    OPS = {
        "add": (_A, 1),
        "remove": (_R, 1),
        "clear": (_C, 0),
    }

    @staticmethod
    def read_view(d: dict):
        return set(d)
