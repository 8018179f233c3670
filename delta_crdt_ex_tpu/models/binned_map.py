"""BinnedAWLWWMap — the AWLWWMap model over the bucket-binned store.

The TPU-native counterpart of the reference's pluggable ``crdt_module``
(``DeltaCrdt.AWLWWMap``, ``aw_lww_map.ex``), backed by the row-local
kernels of :mod:`delta_crdt_ex_tpu.ops.binned` (see
:mod:`delta_crdt_ex_tpu.models.binned` for the layout). The replica
runtime is generic over this class — the reference's ``crdt_module``
indirection (``causal_crdt.ex:50,72,189,339,384``).

Semantic contract (SURVEY §7 non-negotiables) is identical to the flat
model: add-wins observed-remove; LWW by (ts, writer gid, ctr); causal
join ``(s1∩s2) ∪ (s1∖c2) ∪ (s2∖c1)``; context union = per-replica max.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from delta_crdt_ex_tpu.models.binned import BinnedStore, pow2_tier as _pow2, pow4_tier as _pow4
from delta_crdt_ex_tpu.ops import binned as binned_ops
from delta_crdt_ex_tpu.ops.apply import OP_ADD, OP_CLEAR, OP_PAD, OP_REMOVE
from delta_crdt_ex_tpu.utils import transfers
from delta_crdt_ex_tpu.utils.jitcache import named_jit

# named_jit = jax.jit + compile-cache audit registration under the
# kernel's own name (``crdt_jit_compiles_total{name=...}``)
jit_row_apply = named_jit(binned_ops.row_apply)
jit_clear_all = named_jit(binned_ops.clear_all)
jit_merge_slice = named_jit(
    binned_ops.merge_slice, static_argnames=("kill_budget", "max_inserts")
)
jit_merge_rows = named_jit(binned_ops.merge_rows)
jit_extract_rows = named_jit(binned_ops.extract_rows)
jit_extract_own_delta = named_jit(binned_ops.extract_own_delta)
jit_winners_for_keys = named_jit(binned_ops.winners_for_keys)
jit_winner_rows = named_jit(binned_ops.winner_rows)
jit_winner_all = named_jit(binned_ops.winner_all)
jit_compact_rows = named_jit(binned_ops.compact_rows)
jit_tree_from_leaves = named_jit(binned_ops.tree_from_leaves)

#: the merge kernel's escape flags, read once per kernel call: the
#: blocking wait on every merge, so it is a counted crossing and a
#: ``crdt.readback.binned_map.merge_flags`` span
_TR_MERGE_FLAGS = transfers.register("binned_map.merge_flags")


class GroupedBatch:
    """A local mutation batch grouped by bucket row for :func:`row_apply`.

    ``index`` maps each original batch position to its (row, col) in the
    grouped arrays so callers can recover per-op results (assigned dot
    counters). Shapes are padded to power-of-two tiers to bound kernel
    recompiles.
    """

    def __init__(self, rows, op, key, valh, ts, index):
        self.rows = rows
        self.op = op
        self.key = key
        self.valh = valh
        self.ts = ts
        self.index = index


def group_batch(num_buckets: int, op, key, valh, ts) -> GroupedBatch:
    """Group flat batch arrays (numpy, batch order) by bucket row.

    Ops for the same key keep their relative order inside a row, which is
    all the sequential batch semantics need (ops on different keys
    commute; see :func:`delta_crdt_ex_tpu.ops.binned.row_apply`).
    ``clear`` must be split out by the caller (``clear_all``).
    """
    n = len(op)
    bucket = (key & np.uint64(num_buckets - 1)).astype(np.int64)
    order: dict[int, int] = {}
    cols = np.zeros(n, np.int64)
    counts: dict[int, int] = {}
    urow_of = np.zeros(n, np.int64)
    for i in range(n):
        b = int(bucket[i])
        if b not in order:
            order[b] = len(order)
            counts[b] = 0
        urow_of[i] = order[b]
        cols[i] = counts[b]
        counts[b] += 1
    u = _pow2(max(len(order), 1))
    m = _pow2(max(counts.values(), default=1))
    rows = np.full(u, -1, np.int32)
    for b, r in order.items():
        rows[r] = b
    g_op = np.full((u, m), OP_PAD, np.int32)
    g_key = np.zeros((u, m), np.uint64)
    g_valh = np.zeros((u, m), np.uint32)
    g_ts = np.zeros((u, m), np.int64)
    g_op[urow_of, cols] = op
    g_key[urow_of, cols] = key
    g_valh[urow_of, cols] = valh
    g_ts[urow_of, cols] = ts
    return GroupedBatch(rows, g_op, g_key, g_valh, g_ts, (urow_of, cols))


_CTX_GAP_MSG = (
    "delta-interval slice is not contiguous with the local context; "
    "re-sync with a full-row slice (ctx_lo=0)"
)


class CtxGapError(ValueError):
    """A delta-interval slice is not contiguous with the local context
    (``need_ctx_gap``): growth cannot heal this — the *sender* must fall
    back to a full-row (state-form, ``ctx_lo=0``) slice. A distinct type
    so sync layers that ship delta-intervals can catch it and request the
    fallback — the replica runtime's eager delta pushes do exactly that
    (``runtime/replica.py``: ``_push_deltas`` sends intervals, the
    ``_handle_entries_inner`` catcher answers a gap with a ``GetDiffMsg``
    full-row repair).

    ``gap_rows`` (numpy bool[U], when the row-granular kernel raised) is
    the per-row gap mask; ``gapped_members`` (set[int], when a grouped
    fan-in merge raised) maps those rows back to the offending member
    slices so the caller replays only the gapped senders solo and keeps
    the clean members in one grouped dispatch."""

    gap_rows = None  # numpy bool[U] from the row-granular kernel
    gapped_members: "set[int] | None" = None  # member indices of a grouped merge


def tier_retry_merge(
    state: BinnedStore,
    sl,
    merge,
    compact,
    kill_budget: int,
    max_inserts: int,
    on_grow=None,
):
    """The tier-escalation policy shared by the single-state and the
    vmapped fan-out merge paths: run ``merge(state, sl, kill_budget,
    max_inserts)``, and on overflow grow the offending tier and retry —
    gid table ×2, kill budget ×4 (capped at the slice's row count),
    insert tier ×4 (capped at the slice grid), and for fill overflow one
    compact first, then bin capacity ×2. ``merge`` may be vmapped: flags
    are reduced with any()/all(), so one overflowing neighbour retiers
    the whole stack.

    Returns ``(new_state, last_result, n_retries)``; each retry is one
    fresh jit compile of the new tier combination. Worst case retries ≤
    ``log4(U/kb0) + 1 + log2(B_end/B0) + log2(R_end/R0)`` (holes cannot
    reappear between a compact and the next retry — only successful
    merges create them — so after one compact further fill overflows go
    straight to bin growth).

    Raises :class:`CtxGapError` on a non-contiguous delta-interval:
    growth cannot heal that — the *sender* must fall back to a full-row
    (state-form, ``ctx_lo=0``) slice.
    """
    compacted = False
    retries = 0
    mi = max_inserts
    while True:
        res = merge(state, sl, kill_budget, mi)
        if bool(np.asarray(res.ok).all()):
            return res.state, res, retries
        retries += 1
        if bool(np.asarray(res.need_ctx_gap).any()):
            raise CtxGapError(_CTX_GAP_MSG)
        if bool(np.asarray(res.need_gid_grow).any()):
            state = state.grow(replica_capacity=state.replica_capacity * 2)
            if on_grow:
                on_grow(state)
        if bool(np.asarray(res.need_kill_tier).any()):
            kill_budget = min(kill_budget * 4, int(sl.rows.shape[0]))
        if bool(np.asarray(res.need_ins_tier).any()):
            mi = min(mi * 4, int(sl.alive.size))
        if bool(np.asarray(res.need_fill_compact).any()):
            if not compacted:
                state = compact(state)
                compacted = True
            else:
                state = state.grow(bin_capacity=state.bin_capacity * 2)
                if on_grow:
                    on_grow(state)


def merge_rows_into(state: BinnedStore, sl, on_grow=None):
    """Merge a RowSlice via the row-granular kernel
    (:func:`~delta_crdt_ex_tpu.ops.binned.merge_rows`) — the runtime's
    merge path: slices there are at most ``max_sync_size`` rows, where
    row-granular cost equals the element-scatter path but needs no
    kill-budget or insert tiers (the only escapes left are genuine
    growth). Returns ``(new_state, last_result)``; raises
    :class:`CtxGapError` on a non-contiguous delta-interval."""
    while True:
        res = jit_merge_rows(state, sl)
        ok, ctx_gap, gid_grow, fill_grow = _TR_MERGE_FLAGS.get(
            (res.ok, res.need_ctx_gap, res.need_gid_grow, res.need_fill_grow)
        )
        if ok:
            return res.state, res
        if ctx_gap:
            err = CtxGapError(_CTX_GAP_MSG)
            # per-row gap mask (host copy: error path, cost irrelevant)
            # so grouped callers can isolate the gapped member slices
            err.gap_rows = np.asarray(res.gap_row)
            raise err
        if gid_grow:
            state = state.grow(replica_capacity=state.replica_capacity * 2)
            if on_grow:
                on_grow(state)
        if fill_grow:
            state = state.grow(bin_capacity=state.bin_capacity * 2)
            if on_grow:
                on_grow(state)


#: entry columns of the EntriesMsg wire dict, in RowSlice order
_WIRE_ENTRY_COLS = ("key", "valh", "ts", "ctr", "alive")


def combine_entry_arrays(
    arrays_list: list, to_device: bool = True
) -> "tuple[binned_ops.RowSlice, list]":
    """Combine k host-plane ``EntriesMsg`` column dicts into ONE
    :class:`~delta_crdt_ex_tpu.ops.binned.RowSlice` — the ingress
    coalescing fan-in: instead of k sequential ``merge_rows`` dispatches,
    the runtime merges the whole group with one.

    Safety preconditions (the caller's grouping rules):

    - bucket rows are pairwise DISJOINT across messages — ``merge_rows``
      is row-local, so the combined merge then equals the sequential
      merges bit-for-bit (insert/kill/pack decisions per row see exactly
      the state the sequential merge would);
    - entry lane tiers are EQUAL (``key.shape[1]``) — the row-compact
      sort width is then identical to the per-message merges, so even
      dead-slot bytes match.

    Writer tables are unioned in first-appearance order (message order,
    slot order within a message) — the same order sequential
    ``merge_gid_tables`` calls would append unknown gids in, keeping
    ``ctx_gid`` bit-identical. Each message's ``node`` column and context
    columns are remapped into the union table; zero-gid (empty) slots
    map to a guaranteed-empty padding column so they stay "no local
    slot" (-1) through the kernel's remap, exactly as before combining.

    Returns ``(slice, offsets)`` where ``offsets[i] = (lo, hi)`` is
    message i's row range in the combined slice (for per-message
    accounting over the kernel's per-row counts). ``to_device=False``
    keeps the combined columns as host numpy (the fleet scheduler
    stacks many replicas' combined slices along a leading axis first
    and moves the stack to device in one hop — see
    :func:`stack_entry_slices`).
    """
    # union writer table, first-appearance order
    union_idx: dict[int, int] = {}
    for a in arrays_list:
        for g in np.asarray(a["ctx_gid"]).tolist():
            if g != 0 and g not in union_idx:
                union_idx[g] = len(union_idx)
    rr_u = len(union_idx)
    rp = _pow2(rr_u + 1, floor=2)  # ≥1 trailing zero column, tiered
    null_col = rr_u  # first padding column: gid 0, remaps to -1
    ctx_gid = np.zeros(rp, np.uint64)
    if rr_u:
        ctx_gid[:rr_u] = np.array(list(union_idx), dtype=np.uint64)

    parts: dict[str, list] = {c: [] for c in _WIRE_ENTRY_COLS}
    rows_parts: list = []
    node_parts: list = []
    ctx_rows_parts: list = []
    ctx_lo_parts: list = []
    offsets: list[tuple[int, int]] = []
    off = 0
    for a in arrays_list:
        table = np.asarray(a["ctx_gid"])
        rr_i = table.shape[0]
        remap = np.full(rr_i, null_col, np.int64)
        nz = np.nonzero(table)[0]
        remap[nz] = [union_idx[int(g)] for g in table[nz].tolist()]
        node = np.asarray(a["node"])
        node_parts.append(remap[np.clip(node, 0, rr_i - 1)].astype(np.int32))
        u_i = node.shape[0]
        crows = np.zeros((u_i, rp), np.uint32)
        clo = np.zeros((u_i, rp), np.uint32)
        crows[:, remap[nz]] = np.asarray(a["ctx_rows"])[:, nz]
        clo[:, remap[nz]] = np.asarray(a["ctx_lo"])[:, nz]
        ctx_rows_parts.append(crows)
        ctx_lo_parts.append(clo)
        rows_parts.append(np.asarray(a["rows"], np.int32))
        for c in _WIRE_ENTRY_COLS:
            parts[c].append(np.asarray(a[c]))
        offsets.append((off, off + u_i))
        off += u_i

    cols = {c: np.concatenate(parts[c], axis=0) for c in _WIRE_ENTRY_COLS}
    rows = np.concatenate(rows_parts)
    node = np.concatenate(node_parts, axis=0)
    ctx_rows = np.concatenate(ctx_rows_parts, axis=0)
    ctx_lo = np.concatenate(ctx_lo_parts, axis=0)

    # pad the row axis to the wire tier (bounds distinct compiles); -1
    # rows are dropped by the kernel's valid mask
    u_pad = _pow4(max(off, 1))
    if u_pad != off:
        pad = u_pad - off
        rows = np.concatenate([rows, np.full(pad, -1, np.int32)])
        node = np.concatenate([node, np.zeros((pad,) + node.shape[1:], node.dtype)])
        ctx_rows = np.concatenate([ctx_rows, np.zeros((pad, rp), np.uint32)])
        ctx_lo = np.concatenate([ctx_lo, np.zeros((pad, rp), np.uint32)])
        cols = {
            c: np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
            for c, v in cols.items()
        }

    put = jnp.asarray if to_device else (lambda a: a)
    sl = binned_ops.RowSlice(
        rows=put(rows),
        key=put(cols["key"]),
        valh=put(cols["valh"]),
        ts=put(cols["ts"]),
        node=put(node),
        ctr=put(cols["ctr"]),
        alive=put(cols["alive"]),
        ctx_rows=put(ctx_rows),
        ctx_lo=put(ctx_lo),
        ctx_gid=put(ctx_gid),
    )
    return sl, offsets


#: RowSlice fields padded along the writer-table (Rr) axis by
#: :func:`stack_entry_slices`'s ragged masking
_SLICE_CTX_FIELDS = ("ctx_rows", "ctx_lo")


def stack_entry_slices(
    slices: list, lanes: int | None = None
) -> "tuple[binned_ops.RowSlice, int]":
    """``combine_entry_arrays`` generalised to a replica axis: stack k
    per-replica combined slices (HOST numpy form, ``to_device=False``)
    into one :class:`~delta_crdt_ex_tpu.ops.binned.RowSlice` with a
    leading replica axis, for ONE ``fleet_merge_rows`` dispatch
    (:mod:`delta_crdt_ex_tpu.runtime.transition`).

    Ragged fan-in is handled by PER-REPLICA MASKING, not truncation:

    - row counts pad to the stack's max row tier with ``-1`` rows (the
      kernel's valid mask drops them — bit-for-bit no-ops);
    - writer-table widths pad to the max with zero gids (zero = empty
      slot: ``merge_gid_tables`` skips them and an all-zero interval
      column claims nothing);
    - entry-lane tiers (``key.shape[1]``) must be EQUAL — lane padding
      would change the row-compact sort width and with it dead-slot
      bytes, breaking bit parity (the same-lane-tier rule grouped
      ingest already enforces); the fleet buckets unequal tiers into
      separate dispatches instead.

    ``lanes`` pads the REPLICA axis (compile-shape tiering for the
    batched dispatch) with all-padding lanes that merge nothing; padded
    lanes replicate lane 0's geometry. Returns ``(stacked slice,
    real_rows)`` where ``real_rows`` counts non-padding bucket rows
    across real lanes — the ragged-mask fill-ratio numerator.
    """
    n = len(slices)
    lanes = n if lanes is None else lanes
    s_widths = {s.key.shape[1] for s in slices}
    if len(s_widths) > 1:
        raise ValueError(f"unequal entry-lane tiers in one stack: {s_widths}")
    u_to = max(s.rows.shape[0] for s in slices)
    rp_to = max(s.ctx_gid.shape[0] for s in slices)
    real_rows = 0

    def pad(sl: binned_ops.RowSlice) -> binned_ops.RowSlice:
        du = u_to - sl.rows.shape[0]
        drp = rp_to - sl.ctx_gid.shape[0]
        out = {}
        for c in binned_ops.RowSlice._fields:
            a = np.asarray(getattr(sl, c))
            if c == "rows":
                if du:
                    a = np.concatenate([a, np.full(du, -1, a.dtype)])
            elif c == "ctx_gid":
                if drp:
                    a = np.concatenate([a, np.zeros(drp, a.dtype)])
            else:
                if c in _SLICE_CTX_FIELDS and drp:
                    a = np.concatenate(
                        [a, np.zeros((a.shape[0], drp), a.dtype)], axis=1
                    )
                if du:
                    a = np.concatenate(
                        [a, np.zeros((du,) + a.shape[1:], a.dtype)]
                    )
            out[c] = a
        return binned_ops.RowSlice(**out)

    padded = []
    for sl in slices:
        rows = np.asarray(sl.rows)
        real_rows += int((rows >= 0).sum())
        padded.append(pad(sl))
    if lanes > n:
        blank = binned_ops.RowSlice(
            rows=np.full(u_to, -1, np.int32),
            **{
                c: np.zeros_like(np.asarray(getattr(padded[0], c)))
                for c in binned_ops.RowSlice._fields
                if c != "rows"
            },
        )
        padded.extend([blank] * (lanes - n))
    stacked = binned_ops.RowSlice(
        **{
            c: jnp.asarray(np.stack([np.asarray(getattr(s, c)) for s in padded]))
            for c in binned_ops.RowSlice._fields
        }
    )
    return stacked, real_rows


def merge_group_into(state: BinnedStore, arrays_list: list, on_grow=None):
    """Grouped fan-in merge: combine k compatible host-plane entry
    slices (:func:`combine_entry_arrays`) and join them with ONE
    row-granular kernel dispatch (:func:`merge_rows_into`) — the
    bench-proven grouped-merge amortisation (one device call for the
    whole group) landed on the runtime ingress path. Returns
    ``(new_state, result, offsets)``; raises :class:`CtxGapError` when
    ANY member's delta-interval gaps, with ``gapped_members`` set to the
    offending member indices (mapped from the kernel's per-row gap mask
    through ``offsets``) so the caller replays only those solo and keeps
    the clean members in one grouped dispatch.
    """
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


def merge_into(
    state: BinnedStore, sl, kill_budget: int = 16, on_grow=None, n_alive: int | None = None
):
    """Merge a :class:`~delta_crdt_ex_tpu.ops.binned.RowSlice` into
    ``state`` via :func:`tier_retry_merge` over the element-scatter
    kernel — the bulk fan-in path (cost ∝ slice entries, best for
    sparse many-row slices like the bench's 8192-row delta groups).
    Returns ``(new_state, last_result)``. ``on_grow(state)`` fires after
    each capacity growth (telemetry hook)."""
    # compact the insert scatter to a power-of-two tier of the slice's
    # alive count (scatter cost is per index entry; the [U, S] grid is
    # mostly padding); callers that built the slice from host arrays pass
    # n_alive to avoid a device->host readback here
    if n_alive is None:
        n_alive = int(np.asarray(sl.alive).sum())
    new_state, res, _ = tier_retry_merge(
        state,
        sl,
        lambda st, s, kb, mi: jit_merge_slice(st, s, kill_budget=kb, max_inserts=mi),
        jit_compact_rows,
        kill_budget,
        _pow4(max(n_alive, 1)),
        on_grow=on_grow,
    )
    return new_state, res


class BinnedAWLWWMap:
    """Model class: op vocabulary + kernels over :class:`BinnedStore`."""

    #: mutation name → (op code, arity of user args)
    OPS = {
        "add": (OP_ADD, 2),  # add(key, value)    aw_lww_map.ex:99
        "remove": (OP_REMOVE, 1),  # remove(key)  aw_lww_map.ex:133
        "clear": (OP_CLEAR, 0),  # clear()        aw_lww_map.ex:148
    }

    Store = BinnedStore
    new = staticmethod(BinnedStore.new)
    group_batch = staticmethod(group_batch)
    row_apply = staticmethod(jit_row_apply)
    clear_all = staticmethod(jit_clear_all)
    merge_slice = staticmethod(jit_merge_slice)
    merge_rows = staticmethod(jit_merge_rows)
    extract_rows = staticmethod(jit_extract_rows)
    extract_own_delta = staticmethod(jit_extract_own_delta)
    winners_for_keys = staticmethod(jit_winners_for_keys)
    winner_rows = staticmethod(jit_winner_rows)
    winner_all = staticmethod(jit_winner_all)
    compact_rows = staticmethod(jit_compact_rows)
    tree_from_leaves = staticmethod(jit_tree_from_leaves)
    merge_into = staticmethod(merge_into)
    merge_rows_into = staticmethod(merge_rows_into)
    merge_group_into = staticmethod(merge_group_into)
    combine_entry_arrays = staticmethod(combine_entry_arrays)
    RowSlice = binned_ops.RowSlice

    @staticmethod
    def read_view(d: dict):
        """Shape the resolved winner dict into this model's read form
        (the map: identity). Models sharing the kernel table override
        this (e.g. :class:`AWSet` below)."""
        return d

    # -- replica/fleet backend seam (ISSUE 8): each dot-store backend
    # declares its own growth escape and batch-compatibility key instead
    # of the runtime hardcoding binned geometry

    #: snapshot backend tag (recorded in snapshots; cross-backend
    #: restore must go through extraction — MIGRATING.md)
    backend = "binned"
    #: static (non-array) Store fields — none for this backend
    STORE_META = ()

    @staticmethod
    def grow_for_apply(state: BinnedStore) -> BinnedStore:
        """Local-mutation overflow escape: bin tier ×2."""
        return state.grow(bin_capacity=state.bin_capacity * 2)

    @staticmethod
    def post_apply(state: BinnedStore, res, on_grow=None) -> BinnedStore:
        """Post-commit hook (no load advisory for the binned store)."""
        return state

    @staticmethod
    def load_high(max_window_fill: int, probe_window: int) -> bool:
        """No fleet growth advisory: bin growth happens via the
        per-merge ``need_fill_grow`` escape only."""
        return False

    @staticmethod
    def store_load_high(state: BinnedStore) -> bool:
        return False

    @staticmethod
    def geometry(state: BinnedStore) -> tuple:
        """Batch-compatibility key: fleet batch buckets require equal
        state geometry — for this backend the per-bucket lane tier B,
        which is why binned fleets split batches at tier boundaries."""
        return (
            "binned",
            state.num_buckets,
            state.bin_capacity,
            state.replica_capacity,
        )

    @staticmethod
    def geometry_stacked(stacked) -> tuple:
        """Same key read from a fleet-stacked pytree's shapes (must not
        materialise a lane)."""
        return (
            "binned",
            stacked.key.shape[1],
            stacked.key.shape[2],
            stacked.ctx_gid.shape[1],
        )

    @classmethod
    def fleet_merge_rows(cls, states, slices):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_fleet_merge_rows(states, slices)

    # -- batched fleet egress (ISSUE 10): one vmapped extraction serves
    # a whole sync-tick bucket. Each returns ``(stacked_slice, s_tiers)``
    # where ``s_tiers`` trims each lane's entry-lane axis back to the
    # member's own solo tier (None = the backend's lane axis is static
    # state geometry — nothing to trim; lane k of the stack IS the solo
    # extraction bit-for-bit).

    @classmethod
    def fleet_extract_rows(cls, states, rows):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_fleet_extract_rows(states, rows), None

    @classmethod
    def fleet_extract_own_delta(cls, states, rows, self_slots, gid_selfs, lo):
        from delta_crdt_ex_tpu.runtime import transition

        return (
            transition.jit_fleet_interval_slices(
                states, rows, self_slots, gid_selfs, lo
            ),
            None,
        )

    # NB: no fleet_tree_from_leaves seam — leaf digests are bit-identical
    # across backends, so the fleet's batched tree build groups members
    # by leaf length alone (possibly mixing backends in one stack) and
    # calls transition.jit_fleet_tree_from_leaves directly.

    # -- mesh-sharded fleet seam (ISSUE 13): the same batched forms
    # lifted onto a replica-sharded device mesh. Lane k of the sharded
    # dispatch is bit-for-bit the vmapped (and therefore the solo)
    # kernel on lane k's inputs — the fleet's mesh mode swaps these in
    # without touching any bookkeeping.

    @classmethod
    def mesh_fleet_merge_rows(cls, mesh, states, slices):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_mesh_fleet_merge_rows(mesh, states, slices)

    @classmethod
    def mesh_fleet_extract_rows(cls, mesh, states, rows):
        from delta_crdt_ex_tpu.runtime import transition

        return transition.jit_mesh_fleet_extract_rows(mesh, states, rows), None

    @classmethod
    def mesh_fleet_extract_own_delta(
        cls, mesh, states, rows, self_slots, gid_selfs, lo
    ):
        from delta_crdt_ex_tpu.runtime import transition

        return (
            transition.jit_mesh_fleet_interval_slices(
                mesh, states, rows, self_slots, gid_selfs, lo
            ),
            None,
        )


class AWSet(BinnedAWLWWMap):
    """Add-wins observed-remove set — the second δ-CRDT of the reference
    family (shipped by pre-0.4 versions of the Elixir library; v0.5.10
    kept only AWLWWMap and the pluggable ``crdt_module`` seam this class
    plugs into, ``delta_crdt.ex:56``).

    Presence-only semantics over the identical kernel table: an element
    is a key whose stored value is the constant ``True``; adds/removes
    keep full add-wins observed-remove behaviour (a concurrent add
    survives a remove that did not observe it), and ``read`` returns the
    member set. Diffs feed as ``("add", elem, True)`` / ``("remove",
    elem)``.
    """

    OPS = {
        "add": (OP_ADD, 1),  # add(elem)
        "remove": (OP_REMOVE, 1),  # remove(elem)
        "clear": (OP_CLEAR, 0),  # clear()
    }

    @staticmethod
    def read_view(d: dict):
        return set(d)
