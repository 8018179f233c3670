"""Open-addressing hash-table kernels over the flat dot store.

Second dot-store backend (ISSUE 8): where the binned engine stores
entries in dense ``[L, B]`` bucket rows (one row per sync-index leaf)
and pays tier-promotion repacking when a row outgrows its lane tier,
this engine stores every entry in ONE flat open-addressing table
(WarpSpeed-style bucketed probing, PAPERS.md):

    slot lanes [H] : key/valh/ts/node/ctr/alive/ehash/arr

An entry's slot is found by probing a bounded window of lanes starting
at its key's group-aligned base (``_probe_window``): group size
:data:`GROUP` lanes, window ``probe_window`` lanes, linear at lane
granularity. Placement takes the first DEAD lane of the window — every
lookup scans its whole fixed window masked by ``alive`` (no
early-termination probe chains), so there are no tombstones: a kill
frees its lane immediately and steady-state update churn (kill old
dot, insert new) reuses lanes instead of creeping the table full. A
window with no dead lane signals ``need_fill_grow`` and the host
rehashes the table ×2 — the ONLY growth event (no per-tier repacking,
no lane-tier wire splits). Probing is by KEY, so every concurrent dot
of a key lives in that key's window — point lookups (reads,
kill/present tests) touch ``probe_window`` lanes instead of a bin row.

The sync-index geometry is UNCHANGED from the binned store: the
cluster-agreed ``L`` leaf buckets keep their per-bucket causal context
(``ctx_gid``/``ctx_max``), maintained leaf digests (identical wrapping
ehash sums ⇒ identical digest trees ⇒ identical walk traffic), and
per-(writer, bucket) dot counters. Join semantics are the reference's,
verbatim through the SAME interval preamble the binned kernels use
(:func:`delta_crdt_ex_tpu.ops.binned._slice_view` — one implementation,
so ``CtxGapError`` gap semantics cannot drift between backends).

Every entry carries an ``arr`` arrival stamp (per sync bucket, minted
from ``rowseq``): extraction sorts a bucket's entries by arrival, so
the dense non-padded wire slices this store ships are deterministic —
the canonical order the parity suite compares.

All functions here are pure jit entry roots (crdtlint SYNC001 treats
this module like ``runtime/transition.py``): no host syncs, no
mutation, data-dependent control flow stays on the host (the model
wrapper in :mod:`delta_crdt_ex_tpu.models.hash_store`).

A Pallas TPU kernel serves the probe-window point lookup
(:func:`probe_lookup_pallas`): the table stays in HBM and each query
block DMAs exactly its two 128-lane rows into VMEM — the WarpSpeed
access shape. Selection follows ``ops/pallas_tree.py``: by platform,
the kernel on a TPU and the pure ``jax.numpy`` path (its reference)
everywhere else.

No kernel here sorts the whole table on a hot path: a TPU sort of
``H`` lanes takes minutes to compile at a 1M-key table's width, so
extraction sorts only the slice's entries and the full read resolves
winners within probe windows (``winner_all``).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from delta_crdt_ex_tpu.models.binned import U32_MAX
from delta_crdt_ex_tpu.models.hash_store import GROUP, HashStore
from delta_crdt_ex_tpu.ops import binned as binned_ops
from delta_crdt_ex_tpu.ops.apply import OP_ADD, OP_REMOVE
from delta_crdt_ex_tpu.ops.binned import (
    RowSlice,
    _argmax_lww,
    _slice_view,
    _sorted_winners,
    _table_lookup,
    entry_hash,
)

#: probe-hash salt: the window base must be independent of the sync
#: bucket (= low key bits), or every bucket's entries would crowd a
#: correlated table region
_SALT = jnp.uint64(0x9E3779B97F4A7C15)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def probe_base(key: jnp.ndarray, table_size: int) -> jnp.ndarray:
    """int32[...]: group-aligned first lane of ``key``'s probe window."""
    ng = table_size // GROUP
    h = _mix64(key ^ _SALT)
    return ((h & jnp.uint64(ng - 1)) * jnp.uint64(GROUP)).astype(jnp.int32)


def _window(key: jnp.ndarray, table_size: int, window: int):
    """Candidate lanes ``int32[..., W]`` for ``key`` plus their in-table
    mask. Windows do not wrap: lanes past the table end are masked out,
    so inserts near the end overflow into a rehash instead (placement
    and lookup must agree on one probe sequence)."""
    slots = probe_base(key, table_size)[..., None] + jnp.arange(window, dtype=jnp.int32)
    return slots, slots < table_size


def _place(occupied: jnp.ndarray, want: jnp.ndarray, slots: jnp.ndarray, slot_ok: jnp.ndarray):
    """Place each flagged entry at the first unoccupied lane of its
    candidate window, resolving same-lane collisions *within the batch*:
    rounds of propose → scatter-min claim → winners commit. An entry
    loses a round only when its proposed lane was just taken, so after
    ``W`` rounds it is either placed or its window is provably full.
    ``occupied`` is the POST-KILL alive mask (dead lanes are free — no
    tombstones; see module docstring). Returns ``(placed int32[N] (-1 =
    window full), occupied')`` where ``occupied'`` is the alive mask
    with every placed lane claimed."""
    n, w = slots.shape
    h = occupied.shape[0]
    slots_c = jnp.where(slot_ok, slots, h)  # h = out-of-window sentinel
    used_p = jnp.concatenate([occupied, jnp.ones(1, bool)])  # sentinel lane reads taken
    ids = jnp.arange(n, dtype=jnp.int32)
    placed0 = jnp.full(n, -1, jnp.int32)

    def body(_, carry):
        used_p, placed = carry
        unplaced = want & (placed < 0)
        free = ~used_p[slots_c]  # [N, W]
        has = jnp.any(free, axis=1)
        pos = jnp.argmax(free, axis=1)
        cand = jnp.where(
            unplaced & has,
            jnp.take_along_axis(slots_c, pos[:, None], axis=1)[:, 0],
            h,
        )
        claim = jnp.full(h + 1, n, jnp.int32).at[cand].min(ids)
        win = unplaced & has & (claim[cand] == ids)
        placed = jnp.where(win, cand, placed)
        used_p = used_p.at[jnp.where(win, cand, h)].set(True)
        return used_p, placed

    used_p, placed = jax.lax.fori_loop(0, w, body, (used_p, placed0))
    return placed, used_p[:-1]


def _row_lookup(rows: jnp.ndarray, num_buckets: int):
    """Per-slice row plumbing shared by merge/extract: ``(valid[U],
    rows_safe[U] (L = padding sentinel), rows_clip[U], row_to_u[L])``
    where ``row_to_u`` maps a sync bucket to its position in ``rows``
    (``U`` = not requested)."""
    u = rows.shape[0]
    valid = rows >= 0
    rows_safe = jnp.where(valid, rows, num_buckets)
    rows_clip = jnp.clip(rows_safe, 0, num_buckets - 1)
    row_to_u = (
        jnp.full(num_buckets, u, jnp.int32)
        .at[rows_safe]
        .set(jnp.arange(u, dtype=jnp.int32), mode="drop")
    )
    return valid, rows_safe, rows_clip, row_to_u


def _max_window_fill(alive: jnp.ndarray, table_size: int, window: int) -> jnp.ndarray:
    """int32: alive entries in the fullest probe window. THE growth
    pressure signal: overflow of one window — not global load — is what
    forces a rehash, so the advisory measures per-window fill (one
    cumsum + a strided windowed difference, scale-independent)."""
    cum = jnp.cumsum(alive.astype(jnp.int32))
    bases = jnp.arange(0, table_size, GROUP, dtype=jnp.int32)
    hi = jnp.clip(bases + window - 1, 0, table_size - 1)
    below = cum[bases] - alive[bases].astype(jnp.int32)  # cum[b-1], branch-free
    return jnp.max(cum[hi] - below)


def max_window_fill(state: HashStore) -> jnp.ndarray:
    """Advisory signal recomputed from a state (the fleet's off-batch
    re-check; merge/apply results carry it for free)."""
    return _max_window_fill(state.alive, state.table_size, state.probe_window)


def _entry_rows(state: HashStore) -> jnp.ndarray:
    """int32[H]: the sync bucket of each slot's key (stale for dead
    slots — always mask by ``alive``)."""
    return (state.key & jnp.uint64(state.num_buckets - 1)).astype(jnp.int32)


def _splice_leaf(state: HashStore, alive2, ehash2, rows_safe, rows_clip):
    """Recompute the maintained leaf digests of the touched rows from
    the updated table (wrapping sum of alive ehash — commutative, so it
    lands bit-identical to the binned store's row-local sums)."""
    L = state.num_buckets
    ent_row = _entry_rows(state)
    touched = jnp.zeros(L, bool).at[rows_safe].set(True, mode="drop")
    sel = alive2 & touched[ent_row]
    leaf_all = jnp.zeros(L, jnp.uint32).at[jnp.where(sel, ent_row, L)].add(
        jnp.where(sel, ehash2, jnp.uint32(0)), mode="drop"
    )
    return state.leaf.at[rows_safe].set(leaf_all[rows_clip], mode="drop")


# ---------------------------------------------------------------------------
# local mutation batch


def row_apply(
    state: HashStore,
    self_slot: jnp.ndarray,  # int32 scalar
    rows: jnp.ndarray,  # int32[U] unique bucket rows (-1 = padding)
    op: jnp.ndarray,  # int32[U, M] ops per row, batch order (OP_PAD pads)
    key: jnp.ndarray,  # uint64[U, M]
    valh: jnp.ndarray,  # uint32[U, M]
    ts: jnp.ndarray,  # int64[U, M]
):
    """Apply a bucket-grouped local mutation batch — the open-addressing
    counterpart of :func:`delta_crdt_ex_tpu.ops.binned.row_apply`, with
    IDENTICAL observable semantics (sequential shadowing, per-bucket dot
    counters, kill accounting): kills probe each touched key's window,
    inserts place at the first free window lane. ``ok=False`` means some
    insert's window was full — the host rehashes ×2 and retries."""
    L = state.num_buckets
    H = state.table_size
    W = state.probe_window
    u, m = op.shape
    n = u * m

    valid = rows >= 0
    rows_safe = jnp.where(valid, rows, L)
    rows_clip = jnp.clip(rows_safe, 0, L - 1)

    is_add = (op == OP_ADD) & valid[:, None]
    is_touch = is_add | ((op == OP_REMOVE) & valid[:, None])

    # dot counters: one contiguous sequence per (replica, bucket) — the
    # binned kernel's exact assignment (bit parity of minted dots)
    base = state.ctx_max[rows_clip, self_slot]
    add_rank = jnp.cumsum(is_add.astype(jnp.uint32), axis=1)
    ctr_assigned = base[:, None] + add_rank

    # batch-internal shadowing (binned row_apply, verbatim)
    later = jnp.triu(jnp.ones((m, m), bool), 1)
    key_eq = key[:, :, None] == key[:, None, :]
    shadowed = jnp.any(key_eq & later[None] & is_touch[:, None, :], axis=2)
    ins = is_add & ~shadowed

    # pre-batch kills: probe every touched key's window for alive
    # same-key entries (same key ⇒ same window, so all of them are here)
    key_f = key.reshape(n)
    touch_f = is_touch.reshape(n)
    slots, slot_in = _window(key_f, H, W)
    slots_c = jnp.where(slot_in, slots, H)
    slots_g = jnp.clip(slots, 0, H - 1)
    t_alive = state.alive[slots_g] & slot_in
    match = touch_f[:, None] & t_alive & (state.key[slots_g] == key_f[:, None])
    alive1 = state.alive.at[jnp.where(match, slots_c, H)].set(False, mode="drop")
    killed_any = jnp.any(match, axis=1).reshape(u, m)
    row_killed = jnp.any(killed_any & is_touch, axis=1)

    # inserts: first dead window lane (kills above just freed the old
    # dots' lanes — update churn reuses them), batch collisions resolved
    placed, alive2 = _place(alive1, ins.reshape(n), slots, slot_in)
    ok = ~jnp.any(ins.reshape(n) & (placed < 0))
    tgt = jnp.where(placed >= 0, placed, H)

    gid_self = state.ctx_gid[self_slot]
    eh = entry_hash(key, gid_self, ctr_assigned, ts, valh)
    ins_rank = jnp.cumsum(ins.astype(jnp.uint32), axis=1) - jnp.uint32(1)
    arr_new = state.rowseq[rows_clip][:, None] + ins_rank

    put = lambda col, vals: col.at[tgt].set(vals.reshape(n), mode="drop")
    key2 = put(state.key, key)
    valh2 = put(state.valh, valh)
    ts2 = put(state.ts, ts)
    node2 = put(state.node, jnp.full((u, m), self_slot, jnp.int32))
    ctr2 = put(state.ctr, ctr_assigned)
    ehash2 = put(state.ehash, eh)
    arr2 = put(state.arr, arr_new)

    n_ins_row = jnp.sum(ins.astype(jnp.uint32), axis=1, dtype=jnp.uint32)
    rowseq2 = state.rowseq.at[rows_safe].add(n_ins_row, mode="drop")
    own_max = jnp.max(jnp.where(ins, ctr_assigned, jnp.uint32(0)), axis=1)

    st2 = HashStore(
        key=key2, valh=valh2, ts=ts2, node=node2, ctr=ctr2,
        alive=alive2, ehash=ehash2, arr=arr2,
        leaf=state.leaf, rowseq=rowseq2,
        ctx_gid=state.ctx_gid,
        ctx_max=state.ctx_max.at[rows_safe, self_slot].max(own_max, mode="drop"),
        probe_window=W,
    )
    st2 = dataclasses.replace(
        st2, leaf=_splice_leaf(st2, alive2, ehash2, rows_safe, rows_clip)
    )

    # telemetry count: distinct keys whose dot store changed (binned
    # row_apply, verbatim — first-occurrence marks over the batch)
    earlier = jnp.tril(jnp.ones((m, m), bool), -1)
    first_occ = ~jnp.any(key_eq & earlier[None] & is_touch[:, None, :], axis=2)
    changed = is_touch & first_occ & (ins | killed_any)
    n_keys_changed = jnp.sum(changed.astype(jnp.int32))

    return HashApplyResult(
        st2, ok, ctr_assigned, n_keys_changed, row_killed,
        jnp.sum(alive2.astype(jnp.int32)),
        _max_window_fill(alive2, H, W),
    )


class HashApplyResult(NamedTuple):
    state: HashStore
    ok: jnp.ndarray  # bool: every insert found a free window lane
    ctr_assigned: jnp.ndarray  # uint32[U, M]
    n_keys_changed: jnp.ndarray  # int32
    row_killed: jnp.ndarray  # bool[U]
    #: table pressure, read back alongside ``ok`` so the host's
    #: growth-advisory policy costs no extra device sync
    n_alive: jnp.ndarray  # int32
    max_window_fill: jnp.ndarray  # int32


class HashMergeResult(NamedTuple):
    """Field-compatible superset of
    :class:`delta_crdt_ex_tpu.ops.binned.MergeRowsResult` (the fleet and
    grouped-ingest tails duck-type on these names); ``need_fill_grow``
    here means "some insert's probe window was full — rehash ×2"."""

    state: HashStore
    ok: jnp.ndarray
    need_gid_grow: jnp.ndarray
    need_fill_grow: jnp.ndarray
    need_ctx_gap: jnp.ndarray
    n_inserted: jnp.ndarray
    n_killed: jnp.ndarray
    n_ins_row: jnp.ndarray  # int32[U]
    n_kill_row: jnp.ndarray  # int32[U]
    gap_row: jnp.ndarray  # bool[U]
    n_alive: jnp.ndarray  # int32 (growth advisory, see HashApplyResult)
    max_window_fill: jnp.ndarray  # int32


def clear_all(state: HashStore) -> HashStore:
    """Kill every observed dot (``AWLWWMap.clear``): entries die, the
    context stays. Every lane frees immediately (no tombstones) — the
    next inserts reuse them."""
    return dataclasses.replace(
        state,
        alive=jnp.zeros_like(state.alive),
        leaf=jnp.zeros_like(state.leaf),
    )


# ---------------------------------------------------------------------------
# anti-entropy merge


def merge_rows(state: HashStore, sl: RowSlice) -> HashMergeResult:
    """Join a received bucket slice — the open-addressing counterpart of
    :func:`delta_crdt_ex_tpu.ops.binned.merge_rows`, same join on the
    same wire shape (any ``[U, S]`` slice, padded binned rows or dense
    hash extractions alike):

    - interval/insert preamble via the SHARED ``_slice_view`` (insert
      mask, dense interval bounds, gap detection — gap semantics are the
      binned kernel's by construction);
    - inserts probe-place (cost ∝ slice entries × window);
    - the kill pass tests every alive entry of the synced rows against
      the interval with three O(H) element-wise gathers (bucket → slice
      row → coverage), and presence of local dots in the slice is
      resolved by probing the slice entries' windows — no [H, S] cross
      product, no kill budget, no retry tiers.
    """
    L = state.num_buckets
    H = state.table_size
    W = state.probe_window
    u, s = sl.key.shape

    v = _slice_view(state, sl)
    valid, rows_safe, rows_clip = v.valid, v.rows_safe, v.rows_clip
    gids, rdense, ldense = v.gids, v.rdense, v.ldense
    ln, ln_clip, ins, need_ctx_gap = v.ln, v.ln_clip, v.ins, v.need_ctx_gap

    _, _, _, row_to_u = _row_lookup(sl.rows, L)

    # --- kill pass ((s1∩s2) ∪ (s1∖c2)) over the synced rows ------------
    ent_row = _entry_rows(state)
    u_of = row_to_u[ent_row]  # [H]: position in sl.rows, u = not synced
    in_slice = state.alive & (u_of < u)
    u_clip = jnp.clip(u_of, 0, u - 1)
    node_clip = jnp.clip(state.node, 0, state.replica_capacity - 1)
    cov_hi = rdense[u_clip, node_clip]
    cov_lo = ldense[u_clip, node_clip]
    covered = (cov_hi >= state.ctr) & (cov_lo < state.ctr)

    # presence: probe each slice entry's window for its exact local dot
    r_ok = sl.alive & (ln >= 0) & valid[:, None]
    skey_f = sl.key.reshape(u * s)
    slots, slot_in = _window(skey_f, H, W)
    slots_c = jnp.where(slot_in, slots, H)
    slots_g = jnp.clip(slots, 0, H - 1)
    pmatch = (
        r_ok.reshape(u * s)[:, None]
        & slot_in
        & state.alive[slots_g]
        & (state.key[slots_g] == skey_f[:, None])
        & (state.node[slots_g] == ln_clip.reshape(u * s).astype(jnp.int32)[:, None])
        & (state.ctr[slots_g] == sl.ctr.reshape(u * s)[:, None])
    )
    present = (
        jnp.zeros(H + 1, bool)
        .at[jnp.where(pmatch, slots_c, H)]
        .set(True)[:H]
    )

    die = in_slice & covered & ~present
    alive1 = state.alive & ~die
    n_kill_row = (
        jnp.zeros(u, jnp.int32)
        .at[jnp.where(die, u_of, u)]
        .add(1, mode="drop")
    )

    # --- insert pass (s2 ∖ c1): probe-place the slice entries into
    # dead window lanes (incl. those the kill pass above just freed) ----
    ins_f = ins.reshape(u * s)
    placed, alive2 = _place(alive1, ins_f, slots, slot_in)
    need_fill_grow = jnp.any(ins_f & (placed < 0))
    tgt = jnp.where(placed >= 0, placed, H)

    eh_ins = entry_hash(
        sl.key,
        _table_lookup(sl.ctx_gid, jnp.clip(sl.node, 0, sl.ctx_gid.shape[0] - 1)),
        sl.ctr,
        sl.ts,
        sl.valh,
    )
    ins_rank = jnp.cumsum(ins.astype(jnp.uint32), axis=1) - jnp.uint32(1)
    arr_new = state.rowseq[rows_clip][:, None] + ins_rank

    put = lambda col, vals: col.at[tgt].set(vals.reshape(u * s), mode="drop")
    key2 = put(state.key, sl.key)
    valh2 = put(state.valh, sl.valh)
    ts2 = put(state.ts, sl.ts)
    node2 = put(state.node, ln_clip.astype(jnp.int32))
    ctr2 = put(state.ctr, sl.ctr)
    ehash2 = put(state.ehash, eh_ins)
    arr2 = put(state.arr, arr_new)

    n_ins_row = jnp.sum(ins.astype(jnp.int32), axis=1)
    rowseq2 = state.rowseq.at[rows_safe].add(
        n_ins_row.astype(jnp.uint32), mode="drop"
    )
    ctx2 = jnp.maximum(v.local_ctx, rdense)

    st2 = HashStore(
        key=key2, valh=valh2, ts=ts2, node=node2, ctr=ctr2,
        alive=alive2, ehash=ehash2, arr=arr2,
        leaf=state.leaf, rowseq=rowseq2,
        ctx_gid=gids.ctx_gid,
        ctx_max=state.ctx_max.at[rows_safe].set(ctx2, mode="drop"),
        probe_window=W,
    )
    st2 = dataclasses.replace(
        st2, leaf=_splice_leaf(st2, alive2, ehash2, rows_safe, rows_clip)
    )

    ok = ~(gids.overflow | need_fill_grow | need_ctx_gap)
    return HashMergeResult(
        st2,
        ok,
        gids.overflow,
        need_fill_grow,
        need_ctx_gap,
        jnp.sum(n_ins_row),
        jnp.sum(n_kill_row),
        n_ins_row,
        n_kill_row,
        v.gap_row,
        jnp.sum(alive2.astype(jnp.int32)),
        _max_window_fill(alive2, H, W),
    )


# ---------------------------------------------------------------------------
# extraction (the dense, non-padded wire path)


def row_counts(state: HashStore, rows: jnp.ndarray) -> jnp.ndarray:
    """int32[U]: alive entries per requested sync row — the host sizes
    the dense extraction tier from this before the packed gather."""
    L = state.num_buckets
    u = rows.shape[0]
    _, _, _, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]
    sel = state.alive & (u_of < u)
    return (
        jnp.zeros(u, jnp.int32).at[jnp.where(sel, u_of, u)].add(1, mode="drop")
    )


def own_delta_counts(
    state: HashStore, rows: jnp.ndarray, self_slot: jnp.ndarray, lo: jnp.ndarray
) -> jnp.ndarray:
    """int32[U]: own-writer entries with counter in ``(lo, ∞)`` per
    requested row (the delta-interval extraction's sizing pass)."""
    L = state.num_buckets
    u = rows.shape[0]
    _, _, _, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]
    u_clip = jnp.clip(u_of, 0, u - 1)
    sel = (
        state.alive
        & (u_of < u)
        & (state.node == self_slot)
        & (state.ctr > lo[u_clip])
    )
    return (
        jnp.zeros(u, jnp.int32).at[jnp.where(sel, u_of, u)].add(1, mode="drop")
    )


def _pack_rows(state: HashStore, rows: jnp.ndarray, sel: jnp.ndarray, lanes: int):
    """Pack the selected entries into a dense ``[U, lanes]`` grid, each
    row in arrival (``arr``) order, dead lanes zeroed — the
    deterministic dense wire form. The selected lanes are first
    compacted, in table order, into ``U × lanes`` slots (``lanes`` is at
    least the fullest row's count, so they fit); one stable sort of that
    buffer by ``(row position, arr)`` then orders them, and per-row lane
    = rank − row start. Sorting the buffer rather than the whole table
    keeps the sort's size, and its compile time on a TPU, at the slice's
    size."""
    L = state.num_buckets
    u = rows.shape[0]
    H = state.table_size
    n = u * lanes
    _, _, _, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]

    rank = jnp.cumsum(sel.astype(jnp.int32)) - 1
    lane_of = (
        jnp.full(n, H, jnp.int32)
        .at[jnp.where(sel, rank, n)]
        .set(jnp.arange(H, dtype=jnp.int32), mode="drop")
    )
    used = lane_of < H
    lane_g = jnp.clip(lane_of, 0, H - 1)
    # unused slots sort last: their row position ``u`` is past every row
    _, _, order = jax.lax.sort(
        (jnp.where(used, u_of[lane_g], u), state.arr[lane_g], lane_g),
        num_keys=2,
        is_stable=True,
    )
    sel_s = jnp.arange(n, dtype=jnp.int32) < jnp.sum(used.astype(jnp.int32))
    u_s = jnp.clip(u_of[order], 0, u - 1)
    counts = (
        jnp.zeros(u, jnp.int32).at[jnp.where(sel, u_of, u)].add(1, mode="drop")
    )
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n, dtype=jnp.int32) - starts[u_s]
    tgt_u = jnp.where(sel_s & (pos < lanes), u_s, u)
    tgt_p = jnp.clip(pos, 0, lanes - 1)

    def pack(col):
        return (
            jnp.zeros((u, lanes), col.dtype)
            .at[tgt_u, tgt_p]
            .set(col[order], mode="drop")
        )

    cols = {c: pack(getattr(state, c)) for c in ("key", "valh", "ts", "node", "ctr")}
    alive = (
        jnp.zeros((u, lanes), bool).at[tgt_u, tgt_p].set(sel_s, mode="drop")
    )
    return cols, alive


def extract_rows_packed(state: HashStore, rows: jnp.ndarray, lanes: int) -> RowSlice:
    """Dense full-row state slice (``ctx_lo = 0``) for the requested
    sync rows — the wire/WAL/log-ship transfer shape of this store:
    ``lanes`` is the host-chosen pow2 tier of the fullest requested row,
    so shipped bytes track content, not a bin-capacity tier."""
    L = state.num_buckets
    u = rows.shape[0]
    _, _, _, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]
    sel = state.alive & (u_of < u)
    cols, alive = _pack_rows(state, rows, sel, lanes)
    valid = rows >= 0
    rows_clip = jnp.clip(rows, 0, L - 1)
    return RowSlice(
        rows=rows,
        key=cols["key"],
        valh=cols["valh"],
        ts=cols["ts"],
        node=cols["node"],
        ctr=cols["ctr"],
        alive=alive,
        ctx_rows=state.ctx_max[rows_clip] * valid[:, None].astype(jnp.uint32),
        ctx_lo=jnp.zeros_like(state.ctx_max[rows_clip]),
        ctx_gid=state.ctx_gid,
    )


def extract_own_delta_packed(
    state: HashStore,
    rows: jnp.ndarray,
    self_slot: jnp.ndarray,
    gid_self: jnp.ndarray,
    lo: jnp.ndarray,
    lanes: int,
) -> RowSlice:
    """Dense own-writer delta-interval slice (Almeida et al.'s delta
    mode), claiming exactly ``(lo, ctx_max]`` per row — the eager-push
    shape, without the binned store's whole-row lane padding."""
    L = state.num_buckets
    u = rows.shape[0]
    valid, _, rows_clip, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]
    u_clip = jnp.clip(u_of, 0, u - 1)
    sel = (
        state.alive
        & (u_of < u)
        & (state.node == self_slot)
        & (state.ctr > lo[u_clip])
    )
    cols, alive = _pack_rows(state, rows, sel, lanes)
    hi = state.ctx_max[rows_clip, self_slot] * valid.astype(jnp.uint32)
    return RowSlice(
        rows=rows,
        key=cols["key"],
        valh=cols["valh"],
        ts=cols["ts"],
        node=jnp.zeros_like(cols["node"]),
        ctr=cols["ctr"],
        alive=alive,
        ctx_rows=hi[:, None],
        ctx_lo=(lo * valid.astype(jnp.uint32))[:, None],
        ctx_gid=gid_self[None],
    )


# ---------------------------------------------------------------------------
# reads


def winners_for_keys(state: HashStore, khash: jnp.ndarray):
    """LWW winner per queried key hash: gather each key's probe window
    (``W`` lanes — the O(1) point read this layout exists for) and take
    the lexicographic (ts, gid, ctr) maximum among alive matches."""
    H = state.table_size
    W = state.probe_window
    slots, slot_in = _window(khash, H, W)
    slots_g = jnp.clip(slots, 0, H - 1)
    g_alive = state.alive[slots_g] & slot_in & (state.key[slots_g] == khash[:, None])
    g_gid = _table_lookup(
        state.ctx_gid,
        jnp.clip(state.node[slots_g], 0, state.replica_capacity - 1),
    )
    g_ctr = state.ctr[slots_g]
    g_ts = state.ts[slots_g]
    best = _argmax_lww(g_ts, g_gid, g_ctr, g_alive)
    take = lambda a: jnp.take_along_axis(a, best, axis=1)[:, 0]
    return binned_ops.KeyWinners(
        found=take(g_alive),
        gid=take(g_gid),
        ctr=take(g_ctr),
        valh=take(state.valh[slots_g]),
        ts=take(g_ts),
    )


def winner_all(state: HashStore):
    """Whole-table LWW winners, one ``[1, H]`` row in table order (the
    binned ``winner_all`` shape; callers select by ``win``). No sort:
    every dot of a key lies in that key's probe window, so two lanes
    holding one key are fewer than ``W`` lanes apart. An alive lane wins
    unless a lane within ``W - 1`` of it holds the same key and ranks
    above it in (ts, gid, ctr) — ties go to the right-hand lane, so each
    key keeps exactly one winner. ``2W - 2`` shifted compares replace a
    whole-table sort, whose TPU compile takes minutes at this width."""
    W = state.probe_window
    H = state.table_size
    gid = _table_lookup(
        state.ctx_gid, jnp.clip(state.node, 0, state.replica_capacity - 1)
    )
    t, g, c = binned_ops._lww_rank(state.ts, gid, state.ctr, state.alive)
    pad = lambda a: jnp.concatenate([jnp.zeros(W, a.dtype), a, jnp.zeros(W, a.dtype)])
    cols = tuple(map(pad, (state.key, state.alive, t, g, c)))

    def beaten_at(d, beaten):
        s = d - (W - 1)  # neighbour offset in [1 - W, W - 1]
        key, alive, t2, g2, c2 = (
            jax.lax.dynamic_slice(a, (W + s,), (H,)) for a in cols
        )
        above = (t2 > t) | ((t2 == t) & (
            (g2 > g) | ((g2 == g) & ((c2 > c) | ((c2 == c) & (s > 0))))
        ))
        return beaten | ((s != 0) & alive & (key == state.key) & above)

    beaten = jax.lax.fori_loop(0, 2 * W - 1, beaten_at, jnp.zeros(H, bool))
    one = lambda a: a[None, :]
    return binned_ops.RowWinners(
        one(state.alive & ~beaten), one(state.key), one(g), one(state.ctr),
        one(state.valh), one(t),
    )


def winner_rows_packed(state: HashStore, rows: jnp.ndarray, lanes: int):
    """Per-key LWW winners within the given sync rows: pack the rows'
    entries dense, then the shared sorted-winner core."""
    L = state.num_buckets
    u = rows.shape[0]
    _, _, _, row_to_u = _row_lookup(rows, L)
    u_of = row_to_u[_entry_rows(state)]
    sel = state.alive & (u_of < u)
    cols, alive = _pack_rows(state, rows, sel, lanes)
    gid = _table_lookup(
        state.ctx_gid, jnp.clip(cols["node"], 0, state.replica_capacity - 1)
    )
    return _sorted_winners(
        cols["key"], cols["ts"], gid, cols["ctr"], alive, cols["valh"]
    )


# ---------------------------------------------------------------------------
# maintenance: rehash (THE growth event) + invariant rebuild


def rehash(state: HashStore, table_size: int, probe_window: int):
    """Rebuild the table at ``table_size`` lanes: every alive entry
    re-places by bucketed linear probing. The
    placement is one sort + one cumulative max — entries sorted by
    (new base, arrival) take ``slot_j = j + cummax(base_j − j)``, which
    IS linear probing's first-free-lane rule evaluated for the whole
    table at once. Returns ``(state', ok)``; ``ok=False`` (an entry
    displaced past the probe window, or off the table end) tells the
    host to grow further / widen the window and retry."""
    H_old = state.table_size
    sel = state.alive
    base = probe_base(state.key, table_size)
    # two 32-bit sort keys, not one packed 64-bit key: the chip emulates
    # 64-bit compares, and the sort compiles for twice as long
    _, _, order = jax.lax.sort(
        (
            jnp.where(sel, base, jnp.int32(2**31 - 1)),
            jnp.where(sel, state.arr, jnp.uint32(U32_MAX)),
            jnp.arange(H_old, dtype=jnp.int32),
        ),
        num_keys=2,
        is_stable=True,
    )
    sel_s = sel[order]
    base_s = base[order].astype(jnp.int64)
    j = jnp.arange(H_old, dtype=jnp.int64)
    slot = j + jax.lax.cummax(jnp.where(sel_s, base_s - j, jnp.int64(-(2**40))))
    disp = slot - base_s
    ok = ~jnp.any(sel_s & ((slot >= table_size) | (disp >= probe_window)))
    tgt = jnp.where(sel_s & (slot < table_size), slot, table_size).astype(jnp.int32)

    def move(col):
        return (
            jnp.zeros(table_size, col.dtype).at[tgt].set(col[order], mode="drop")
        )

    alive_new = jnp.zeros(table_size, bool).at[tgt].set(sel_s, mode="drop")
    st2 = HashStore(
        key=move(state.key),
        valh=move(state.valh),
        ts=move(state.ts),
        node=move(state.node),
        ctr=move(state.ctr),
        alive=alive_new,
        ehash=move(state.ehash),
        arr=move(state.arr),
        leaf=state.leaf,
        rowseq=state.rowseq,
        ctx_gid=state.ctx_gid,
        ctx_max=state.ctx_max,
        probe_window=probe_window,
    )
    return st2, ok


def compact_rows(state: HashStore) -> HashStore:
    """Rebuild the maintained leaf digests from the entry lanes (the
    binned ``compact_rows`` analog for host-constructed states; the
    table itself has nothing to repack — rehash owns reclamation)."""
    L = state.num_buckets
    ent_row = _entry_rows(state)
    sel = state.alive
    leaf = (
        jnp.zeros(L, jnp.uint32)
        .at[jnp.where(sel, ent_row, L)]
        .add(jnp.where(sel, state.ehash, jnp.uint32(0)), mode="drop")
    )
    return dataclasses.replace(state, leaf=leaf)


# ---------------------------------------------------------------------------
# Pallas TPU point-lookup kernel (the jnp path above is its reference)

#: lanes per table row the kernel DMAs
_PL_LANES = 128
#: fewest queries per grid step: a rank-1 SMEM block spans a multiple
#: of 128 entries
_PL_QUERIES = 128
#: table columns the kernel DMAs per query
_PL_COLS = 8


def _cover_rows(window: int) -> int:
    """Table rows that always hold a whole probe window: a window base
    is group-aligned, so it starts at most ``_PL_LANES - GROUP`` lanes
    into its row."""
    return (_PL_LANES - GROUP + window - 1) // _PL_LANES + 1


def _probe_kernel_body(nq: int, window: int, r: int, nrows: int):
    """Build the kernel fn for a static (block, window, R) shape. All
    comparisons run on int32 bit-halves (TPU has no native i64): an
    unsigned word is ordered as a signed one after flipping its sign
    bit, and the LWW order is resolved by lexicographic candidate
    narrowing over (ts_hi, ts_lo, gid_hi, gid_lo, ctr) — the
    ``_argmax_lww`` scheme on word halves. Per query the kernel DMAs
    only the 128-lane HBM rows covering its probe window — the
    WarpSpeed access shape."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(
        base_ref,  # SMEM int32[NQ]  window base lane per query
        qlo_ref, qhi_ref,  # SMEM int32[NQ] query key halves
        gid_ref,  # SMEM int32[2R] ctx_gid halves (hi[0..R), lo[0..R))
        tkey_lo_ref, tkey_hi_ref,  # HBM int32[H/128, 128] key bit-halves
        alive_ref,  # HBM int32[H/128, 128]
        node_ref,  # HBM int32[H/128, 128]
        ctr_ref,  # HBM int32[H/128, 128] (uint32 bits)
        tslo_ref, tshi_ref,  # HBM int32[H/128, 128] ts bit-halves
        valh_ref,  # HBM int32[H/128, 128]
        out_ref,  # SMEM int32[NQ * 8]
        scratch, sems,
    ):
        cols = (tkey_lo_ref, tkey_hi_ref, alive_ref, node_ref, ctr_ref,
                tslo_ref, tshi_ref, valh_ref)
        # x64 is on process-wide: every scalar the kernel computes with
        # is an explicit int32, or Mosaic is handed i64 operands
        i32 = jnp.int32
        low = i32(-(2**31))  # also the sign bit
        far = i32(2**30)
        lanes = i32(_PL_LANES)
        cover = _cover_rows(window)
        tile = (cover, _PL_LANES)
        lane = (
            jax.lax.broadcasted_iota(jnp.int32, tile, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, tile, 1)
        )

        def one_query(q, carry):
            b = base_ref[q]
            # ``cover`` rows from b's row hold [b, b+window); near the
            # table's end the rows shift back (lanes outside the window
            # are masked off by the window test either way)
            row0 = jnp.minimum(jax.lax.div(b, lanes), i32(nrows - cover))
            copies = [
                pltpu.make_async_copy(
                    ref.at[pl.ds(row0, cover)],
                    scratch.at[i32(ci)],
                    sems.at[i32(ci)],
                )
                for ci, ref in enumerate(cols)
            ]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            flat = lane + row0 * lanes
            off = flat - b
            in_win = (off >= i32(0)) & (off < i32(window))
            keq = (scratch[i32(0)] == qlo_ref[q]) & (scratch[i32(1)] == qhi_ref[q])
            alive = scratch[i32(2)] != i32(0)
            m = in_win & keq & alive
            node = scratch[i32(3)]
            g_lo = jnp.zeros(tile, jnp.int32)
            g_hi = jnp.zeros(tile, jnp.int32)
            for i in range(r):
                g_hi = jnp.where(node == i32(i), gid_ref[i32(i)], g_hi)
                g_lo = jnp.where(node == i32(i), gid_ref[i32(r + i)], g_lo)
            cand = m
            for part in (scratch[i32(6)], scratch[i32(5)], g_hi, g_lo, scratch[i32(4)]):
                u = part ^ low  # unsigned order as signed
                top = jnp.max(jnp.where(cand, u, low))
                cand = cand & (u == top)
            found = jnp.max(m.astype(jnp.int32))
            # winner lane: the (unique) surviving candidate's value
            pick = lambda col: jnp.max(jnp.where(cand, col, low))
            free_slot = jnp.min(jnp.where(in_win & ~alive, flat, far))
            hit = found != i32(0)
            vals = (
                found,
                jnp.where(hit, pick(flat), i32(-1)),
                jnp.where(hit, pick(node), i32(0)),
                jnp.where(hit, pick(scratch[i32(4)]), i32(0)),
                jnp.where(hit, pick(scratch[i32(7)]), i32(0)),
                jnp.where(hit, pick(scratch[i32(5)]), i32(0)),
                jnp.where(hit, pick(scratch[i32(6)]), i32(0)),
                free_slot,
            )
            for c, v in enumerate(vals):
                out_ref[q * i32(_PL_COLS) + i32(c)] = v
            return carry

        jax.lax.fori_loop(i32(0), i32(nq), one_query, i32(0))

    return kernel


def probe_lookup_pallas(
    khash: jnp.ndarray, state: HashStore, interpret: bool = False
) -> jnp.ndarray:
    """Pallas point lookup: per query key, DMA the 128-lane HBM rows
    covering its probe window into VMEM and resolve the LWW winner (and
    the first free lane — the upsert probe) on-chip. Returns
    ``int32[Q, 8]`` (found, slot, node, ctr, valh, ts_lo, ts_hi,
    free_slot). 64-bit columns travel as uint32 half-lanes (TPU has no
    native i64); timestamps are non-negative LWW stamps. A table smaller
    than the rows one window needs is padded with dead lanes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H = state.table_size
    W = state.probe_window
    R = state.replica_capacity
    cover = _cover_rows(W)
    nrows = max(-(-H // _PL_LANES), cover)
    q = khash.shape[0]
    # XLA tiles a rank-1 SMEM array by min(size, 1024) lanes, and each
    # block must match that tiling: pow2 up to one block, then whole
    # blocks of 1024 queries
    nq = min(max(pl.next_power_of_2(q), _PL_QUERIES), 1024)
    q_pad = -(-q // nq) * nq
    kh = jnp.concatenate([khash, jnp.zeros(q_pad - q, jnp.uint64)])
    rows = lambda a: jnp.pad(a, (0, nrows * _PL_LANES - H)).reshape(
        nrows, _PL_LANES
    )
    i32 = lambda u32_bits: jax.lax.bitcast_convert_type(u32_bits, jnp.int32)
    lo32 = lambda a64: i32((a64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
    hi32 = lambda a64: i32((a64 >> jnp.uint64(32)).astype(jnp.uint32))
    ts_u = state.ts.astype(jnp.uint64)  # non-negative LWW stamps
    smem = lambda n: pl.BlockSpec((n,), lambda i: (i,), memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _probe_kernel_body(nq, W, R, nrows),
        out_shape=jax.ShapeDtypeStruct((q_pad * _PL_COLS,), jnp.int32),
        grid=(q_pad // nq,),
        in_specs=[
            smem(nq), smem(nq), smem(nq),
            pl.BlockSpec(
                (2 * R,), lambda i: (jnp.int32(0),), memory_space=pltpu.SMEM
            ),
            *[hbm] * _PL_COLS,
        ],
        out_specs=smem(nq * _PL_COLS),
        scratch_shapes=[
            pltpu.VMEM((_PL_COLS, cover, _PL_LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((_PL_COLS,)),
        ],
        interpret=interpret,
    )(
        probe_base(kh, H), lo32(kh), hi32(kh),
        jnp.concatenate([hi32(state.ctx_gid), lo32(state.ctx_gid)]),
        rows(lo32(state.key)), rows(hi32(state.key)),
        rows(state.alive.astype(jnp.int32)),
        rows(state.node),
        rows(i32(state.ctr)),
        rows(lo32(ts_u)), rows(hi32(ts_u)),
        rows(i32(state.valh)),
    )
    return out.reshape(q_pad, _PL_COLS)[:q]


def probe_winners(state: HashStore, khash: jnp.ndarray, interpret: bool = False):
    """:class:`~delta_crdt_ex_tpu.ops.binned.KeyWinners` view of the
    Pallas probe kernel's raw int32 grid — the TPU form of
    :func:`winners_for_keys` (found keys agree bit for bit; a key that
    is not found reports zeros)."""
    out = probe_lookup_pallas(khash, state, interpret=interpret)
    u32 = lambda col: jax.lax.bitcast_convert_type(col, jnp.uint32)
    node = jnp.clip(out[:, 2], 0, state.replica_capacity - 1)
    ts_lo = u32(out[:, 5]).astype(jnp.uint64)
    ts_hi = u32(out[:, 6]).astype(jnp.uint64)
    return binned_ops.KeyWinners(
        found=out[:, 0] != 0,
        gid=_table_lookup(state.ctx_gid, node),
        ctr=u32(out[:, 3]),
        valh=u32(out[:, 4]),
        ts=((ts_hi << jnp.uint64(32)) | ts_lo).astype(jnp.int64),
    )
