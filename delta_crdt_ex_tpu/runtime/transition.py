"""Pure, jit-able replica state transitions — the device half of the
replica split (ISSUE 6).

:mod:`delta_crdt_ex_tpu.runtime.replica` is two layers fused: a pure
state transition (merge/join/compact/read over the binned store) and an
I/O shell (locks, WAL, transport, payload dicts, telemetry). This module
is the *pure* layer factored out, generalised over a leading replica
axis: every function here is a deterministic function of its array
inputs — no locks, no WAL, no transport, no host syncs — and is safe to
``jax.jit``/``jax.vmap``/``shard_map``. crdtlint enforces the contract
structurally: every function in this module is host-sync-checked as a
jit entry point (SYNC001) and the lattice ops are purity-checked
(PURE001–003).

The fleet forms (``fleet_*``) are the DrJAX-style map/reduce shape over
a leading replica axis (PAPERS.md): N replica states stacked along axis
0 ride ONE batched dispatch instead of N host round-trips — the
single-process thousands-of-replicas scheduler
(:mod:`delta_crdt_ex_tpu.runtime.fleet`) drains N mailboxes per tick
into :func:`fleet_merge_rows`. ``vmap`` adds no arithmetic of its own
(integer/bool lattice math batches element-exact), so a vmapped lane is
bit-for-bit the solo kernel on that lane's inputs — the property
``tests/test_fleet.py`` pins.

The mesh forms (``mesh_fleet_*``, ISSUE 13) are the same fleet kernels
lifted one axis further: ``shard_map`` over a 1-D replica-sharded
``Mesh`` with the per-shard ``vmap`` form inside, so N stacked lanes
split into ``mesh.devices.size`` device-resident blocks and each block
runs the UNCHANGED vmapped kernel — lane k's math is identical whether
its block holds 2 lanes or 256, which is why mesh-vs-vmap fleet runs
are bit-for-bit (the SPMD001 lint family proves the kernels carry no
host callbacks, replica-axis Python branches, or implicit axis
reductions that would break under the lift). ``mesh_plane_rotate`` is
the intra-mesh delivery plane's collective: one ``lax.ppermute``
rotation of padded slice buffers along the replica axis
(:mod:`delta_crdt_ex_tpu.runtime.meshplane`).

Stacking helpers (:func:`stack_states`, :func:`index_state`) are pure
pytree shuffles and live here so the shell never touches array layout.
"""

from __future__ import annotations

import jax

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.models.hash_store import HashStore
from delta_crdt_ex_tpu.ops import binned as binned_ops
from delta_crdt_ex_tpu.ops import hash_map as hash_ops
from delta_crdt_ex_tpu.utils.jitcache import named_jit


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: each lifted
    kernel runs its unchanged per-shard vmap form."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# ---------------------------------------------------------------------------
# single-replica transitions (the replica loop's device calls, re-exported
# here as THE pure seam — the shell may only reach the store through these
# or through models/binned_map.py's tiered wrappers)

merge_rows = binned_ops.merge_rows
row_apply = binned_ops.row_apply
extract_rows = binned_ops.extract_rows
compact_rows = binned_ops.compact_rows
winner_all = binned_ops.winner_all


# ---------------------------------------------------------------------------
# fleet transitions: leading replica axis, one dispatch for N replicas


def fleet_merge_rows(states: BinnedStore, slices) -> binned_ops.MergeRowsResult:
    """Batched anti-entropy merge: lane k joins ``slices`` lane k into
    ``states`` lane k (:func:`~delta_crdt_ex_tpu.ops.binned.merge_rows`
    over a leading replica axis). Every result field gains the leading
    axis — per-lane ``ok``/``need_*`` flags let the host shell retry
    only the overflowing lanes through the solo growth path, and
    per-lane ``gap_row`` masks keep the ``CtxGapError`` repair
    per-sender. Padding lanes (rows all ``-1``) merge nothing and
    report ``ok``."""
    return jax.vmap(binned_ops.merge_rows)(states, slices)


def fleet_row_apply(states, self_slots, rows, op, key, valh, ts):
    """Batched local mutation: lane k applies its bucket-grouped batch
    to ``states`` lane k (:func:`~delta_crdt_ex_tpu.ops.binned.row_apply`
    over a leading replica axis)."""
    return jax.vmap(binned_ops.row_apply)(
        states, self_slots, rows, op, key, valh, ts
    )


def fleet_extract_rows(states, rows) -> binned_ops.RowSlice:
    """Batched sync-slice extraction: lane k gathers its own ``rows``
    lane (``-1`` pads) — the fleet-wide eager-push gather."""
    return jax.vmap(binned_ops.extract_rows)(states, rows)


def fleet_interval_slices(states, rows, self_slots, gid_selfs, lo) -> binned_ops.RowSlice:
    """Batched own-writer delta-interval extraction (ISSUE 10): lane k
    gathers its own alive entries with counter in ``(lo, ctx_max]`` per
    bucket row — the fleet-wide eager-push (Almeida et al.'s delta
    mode) in ONE dispatch instead of one ``extract_own_delta`` per
    member. ``self_slots``/``gid_selfs`` are per-lane scalars; padding
    lanes (rows all ``-1``) extract nothing."""
    return jax.vmap(binned_ops.extract_own_delta)(
        states, rows, self_slots, gid_selfs, lo
    )


def fleet_tree_from_leaves(leaves) -> list:
    """Batched digest-tree build over stacked leaf digests ``[N, L]``:
    one dispatch yields every member's levels (each ``[N, 2^j]``) —
    the sync-tick tree rebuild without N per-member launches. Leaf
    digests are backend-agnostic (the shared sync-index geometry), so
    one form serves both stores."""
    return jax.vmap(binned_ops.tree_from_leaves)(leaves)


def fleet_own_ctr_columns(ctx_max, self_slots):
    """uint32[N, L]: each lane's own-writer ``ctx_max`` column — the
    eager-push cursor source (``Replica._own_ctr_cache``), refreshed
    for a whole fleet in one gather + one host transfer instead of N
    per-member column reads. Backend-agnostic: ``ctx_max`` is the
    shared sync-index geometry."""
    return jax.vmap(lambda cm, s: cm[:, s])(ctx_max, self_slots)


def fleet_compact_rows(states: BinnedStore) -> BinnedStore:
    """Batched full repack + invariant rebuild, one dispatch for the
    whole stack."""
    return jax.vmap(binned_ops.compact_rows)(states)


def fleet_winner_all(states: BinnedStore) -> binned_ops.RowWinners:
    """Batched whole-table LWW winner resolution (the fleet read path)."""
    return jax.vmap(binned_ops.winner_all)(states)


# named_jit = jax.jit + a compile-cache audit registration (the SHAPE
# family's runtime cross-check: ``crdt_jit_compiles_total{name=...}``)
jit_fleet_merge_rows = named_jit(fleet_merge_rows)
jit_fleet_row_apply = named_jit(fleet_row_apply)
jit_fleet_extract_rows = named_jit(fleet_extract_rows)
jit_fleet_interval_slices = named_jit(fleet_interval_slices)
jit_fleet_tree_from_leaves = named_jit(fleet_tree_from_leaves)
jit_fleet_own_ctr_columns = named_jit(fleet_own_ctr_columns)
jit_fleet_compact_rows = named_jit(fleet_compact_rows)
jit_fleet_winner_all = named_jit(fleet_winner_all)


# ---------------------------------------------------------------------------
# hash-store fleet transitions (ISSUE 8): the same leading-replica-axis
# shape over the open-addressing backend — hash-store fleet members
# bucket by TABLE CAPACITY (which moves only on a rehash) instead of
# the binned per-bucket lane tier, so batches survive growth


def fleet_hash_merge_rows(states: HashStore, slices) -> hash_ops.HashMergeResult:
    """Batched anti-entropy merge over stacked hash-store states: lane k
    joins ``slices`` lane k via
    :func:`~delta_crdt_ex_tpu.ops.hash_map.merge_rows`. Per-lane
    ``ok``/``need_*``/``gap_row`` escapes route through the solo
    growth/repair paths exactly like the binned fleet form."""
    return jax.vmap(hash_ops.merge_rows)(states, slices)


def fleet_hash_row_apply(states, self_slots, rows, op, key, valh, ts):
    """Batched local mutation over stacked hash-store states."""
    return jax.vmap(hash_ops.row_apply)(states, self_slots, rows, op, key, valh, ts)


def fleet_hash_winner_all(states: HashStore):
    """Batched whole-table LWW winner resolution, hash backend."""
    return jax.vmap(hash_ops.winner_all)(states)


def fleet_hash_row_counts(states: HashStore, rows):
    """Batched dense-extraction sizing pass: alive entries per requested
    sync row, every lane in one dispatch (``int32[N, U]``) — the host
    reads it back ONCE and tiers each member's dense lane width pow2 so
    ragged members still share one packed-extraction compile."""
    return jax.vmap(hash_ops.row_counts)(states, rows)


def fleet_hash_own_delta_counts(states: HashStore, rows, self_slots, lo):
    """Batched sizing pass for the own-writer delta-interval extraction
    (``int32[N, U]`` entries per row in ``(lo, ∞)``)."""
    return jax.vmap(hash_ops.own_delta_counts)(states, rows, self_slots, lo)


def fleet_hash_extract_rows(states: HashStore, rows, lanes: int) -> binned_ops.RowSlice:
    """Batched dense full-row extraction over stacked hash stores:
    ``lanes`` is the bucket-wide pow2 tier (the max of the members'
    own dense tiers); each member's solo-tier slice is the leading
    ``[:, :member_lanes]`` columns of its lane, bit-for-bit."""
    return jax.vmap(lambda st, r: hash_ops.extract_rows_packed(st, r, lanes))(
        states, rows
    )


def fleet_hash_interval_slices(
    states: HashStore, rows, self_slots, gid_selfs, lo, lanes: int
) -> binned_ops.RowSlice:
    """Batched dense own-writer delta-interval extraction, hash
    backend (the ``fleet_interval_slices`` shape with a bucket-wide
    static dense lane tier)."""
    return jax.vmap(
        lambda st, r, ss, gs, lo_: hash_ops.extract_own_delta_packed(
            st, r, ss, gs, lo_, lanes
        )
    )(states, rows, self_slots, gid_selfs, lo)


jit_fleet_hash_merge_rows = named_jit(fleet_hash_merge_rows)
jit_fleet_hash_row_apply = named_jit(fleet_hash_row_apply)
jit_fleet_hash_winner_all = named_jit(fleet_hash_winner_all)
jit_fleet_hash_row_counts = named_jit(fleet_hash_row_counts)
jit_fleet_hash_own_delta_counts = named_jit(fleet_hash_own_delta_counts)
jit_fleet_hash_extract_rows = named_jit(
    fleet_hash_extract_rows, static_argnames=("lanes",)
)
jit_fleet_hash_interval_slices = named_jit(
    fleet_hash_interval_slices, static_argnames=("lanes",)
)


# ---------------------------------------------------------------------------
# mesh-lifted fleet transitions (ISSUE 13): shard_map over a 1-D
# replica-sharded mesh with the per-shard vmap form inside. The lanes
# axis must be a multiple of the mesh size (the fleet pads lane tiers
# to max(pow2, shards) — the same discipline as the lane/row padding,
# so SHAPE001 stays green); every twin is registered in utils/jitcache
# so the compile-cache audit covers the mesh seam too. The mesh rides
# as a static argument: one tracing-cache entry per (mesh, geometry),
# bounded exactly like the vmap forms' per-geometry entries.

#: the 1-D fleet mesh axis (matches parallel/mesh_gossip.AXIS)
MESH_AXIS = "replicas"


def replica_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis replica sharding over the fleet mesh — the placement
    of every resident stacked state and stacked slice in mesh mode."""
    return NamedSharding(mesh, P(MESH_AXIS))


def _lift(mesh: Mesh, fn):
    """The mesh lift: ``fn`` (a vmapped ``fleet_*`` form) over per-shard
    lane blocks. A single spec broadcasts over the argument and result
    pytrees — every leaf carries the leading replica axis."""
    spec = P(MESH_AXIS)
    return shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)


def mesh_fleet_merge_rows(mesh, states, slices):
    """:func:`fleet_merge_rows` sharded over the replica mesh: each
    device merges its resident lane block, no cross-shard traffic (the
    merge is lane-local; only the delivery plane permutes)."""
    return _lift(mesh, fleet_merge_rows)(states, slices)


def mesh_fleet_row_apply(mesh, states, self_slots, rows, op, key, valh, ts):
    """:func:`fleet_row_apply` sharded over the replica mesh."""
    return _lift(mesh, fleet_row_apply)(
        states, self_slots, rows, op, key, valh, ts
    )


def mesh_fleet_extract_rows(mesh, states, rows):
    """:func:`fleet_extract_rows` sharded over the replica mesh."""
    return _lift(mesh, fleet_extract_rows)(states, rows)


def mesh_fleet_interval_slices(mesh, states, rows, self_slots, gid_selfs, lo):
    """:func:`fleet_interval_slices` sharded over the replica mesh."""
    return _lift(mesh, fleet_interval_slices)(
        states, rows, self_slots, gid_selfs, lo
    )


def mesh_fleet_tree_from_leaves(mesh, leaves):
    """:func:`fleet_tree_from_leaves` sharded over the replica mesh."""
    return _lift(mesh, fleet_tree_from_leaves)(leaves)


def mesh_fleet_own_ctr_columns(mesh, ctx_max, self_slots):
    """:func:`fleet_own_ctr_columns` sharded over the replica mesh."""
    return _lift(mesh, fleet_own_ctr_columns)(ctx_max, self_slots)


def mesh_fleet_hash_merge_rows(mesh, states, slices):
    """:func:`fleet_hash_merge_rows` sharded over the replica mesh."""
    return _lift(mesh, fleet_hash_merge_rows)(states, slices)


def mesh_fleet_hash_row_counts(mesh, states, rows):
    """:func:`fleet_hash_row_counts` sharded over the replica mesh."""
    return _lift(mesh, fleet_hash_row_counts)(states, rows)


def mesh_fleet_hash_own_delta_counts(mesh, states, rows, self_slots, lo):
    """:func:`fleet_hash_own_delta_counts` sharded over the mesh."""
    return _lift(mesh, fleet_hash_own_delta_counts)(
        states, rows, self_slots, lo
    )


def mesh_fleet_hash_extract_rows(mesh, states, rows, lanes: int):
    """:func:`fleet_hash_extract_rows` sharded over the replica mesh
    (``lanes`` is the bucket-wide static dense tier, unchanged)."""
    return _lift(mesh, lambda st, r: fleet_hash_extract_rows(st, r, lanes))(
        states, rows
    )


def mesh_fleet_hash_interval_slices(
    mesh, states, rows, self_slots, gid_selfs, lo, lanes: int
):
    """:func:`fleet_hash_interval_slices` sharded over the replica
    mesh."""
    return _lift(
        mesh,
        lambda st, r, ss, gs, lo_: fleet_hash_interval_slices(
            st, r, ss, gs, lo_, lanes
        ),
    )(states, rows, self_slots, gid_selfs, lo)


def mesh_plane_exchange(mesh, shift: int, depth: int, cols, src, slot):
    """Fused dense-scatter + rotation for the narrow delivery plane
    (ISSUE 17): ``cols`` holds one exchange group's entry rows as dense
    pow2-padded column stacks, ``src``/``slot`` the int32 position of
    each row in the padded ``[shards, depth, ...]`` collective layout.
    The scatter builds that layout ON DEVICE — pad rows carry
    ``src == shards`` and drop out of the scatter (``mode="drop"``) —
    then the buffers ride the same :func:`mesh_plane_rotate`
    ``ppermute``. The host never materialises the padded buffers and
    the rotated result stays device-resident for delivery, which is
    what retires the exchange's ``device_get`` from the transfer
    ledger."""
    shards = mesh.devices.size
    bufs = {
        c: jax.numpy.zeros((shards, depth) + a.shape[1:], a.dtype)
        .at[src, slot]
        .set(a, mode="drop")
        for c, a in cols.items()
    }
    return mesh_plane_rotate(mesh, shift, bufs)


def mesh_plane_rotate(mesh, shift: int, buffers):
    """The intra-mesh delivery plane's collective (ISSUE 13): rotate
    every leaf of ``buffers`` (padded ``[shards, depth, ...]`` slice
    column stacks) ``shift`` shards forward along the replica axis —
    one ``lax.ppermute`` per column, so sync-tick entries bound for a
    co-mesh member ride the interconnect instead of host TCP. The
    permutation is a full static rotation: entries grouped by shard
    distance share one dispatch, and the (mesh size − 1)-rotation
    vocabulary bounds distinct compiles per buffer geometry."""
    n = mesh.devices.size
    perm = [(i, (i + shift) % n) for i in range(n)]

    def rotate(tree):
        return jax.tree.map(
            lambda a: jax.lax.ppermute(a, MESH_AXIS, perm), tree
        )

    return _lift(mesh, rotate)(buffers)


jit_mesh_fleet_merge_rows = named_jit(
    mesh_fleet_merge_rows, static_argnames=("mesh",)
)
jit_mesh_fleet_row_apply = named_jit(
    mesh_fleet_row_apply, static_argnames=("mesh",)
)
jit_mesh_fleet_extract_rows = named_jit(
    mesh_fleet_extract_rows, static_argnames=("mesh",)
)
jit_mesh_fleet_interval_slices = named_jit(
    mesh_fleet_interval_slices, static_argnames=("mesh",)
)
jit_mesh_fleet_tree_from_leaves = named_jit(
    mesh_fleet_tree_from_leaves, static_argnames=("mesh",)
)
jit_mesh_fleet_own_ctr_columns = named_jit(
    mesh_fleet_own_ctr_columns, static_argnames=("mesh",)
)
jit_mesh_fleet_hash_merge_rows = named_jit(
    mesh_fleet_hash_merge_rows, static_argnames=("mesh",)
)
jit_mesh_fleet_hash_row_counts = named_jit(
    mesh_fleet_hash_row_counts, static_argnames=("mesh",)
)
jit_mesh_fleet_hash_own_delta_counts = named_jit(
    mesh_fleet_hash_own_delta_counts, static_argnames=("mesh",)
)
jit_mesh_fleet_hash_extract_rows = named_jit(
    mesh_fleet_hash_extract_rows, static_argnames=("mesh", "lanes")
)
jit_mesh_fleet_hash_interval_slices = named_jit(
    mesh_fleet_hash_interval_slices, static_argnames=("mesh", "lanes")
)
jit_mesh_plane_rotate = named_jit(
    mesh_plane_rotate, static_argnames=("mesh", "shift")
)
jit_mesh_plane_exchange = named_jit(
    mesh_plane_exchange, static_argnames=("mesh", "shift", "depth")
)


# ---------------------------------------------------------------------------
# stacking (pure pytree shuffles — no host round trips)


def stack_pytrees(*trees):
    """Stack per-replica pytrees (identical geometry) along a new
    leading replica axis. Jit this (``jit_stack_pytrees``) on hot
    paths: eager ``jnp.stack`` pays per-operand dispatch overhead
    (expand_dims + concat tracing per member — the dominant cost of
    restacking a 256-member bucket), while the jitted form compiles to
    ONE cached executable per (member count, geometry). Works on whole
    state pytrees and on bare arrays (leaf digests, ctx tables) alike."""
    return jax.tree.map(lambda *xs: jax.numpy.stack(xs), *trees)


jit_stack_pytrees = named_jit(stack_pytrees)


def stack_states(states: list) -> BinnedStore:
    """Stack per-replica states along a new leading replica axis — the
    fleet's resident form (:func:`stack_pytrees` over a list)."""
    return stack_pytrees(*states)


def index_state(stacked: BinnedStore, lane: int) -> BinnedStore:
    """Lane ``lane`` of a stacked fleet state as a solo state pytree."""
    return jax.tree.map(lambda a: a[lane], stacked)
