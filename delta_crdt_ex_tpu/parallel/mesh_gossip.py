"""Multi-chip replication over a device mesh — the ICI data plane.

The reference scales across machines via distributed Erlang's full-mesh
TCP (SURVEY §5.8). The TPU-native equivalent keeps one replica state
resident per device of a ``jax.sharding.Mesh`` and moves whole state
pytrees device↔device over ICI with ``lax.ppermute`` inside ``shard_map``
— no host hop, XLA schedules the collective. A gossip *step* is:

1. (optional) apply a per-replica local mutation batch (the bucket-
   grouped ``row_apply`` kernel — the "compute" of the step);
2. ``ppermute`` the full state pytree one hop around the ring;
3. merge the received state's full-row slice shard-locally;
4. rebuild digest-tree roots from the maintained leaf digests (the
   observability/convergence probe).

Ring gossip converges every replica in ≤ N-1 steps (each state travels
the whole ring); anti-entropy idempotence makes over-delivery harmless —
semantically this is the reference's neighbour gossip with a ring
topology, executed as one SPMD program.

The bounded-divergence variant over ICI is layered the same way —
extract row slices on device, ppermute fixed-size slices, merge — and is
what :mod:`delta_crdt_ex_tpu.parallel.batched_sync` does within a chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.ops.binned import (
    extract_rows,
    flagged_first_order,
    merge_rows,
    row_apply,
    tree_from_leaves,
)

AXIS = "replicas"


def make_mesh(devices=None) -> Mesh:
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (AXIS,))


def replica_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis = replica, one per device."""
    return NamedSharding(mesh, P(AXIS))


def place_states(states: list[BinnedStore], mesh: Mesh) -> BinnedStore:
    """Stack N replica states and shard one-per-device over the mesh."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.device_put(stacked, replica_sharding(mesh))


def _squeeze(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _unsqueeze(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


@partial(jax.jit, static_argnames=("mesh", "frontier"))
def gossip_delta_step(
    mesh: Mesh,
    stacked: BinnedStore,
    self_slot: jnp.ndarray,  # int32[N]
    rows: jnp.ndarray,  # int32[N, U]  bucket-grouped mutation batches
    op: jnp.ndarray,  # int32[N, U, M]
    key: jnp.ndarray,  # uint64[N, U, M]
    valh: jnp.ndarray,  # uint32[N, U, M]
    ts: jnp.ndarray,  # int64[N, U, M]
    frontier: int = 64,
):
    """One bounded-divergence SPMD gossip step — ICI bytes ∝ divergence.

    The host sync walk ships digest blocks level-by-level because digests
    are expensive to move over a slow control plane (the reference's
    8-levels-per-round partial diff, ``causal_crdt.ex:96,255``). Over ICI
    the whole leaf-digest vector is cheap (``L`` × 4 bytes ≪ one bucket
    slice), so the walk collapses to a single exchange; the bytes that
    matter — entry slices — ship only for buckets that actually differ:

    1. apply the per-replica local mutation batch (``row_apply``);
    2. ppermute **leaf digests** one hop forward (i → i+1): the receiver
       compares against its own leaves and selects up to ``frontier``
       differing buckets (fixed-size padded frontier — the
       ``max_sync_size`` analog, ``causal_crdt.ex:206-214``);
    3. ppermute the **frontier request** one hop backward (the receiver
       asks its ring predecessor — the ``{:get_diff, …}`` analog,
       ``causal_crdt.ex:112-123``);
    4. the predecessor extracts exactly those bucket rows and ppermutes
       the **slice** forward; the receiver joins it shard-locally.

    Per-step ICI traffic = L·4 (digests) + frontier·4 (request) + one
    fixed ``frontier``-row slice — the slice is the only term that scales,
    and it is bounded by the actual divergence (padded rows are -1 and
    merge as no-ops). Divergence beyond ``frontier`` buckets heals over
    subsequent steps (``n_diff`` reports the true differing-bucket count;
    sync is idempotent).

    Returns ``(stacked, roots, ok, n_diff, flags)``; ``ok[i]`` folds the
    local apply's bin-capacity flag AND the merge's tier flags — a False
    means replica i's step is invalid and the host must grow that tier
    and replay from the pre-step state (growth cannot happen inside the
    SPMD program; :func:`gossip_delta_drive` is that recovery loop).
    ``flags[i] = [apply_fill, gid_grow, merge_fill]`` names the
    offending tier (the row-granular merge has no kill or insert tiers).
    """
    n = mesh.devices.size
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    spec = P(AXIS)

    def step(local, slot, rows_b, op_b, key_b, valh_b, ts_b):
        local = _squeeze(local)
        applied = row_apply(
            local, slot[0], rows_b[0], op_b[0], key_b[0], valh_b[0], ts_b[0]
        )
        st = applied.state

        # 2. digest exchange: predecessor's leaves arrive here
        prev_leaf = jax.lax.ppermute(st.leaf, AXIS, fwd)
        diff = prev_leaf != st.leaf
        n_diff = jnp.sum(diff.astype(jnp.int32))
        # differing buckets first, ascending index (truncation beyond
        # the frontier is healed by later steps; n_diff reports it)
        order = flagged_first_order(diff, frontier)
        want = jnp.where(diff[order], order.astype(jnp.int32), -1)

        # 3. frontier request travels backward to the predecessor
        asked = jax.lax.ppermute(want, AXIS, bwd)

        # 4. predecessor gathers its rows; slice travels forward
        sl_local = extract_rows(st, asked)
        sl = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, AXIS, fwd), sl_local
        )
        res = merge_rows(st, sl)
        root = tree_from_leaves(res.state.leaf)[0][0]
        ok = applied.ok & res.ok
        flags = jnp.stack([~applied.ok, res.need_gid_grow, res.need_fill_grow])
        return _unsqueeze(res.state), root[None], ok[None], n_diff[None], flags[None]

    return shard_map(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec, spec),
        out_specs=(spec, spec, spec, spec, spec),
        check_vma=False,
    )(stacked, self_slot, rows, op, key, valh, ts)


def gossip_delta_drive(
    mesh: Mesh,
    stacked: BinnedStore,
    self_slot: jnp.ndarray,
    rows: jnp.ndarray,
    op: jnp.ndarray,
    key: jnp.ndarray,
    valh: jnp.ndarray,
    ts: jnp.ndarray,
    frontier: int = 64,
    on_grow=None,
    gather=None,
):
    """Host recovery loop around :func:`gossip_delta_step`: a failed step
    (any ``ok=False``) discards that step's states, grows the offending
    tier on the PRE-step states, and replays — mutation batches re-apply
    idempotently because the failed result was never kept. Growth policy:
    gid table ×2, bin capacity ×2 after one compact; each retier
    recompiles the step for the new shapes.

    ``gather`` (multi-controller meshes): a callable returning the FULL
    ``oks``/``flags`` arrays as host values — e.g.
    ``partial(multihost_utils.process_allgather, tiled=True)``. Without
    it ``np.asarray`` on a process-spanning array would fail; with it
    every controller sees identical values and takes the same
    grow/replay decisions, keeping the SPMD programs in lockstep.

    Returns ``(stacked, roots, n_diff, n_retiers)``.
    """
    import numpy as np

    retiers = 0
    while True:
        out, roots, oks, n_diff, flags = gossip_delta_step(
            mesh, stacked, self_slot, rows, op, key, valh, ts,
            frontier=frontier,
        )
        oks_h = np.asarray(gather(oks) if gather else oks)
        if bool(oks_h.all()):
            return out, roots, n_diff, retiers
        # gather flags only on the (rare) failure path — identical on
        # every controller, so the branch above stays in lockstep
        flags_h = np.asarray(gather(flags) if gather else flags)
        retiers += 1
        f = flags_h.any(axis=0)  # [3] any replica
        apply_fill, gid_grow, merge_fill = map(bool, f)
        if gid_grow:
            stacked = stacked.grow(replica_capacity=stacked.replica_capacity * 2)
            if on_grow:
                on_grow(stacked)
        if apply_fill or merge_fill:
            # no compact-first step: the row-granular merge reclaims holes
            # in-row, and row_apply counts dead slots as free — a fill
            # overflow is genuine, so go straight to growth
            stacked = stacked.grow(bin_capacity=stacked.bin_capacity * 2)
            if on_grow:
                on_grow(stacked)


@partial(jax.jit, static_argnames=("mesh",))
def gossip_train_step(
    mesh: Mesh,
    stacked: BinnedStore,
    self_slot: jnp.ndarray,  # int32[N]     each replica's own ctx slot
    rows: jnp.ndarray,  # int32[N, U]  bucket-grouped mutation batches
    op: jnp.ndarray,  # int32[N, U, M]   (see binned_map.group_batch)
    key: jnp.ndarray,  # uint64[N, U, M]
    valh: jnp.ndarray,  # uint32[N, U, M]
    ts: jnp.ndarray,  # int64[N, U, M]
):
    """One SPMD step: local mutation batch → ring ppermute → merge → roots.

    This is the framework's "training step" shape: per-device compute
    (row-local mutation kernels), one ICI collective (ppermute of the full
    state pytree), then shard-local lattice math. Returns the new stacked
    states, each replica's digest-tree root (uint32[N]) for convergence
    monitoring, and per-replica merge ``ok`` flags (bool[N]) — a False
    flag means that replica's merge overflowed a tier and its state for
    this step is invalid (callers must check; growth cannot happen
    inside the SPMD program).
    """
    n = mesh.devices.size
    perm = [(i, (i + 1) % n) for i in range(n)]
    spec = P(AXIS)

    def step(local, slot, rows_b, op_b, key_b, valh_b, ts_b):
        local = _squeeze(local)
        applied = row_apply(
            local, slot[0], rows_b[0], op_b[0], key_b[0], valh_b[0], ts_b[0]
        )
        received = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, AXIS, perm), applied.state
        )
        all_rows = jnp.arange(applied.state.num_buckets, dtype=jnp.int32)
        sl = extract_rows(received, all_rows)
        res = merge_rows(applied.state, sl)
        root = tree_from_leaves(res.state.leaf)[0][0]
        # ok folds the mutation batch's bin-capacity flag too: a dropped
        # insert (scatter mode='drop') must be as loud as a merge overflow
        ok = applied.ok & res.ok
        return _unsqueeze(res.state), root[None], ok[None]

    return shard_map(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )(stacked, self_slot, rows, op, key, valh, ts)


def snapshot_mesh(stacked: BinnedStore) -> dict:
    """Device→host image of a mesh-stacked replica set (the SPMD analog
    of the replica Storage snapshot, SURVEY §5.4): one gathered pytree of
    numpy arrays plus the engine layout tag, suitable for pickling. The
    gather crosses the ICI/host boundary once per column."""
    import dataclasses as _dc

    import numpy as np

    from delta_crdt_ex_tpu.runtime.storage import CURRENT_LAYOUT

    return {
        "layout": CURRENT_LAYOUT,
        "arrays": {
            f.name: np.asarray(getattr(stacked, f.name))
            for f in _dc.fields(BinnedStore)
        },
    }


def restore_mesh(snap: dict, mesh: Mesh) -> BinnedStore:
    """Re-place a :func:`snapshot_mesh` image onto a mesh (same replica
    count; the device set may differ — elasticity across restarts)."""
    from delta_crdt_ex_tpu.runtime.storage import require_layout

    require_layout(snap.get("layout", "<untagged>"), "mesh snapshot")
    arrays = snap["arrays"]
    n = arrays["key"].shape[0]
    if mesh.devices.size != n:
        raise ValueError(
            f"snapshot holds {n} replicas but the mesh has "
            f"{mesh.devices.size} devices"
        )
    stacked = BinnedStore(**{k: jnp.asarray(v) for k, v in arrays.items()})
    return jax.device_put(stacked, replica_sharding(mesh))
