"""Bucket-binned dot-store — the TPU-native state layout of the lattice.

The reference stores a replica as a 2-level nested map
``%{key => %{{value, ts} => MapSet(dots)}}`` plus a causal context
(``aw_lww_map.ex:2-3``) and pays O(log n) per *touched* key on merges.
The first tensorisation here (a flat entry heap, ``models/state.py``)
preserved the semantics but made every merge O(capacity): joining a
512-entry delta into a 1M-key state re-masked and re-scattered the whole
heap. This layout restores the reference's O(touched) cost model while
staying fully static-shaped for XLA:

    entries live in dense rows ``[L, B]`` — row = 64-bit key-hash bucket
    (== leaf of the sync-index digest tree), B slots per bucket.

Because an entry's row is a pure function of its key, every operation
reduces to *row-local* work over a gathered row subset — dense gathers,
vector math along the bin axis, and small element scatters; no
full-state pass, no large scatter-adds (TPU scatters serialize; gathers
and dense reductions don't).

Columns (all device-resident):

    key   : uint64[L, B]   64-bit key hash (host keeps hash → term)
    valh  : uint32[L, B]   value content digest
    ts    : int64[L, B]    LWW timestamp
    node  : int32[L, B]    writer replica as LOCAL slot into ctx tables
    ctr   : uint32[L, B]   dot counter (dot = (gid_of(node), ctr))
    alive : bool[L, B]     slot occupancy
    ehash : uint32[L, B]   maintained entry content hash (digest term)

Maintained summaries (the O(delta) machinery):

    fill : int32[L]        per-row append pointer (alive ⊆ [0, fill))
    amin : uint32[L, R]    min ctr among ALIVE entries per (bucket,
                           writer-slot); U32_MAX when none. A remote
                           context interval can only kill here if it
                           reaches this minimum — the O(R) kill-pruning
                           test that lets merges skip un-killable rows.
    amax : uint32[L, R]    max ctr among ALIVE entries per (bucket,
                           writer-slot); 0 when none. The other half of
                           the pruning test: an interval ``(lo, hi]``
                           with ``lo >= amax`` cannot kill either (all
                           alive dots predate the claim).
    leaf : uint32[L]       leaf digests, updated incrementally (the
                           ``MerkleMap.put`` analog, ``causal_crdt.ex:
                           390-394``): wrapping sum of alive ehash.

Causal context, exactly as before (compressed per-replica max, decomposed
per bucket so partial syncs stay bucket-atomic — see ``models/state.py``
for why that strengthens the reference):

    ctx_gid : uint64[R]    slot → global replica id (0 = empty)
    ctx_max : uint32[L, R] per-bucket per-replica max observed counter

L is fixed per cluster (it is the sync-index depth: ``L = 2**tree_depth``).
B and R are power-of-two tiers; kernels signal overflow via ``ok`` flags
and the host grows the tier and retries (the only data-dependent control
flow, and it lives on the host).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

U32_MAX = jnp.uint32(0xFFFFFFFF)


def pow2_tier(n: int, floor: int = 1) -> int:
    """Round up to the power-of-two capacity tier (tiers bound kernel
    recompiles; every static shape in the engine comes through here)."""
    c = floor
    while c < n:
        c *= 2
    return c


def pow4_tier(n: int, floor: int = 8) -> int:
    """Round up in ×4 steps — the WIRE tier. Sync slices vary per message
    (row count, alive count), and every distinct tier combination is a
    fresh jit compile, so wire shapes trade up to 4× padding for ~half
    the tier count (profiled: the 2-replica convergence bench spent 18.8s
    of 24.4s in 15 tier compiles before this)."""
    c = floor
    while c < n:
        c *= 4
    return c


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "key", "valh", "ts", "node", "ctr", "alive", "ehash",
        "fill", "amin", "amax", "leaf", "ctx_gid", "ctx_max",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class BinnedStore:
    key: jax.Array  # uint64[L, B]
    valh: jax.Array  # uint32[L, B]
    ts: jax.Array  # int64[L, B]
    node: jax.Array  # int32[L, B]
    ctr: jax.Array  # uint32[L, B]
    alive: jax.Array  # bool[L, B]
    ehash: jax.Array  # uint32[L, B]
    fill: jax.Array  # int32[L]
    amin: jax.Array  # uint32[L, R]
    amax: jax.Array  # uint32[L, R]
    leaf: jax.Array  # uint32[L]
    ctx_gid: jax.Array  # uint64[R]
    ctx_max: jax.Array  # uint32[L, R]

    @property
    def num_buckets(self) -> int:
        return self.key.shape[-2]

    @property
    def bin_capacity(self) -> int:
        return self.key.shape[-1]

    @property
    def capacity(self) -> int:
        return self.key.shape[-2] * self.key.shape[-1]

    @property
    def replica_capacity(self) -> int:
        return self.ctx_gid.shape[-1]

    @staticmethod
    def new(
        num_buckets: int = 64, bin_capacity: int = 16, replica_capacity: int = 8
    ) -> "BinnedStore":
        """Empty lattice state (reference ``AWLWWMap.new/0`` +
        ``compress_dots``, ``causal_crdt.ex:72``)."""
        L, B, R = num_buckets, bin_capacity, replica_capacity
        return BinnedStore(
            key=jnp.zeros((L, B), jnp.uint64),
            valh=jnp.zeros((L, B), jnp.uint32),
            ts=jnp.zeros((L, B), jnp.int64),
            node=jnp.zeros((L, B), jnp.int32),
            ctr=jnp.zeros((L, B), jnp.uint32),
            alive=jnp.zeros((L, B), bool),
            ehash=jnp.zeros((L, B), jnp.uint32),
            fill=jnp.zeros(L, jnp.int32),
            amin=jnp.full((L, R), U32_MAX, jnp.uint32),
            amax=jnp.zeros((L, R), jnp.uint32),
            leaf=jnp.zeros(L, jnp.uint32),
            ctx_gid=jnp.zeros(R, jnp.uint64),
            ctx_max=jnp.zeros((L, R), jnp.uint32),
        )

    def grow(
        self, bin_capacity: int | None = None, replica_capacity: int | None = None
    ) -> "BinnedStore":
        """Pad to a larger tier. L never changes (it is the cluster-agreed
        sync-index geometry); rows and context tables pad with dead slots.
        Rank-agnostic: works on a single state ([L, B] columns) and on a
        neighbour-stacked state ([N, L, B]) alike — padding is always on
        the last axis."""
        b_new = bin_capacity or self.bin_capacity
        r_new = replica_capacity or self.replica_capacity
        db = b_new - self.bin_capacity
        dr = r_new - self.replica_capacity
        assert db >= 0 and dr >= 0
        last = lambda a, d, **kw: jnp.pad(
            a, ((0, 0),) * (a.ndim - 1) + ((0, d),), **kw
        )
        padb = lambda a: last(a, db) if db else a
        return BinnedStore(
            key=padb(self.key),
            valh=padb(self.valh),
            ts=padb(self.ts),
            node=padb(self.node),
            ctr=padb(self.ctr),
            alive=padb(self.alive),
            ehash=padb(self.ehash),
            fill=self.fill,
            amin=last(self.amin, dr, constant_values=U32_MAX) if dr else self.amin,
            amax=last(self.amax, dr) if dr else self.amax,
            leaf=self.leaf,
            ctx_gid=last(self.ctx_gid, dr) if dr else self.ctx_gid,
            ctx_max=last(self.ctx_max, dr) if dr else self.ctx_max,
        )

    def entry_gid(self) -> jax.Array:
        """uint64[L, B]: global replica id of each entry's writer."""
        return self.ctx_gid[self.node]

    def global_ctx(self) -> jax.Array:
        """uint32[R]: the reference's global compressed context view."""
        return jnp.max(self.ctx_max, axis=0)

    def own_counter(self, slot) -> jax.Array:
        """uint32: highest dot counter this replica has issued."""
        return jnp.max(self.ctx_max[:, slot])

    def num_alive(self) -> jax.Array:
        return jnp.sum(self.alive.astype(jnp.int32))

    def bucket_of(self, key: jax.Array) -> jax.Array:
        return (key & jnp.uint64(self.num_buckets - 1)).astype(jnp.int32)
