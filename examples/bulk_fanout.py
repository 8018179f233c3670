"""Bulk fan-in on the promoted packed layout: one device call merges a
delta slice into a whole stack of neighbour replica states.

This is the north-star bench shape (`bench.py`) exposed as a library
path: stack the neighbour states, `pack_states` them into the packed
entry layout (chip A/B 2026-07-31: 2.10× over the column layout), and
`fanout_merge_into` joins the slice into every neighbour in one vmapped
call — with the shared tier-escalation ladder handling capacity growth.
The reference loops neighbours one message at a time
(``causal_crdt.ex:264-283``); here the neighbour axis is a batch axis.

This demo speaks the kernel vocabulary (uint64 key hashes / uint32
value hashes, like `bench.py`); the replica runtime (`start_link`)
wraps the same kernels for arbitrary Python keys and values.

Run: python examples/bulk_fanout.py
(runs on the default JAX backend; JAX_PLATFORMS=cpu runs it on the CPU)
"""

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax.numpy as jnp
import numpy as np

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.models.binned_map import BinnedAWLWWMap
from delta_crdt_ex_tpu.ops.apply import OP_ADD
from delta_crdt_ex_tpu.ops.binned import extract_rows
from delta_crdt_ex_tpu.ops.packed import unpack
from delta_crdt_ex_tpu.parallel import (
    fanout_merge_into,
    pack_states,
    stack_states,
    unstack_states,
)

N_NEIGHBOURS = 16
L = 256  # digest-tree leaves / hash buckets


def fresh_state(gid: int) -> BinnedStore:
    """Empty lattice with this writer's gid in context slot 0."""
    st = BinnedStore.new(num_buckets=L, bin_capacity=16, replica_capacity=4)
    return dataclasses.replace(st, ctx_gid=st.ctx_gid.at[0].set(jnp.uint64(gid)))


def apply_adds(state: BinnedStore, keys: np.ndarray, vals: np.ndarray, t0: int):
    """Local mutation batch through the bucket-grouped row kernel."""
    n = len(keys)
    g = BinnedAWLWWMap.group_batch(
        state.num_buckets,
        np.full(n, OP_ADD, np.int32),
        keys.astype(np.uint64),
        vals.astype(np.uint32),
        np.arange(t0, t0 + n, dtype=np.int64),
    )
    res = BinnedAWLWWMap.row_apply(
        state, 0, g.rows, g.op, g.key, g.valh, g.ts
    )
    if not bool(res.ok):  # no retry path at this level; fail loudly
        raise SystemExit("row_apply overflowed its bin tier")
    return res.state


def main():
    rng = np.random.default_rng(0)
    # a writer replica produces a delta; 16 neighbours each hold their
    # own prior state (different gids — the per-neighbour remap is real)
    writer = apply_adds(
        fresh_state(999),
        rng.integers(1, 1 << 63, size=64, dtype=np.uint64),
        np.arange(64), t0=100,
    )
    neighbours = [
        apply_adds(
            fresh_state(100 + i),
            rng.integers(1, 1 << 63, size=4, dtype=np.uint64),
            np.arange(4), t0=1,
        )
        for i in range(N_NEIGHBOURS)
    ]

    # ship the writer's rows as one slice, fan it into all neighbours
    sl = extract_rows(writer, jnp.arange(L, dtype=jnp.int32))
    stacked = pack_states(stack_states(neighbours))
    t0 = time.perf_counter()
    stacked, res, retries = fanout_merge_into(stacked, sl, kill_budget=16)
    dt = time.perf_counter() - t0
    # fanout_merge_into only returns on all-ok (the tier ladder retries
    # or raises otherwise) — no post-check needed here

    outs = unstack_states(unpack(stacked))
    dots = sorted({int(st.alive.sum()) for st in outs})
    print(f"fanned 1 slice into {N_NEIGHBOURS} neighbours in one call: "
          f"{dt*1e3:.1f} ms (compile included), {retries} tier retries, "
          f"every neighbour now holds {dots} live dots (64 merged + 4 local)")


if __name__ == "__main__":
    main()
