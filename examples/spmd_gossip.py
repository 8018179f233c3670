"""Multi-chip SPMD gossip: one replica per device, bounded-divergence
ring anti-entropy over the mesh (ICI bytes ∝ divergence).

Each device applies its own mutation batch inside the SPMD program,
then `gossip_delta_step` exchanges leaf digests with its ring
neighbour, requests only the differing buckets, and joins the returned
slice shard-locally. N-1 steps converge an N-device ring.

Run: python examples/spmd_gossip.py
(one replica per device JAX finds; on a CPU host,
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
gives it eight virtual devices)
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.models.binned_map import BinnedAWLWWMap
from delta_crdt_ex_tpu.ops.apply import OP_ADD, OP_PAD
from delta_crdt_ex_tpu.parallel import (
    gossip_delta_drive,
    make_mesh,
    place_states,
    unstack_states,
)

n = len(jax.devices())
print(f"mesh of {n} devices: {jax.devices()}")
mesh = make_mesh()

import dataclasses

L, B, R = 64, 8, 8
states = []
for i in range(n):
    st = BinnedStore.new(L, B, R)
    states.append(
        dataclasses.replace(st, ctx_gid=st.ctx_gid.at[0].set(jnp.uint64(100 + i)))
    )
stacked = place_states(states, mesh)
self_slot = jnp.zeros(n, jnp.int32)

# each replica writes one distinct key inside the SPMD step
groups = [
    BinnedAWLWWMap.group_batch(
        L,
        np.array([OP_ADD], np.int32),
        np.array([1000 + i], np.uint64),
        np.array([7 * i], np.uint32),
        np.array([i + 1], np.int64),
    )
    for i in range(n)
]
u = max(g.rows.shape[0] for g in groups)
m = max(g.op.shape[1] for g in groups)
rows = np.full((n, u), -1, np.int32)
op = np.full((n, u, m), OP_PAD, np.int32)
key = np.zeros((n, u, m), np.uint64)
valh = np.zeros((n, u, m), np.uint32)
ts = np.zeros((n, u, m), np.int64)
for i, g in enumerate(groups):
    gu, gm = g.op.shape
    rows[i, :gu] = g.rows
    op[i, :gu, :gm] = g.op
    key[i, :gu, :gm] = g.key
    valh[i, :gu, :gm] = g.valh
    ts[i, :gu, :gm] = g.ts

batch = tuple(map(jnp.asarray, (rows, op, key, valh, ts)))
empty = tuple(
    jnp.asarray(x)
    for x in (np.full((n, 1), -1, np.int32), np.full((n, 1, 1), OP_PAD, np.int32),
              np.zeros((n, 1, 1), np.uint64), np.zeros((n, 1, 1), np.uint32),
              np.zeros((n, 1, 1), np.int64))
)

stacked, roots, n_diff, _ = gossip_delta_drive(mesh, stacked, self_slot, *batch)
print(f"step 1: differing buckets per hop = {np.asarray(n_diff).tolist()}")
for step in range(2, n + 1):
    stacked, roots, n_diff, _ = gossip_delta_drive(mesh, stacked, self_slot, *empty)
    print(f"step {step}: differing buckets per hop = {np.asarray(n_diff).tolist()}")

roots = np.asarray(roots)
assert (roots == roots[0]).all(), "roots must agree after a full ring pass"
want = {1000 + i: 7 * i for i in range(n)}
for i, st in enumerate(unstack_states(stacked)):
    rws = BinnedAWLWWMap.winner_rows(st, jnp.arange(st.num_buckets, dtype=jnp.int32))
    win = np.asarray(rws.win)
    got = {
        int(k): int(v)
        for k, v in zip(np.asarray(rws.key)[win], np.asarray(rws.valh)[win])
    }
    assert got == want, (i, got)
print(f"converged: all {n} replicas share digest root {roots[0]} and hold {len(want)} keys")
