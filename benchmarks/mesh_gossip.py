"""SPMD mesh-gossip benchmark: per-step latency of the bounded-divergence
ring (`gossip_delta_step`) on an N-device mesh.

No reference analog (the reference has no multi-device data plane); this
extends the measured matrix to the parallel layer. Runs on the virtual
CPU mesh (`JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8`)
or a real multi-chip mesh unchanged.

Run: ``python -m benchmarks.mesh_gossip``
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a CPU run needs virtual devices, or it measures a 1-device "ring"
    # (trivial steps, heal in 2). Must run before importing
    # benchmarks.common, whose compilation-cache setup initialises the
    # backend (the host device count parses only once); an explicit
    # XLA_FLAGS count is honoured
    from delta_crdt_ex_tpu.utils.devices import forced_device_count, force_cpu_devices

    _n = forced_device_count()
    force_cpu_devices(_n if _n is not None else 8)

from benchmarks.common import emit, log


def main():
    import jax
    import jax.numpy as jnp

    from delta_crdt_ex_tpu.models.binned import BinnedStore
    from delta_crdt_ex_tpu.models.binned_map import group_batch
    from delta_crdt_ex_tpu.ops.apply import OP_ADD, OP_PAD
    from delta_crdt_ex_tpu.parallel import (
        gossip_delta_step,
        make_mesh,
        place_states,
    )

    n = len(jax.devices())
    assert n > 1, (
        "mesh_gossip needs a multi-device mesh; got 1 device — set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N for CPU runs"
    )
    mesh = make_mesh()
    log(f"mesh: {n} devices ({jax.default_backend()})")

    # writer-table slots must cover every mesh writer (post-gossip each
    # state knows all n gids; this bench asserts tier flags, no auto-grow)
    L, B, R = 1 << 10, 32, max(8, n)
    states = []
    for i in range(n):
        st = BinnedStore.new(L, B, R)
        states.append(
            dataclasses.replace(st, ctx_gid=st.ctx_gid.at[0].set(jnp.uint64(100 + i)))
        )
    stacked = place_states(states, mesh)
    self_slot = jnp.zeros(n, jnp.int32)

    def batch_for(n_ops, seed, u, m):
        """Fixed (u, m) shape across seeds so the timing loop never
        recompiles (group shapes vary with bucket collisions)."""
        r2 = np.random.default_rng(seed)
        groups = []
        for i in range(n):
            keys = r2.integers(1, 1 << 63, size=n_ops, dtype=np.uint64)
            groups.append(
                group_batch(
                    L,
                    np.full(n_ops, OP_ADD, np.int32),
                    keys,
                    (keys & np.uint64(0xFFFF)).astype(np.uint32),
                    (seed * 100_000 + np.arange(n_ops) + 1).astype(np.int64),
                )
            )
        assert all(
            g.rows.shape[0] <= u and g.op.shape[1] <= m for g in groups
        ), "fixed batch shape too small for this seed"
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
        return tuple(map(jnp.asarray, (rows, op, key, valh, ts)))

    results = {}
    for n_ops in (16, 128):
        frontier = 256
        u, m = max(16, 2 * n_ops), 4
        # warm + compile
        stacked2, roots, oks, n_diff, _ = gossip_delta_step(
            mesh, stacked, self_slot, *batch_for(n_ops, 1, u, m), frontier=frontier
        )
        jax.block_until_ready(roots)
        assert bool(np.asarray(oks).all())
        iters = 8
        batches = [batch_for(n_ops, 2 + it, u, m) for it in range(iters)]
        t0 = time.perf_counter()
        st = stacked2
        all_oks = []
        for b in batches:
            st, roots, oks, n_diff, _ = gossip_delta_step(
                mesh, st, self_slot, *b, frontier=frontier
            )
            all_oks.append(oks)
        jax.block_until_ready(roots)
        dt = (time.perf_counter() - t0) / iters
        assert all(bool(np.asarray(o).all()) for o in all_oks), "tier overflow mid-timing"
        results[f"step_ms@{n_ops}ops"] = round(dt * 1e3, 2)
        log(f"{n_ops} ops/replica/step: {dt*1e3:.1f} ms/step")

    # ring-heal latency: steps until full convergence after one write wave
    st, roots, oks, n_diff, _ = gossip_delta_step(
        mesh, stacked, self_slot, *batch_for(64, 99, 128, 4), frontier=256
    )
    assert bool(np.asarray(oks).all()), "tier overflow on the write wave"
    empty = (
        jnp.full((n, 1), -1, jnp.int32),
        jnp.full((n, 1, 1), OP_PAD, jnp.int32),
        jnp.zeros((n, 1, 1), jnp.uint64),
        jnp.zeros((n, 1, 1), jnp.uint32),
        jnp.zeros((n, 1, 1), jnp.int64),
    )
    steps = 1
    while True:
        st, roots, oks, n_diff, _ = gossip_delta_step(
            mesh, st, self_slot, *empty, frontier=256
        )
        steps += 1
        assert bool(np.asarray(oks).all()), "tier overflow during heal"
        if int(np.asarray(n_diff).max()) == 0:
            break
        assert steps < 4 * n, "ring did not converge"
    rr = np.asarray(roots)
    assert (rr == rr[0]).all()
    results["heal_steps_64ops"] = steps
    log(f"ring heal after one 64-op wave: {steps} steps (n={n})")

    emit("mesh_gossip", results)


if __name__ == "__main__":
    main()
