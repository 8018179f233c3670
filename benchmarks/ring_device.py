"""Chip-resident 8-replica ring convergence — the device-side twin of
``ring_bench.py``.

The runtime ring bench drives 8 threaded replicas through the host
control plane, so on a TPU it measures per-op dispatch, not
the engine. This bench keeps the SAME workload shape (8 replicas in a
one-way ring, N keys written at replica 0, clock stops when every
replica's digest root agrees) but entirely device-resident: the ring is
a stacked state batch, one writes-included ``ring_gossip_round`` call
gossips every hop simultaneously, and convergence takes exactly N-1
rounds — the ``shard_map`` multi-chip path's cost model measured on one
chip (``parallel/batched_sync.py::ring_gossip_round``; reference analog
``bench/propagation.exs`` 8-replica ring).

Emits: rounds/sec, total convergence wall-clock, and per-round ms at
the BASELINE ring config (10k keys).

Run: ``python -m benchmarks.ring_device [N ...]``  (default 10000)
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from delta_crdt_ex_tpu.utils.devices import enable_compilation_cache

enable_compilation_cache()

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.ops.binned import tree_from_leaves
from delta_crdt_ex_tpu.parallel import ring_gossip_round, stack_states
from delta_crdt_ex_tpu.utils.synth import build_state
from benchmarks.common import emit, log

RING = 8
TREE_DEPTH = 12  # matches ring_bench's runtime geometry


def run(number: int) -> dict:
    L = 1 << TREE_DEPTH
    rng = np.random.default_rng(0)
    keys = rng.integers(1, 1 << 63, size=number, dtype=np.uint64)

    # replica 0 holds the N written keys; 1..7 start empty (same gids
    # per slot as the runtime ring would negotiate)
    bin_cap = 16
    while bin_cap * L < 4 * number:
        bin_cap *= 2
    writer, _ = build_state(11, keys, num_buckets=L, bin_capacity=bin_cap)
    empties = [
        BinnedStore.new(num_buckets=L, bin_capacity=bin_cap)
        for _ in range(RING - 1)
    ]
    stacked = stack_states([writer, *empties])
    jax.block_until_ready(stacked)

    roots_of = jax.jit(jax.vmap(lambda lf: tree_from_leaves(lf)[0][0]))

    # compile BOTH jitted programs outside the clock (the runtime
    # bench's warm phase analog) — a first-call trace inside the timed
    # loop would dominate a 7-round convergence
    res = ring_gossip_round(stacked)
    jax.block_until_ready(roots_of(res.state.leaf))

    stacked = stack_states([writer, *empties])  # fresh start for timing
    jax.block_until_ready(stacked)
    t0 = time.perf_counter()
    rounds = 0
    all_ok = True
    while rounds < 4 * RING:
        res = ring_gossip_round(stacked)
        stacked = res.state
        rounds += 1
        all_ok &= bool(np.asarray(res.ok).all())
        roots = np.asarray(roots_of(stacked.leaf))
        if bool((roots == roots[0]).all()):
            break
    jax.block_until_ready(stacked)
    conv_s = time.perf_counter() - t0
    if not all_ok:
        raise SystemExit("ring merge overflowed a tier")
    if rounds >= 4 * RING:
        raise SystemExit("ring did not converge within 4*RING rounds")

    log(
        f"device ring({RING}) {number} keys: {rounds} rounds, "
        f"{conv_s:.3f}s total, {conv_s / rounds * 1e3:.1f} ms/round "
        f"({rounds / conv_s:.1f} rounds/sec, incl. per-round root check)"
    )
    return {
        f"converge_s@{number}": round(conv_s, 3),
        f"rounds@{number}": rounds,
        f"ms_per_round@{number}": round(conv_s / rounds * 1e3, 2),
    }


def main(sizes=(10_000,)):
    results = {}
    for n in sizes:
        results.update(run(n))
    emit("ring_device", results)
    return results


if __name__ == "__main__":
    sizes = tuple(int(a) for a in sys.argv[1:]) or (10_000,)
    main(sizes)
