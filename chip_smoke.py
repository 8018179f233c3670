"""Drive the replica path once on a TPU at real size and check it.

Run from the repository root:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the mesh fleet, on four chips

One chip: a 1M-key AWLWWMap (the README north-star map, ``tree_depth=14``)
is loaded into replica A, gossips to replica B over ``LocalTransport``,
takes overwrites and removes that propagate too, and is read back
through B's front door. Answers and ``canonical_state_bytes()`` are
checked against the pure-Python oracle (``utils/pyref.py``) run on the
same seeded ops. The same runs again with ``store="hash"``, whose point
reads go through the Pallas probe kernel, checked bit for bit against
its jnp reference. The Pallas digest-tree kernel is compared bit for bit
with the XLA fold at ``[64, 2**14]``.

``--four-chips``: a mesh-sharded fleet (``start_fleet(mesh=
fleet_mesh(4))``) against its ``mesh=None`` twin on the same seeded ops.
It passes on four virtual CPU devices; on a TPU v5e 2x2 its read check
has failed, for a cause not yet found (PERF.md).

Every phase prints its wall time; the last line is one JSON object
naming the device. Any failed check, or a host with no TPU, exits
non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

N_KEYS = 1_000_000
TREE_DEPTH = 14
#: table lanes per key: the binned store's 2M lanes are 128 per bucket;
#: the hash table rehashes x2 once its fullest probe window is 3/4 full,
#: which 1M keys in 2M lanes reach, so it starts at the 4M lanes its
#: growth policy would settle at
LANES_PER_KEY = {"binned": 2, "hash": 4}
#: writes after the load, each of overwrites and removes
CHURN = 4096
#: keys read back through the front door
SAMPLE = 4096
#: keys per ``mutate_batch`` call of the load
LOAD_BATCH = 1 << 16


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Times one phase and prints it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"[{self.name}] {time.perf_counter() - self.t0:.3f} s")


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# oracle


def oracle_apply(oracle, batch: list, node: str, ts0: int):
    """Apply one batch of ``(op, key, value)`` on distinct keys to the
    pure-Python oracle. Each op's delta comes from the oracle's own
    mutator on the pre-batch state; deltas on distinct keys join as a
    union, and the batch delta joins into the state once."""
    from delta_crdt_ex_tpu.utils.pyref import PyAWLWWMap

    base = oracle.dots.get(node, 0)
    dots, value = set(), {}
    for i, (op, key, val) in enumerate(batch):
        cur = PyAWLWWMap(
            dots={node: base + i},
            value={key: oracle.value[key]} if key in oracle.value else {},
        )
        d = cur.add(key, val, node, ts0 + i) if op == "add" else cur.remove(key)
        dots |= d.dots
        value.update(d.value)
    keys = {key for _op, key, _v in batch}
    return oracle.join(PyAWLWWMap(dots=dots, value=value), keys)


def check_canonical(rep, expect: dict, gid: int) -> None:
    """The canonical winner rows against the oracle's map: same keys,
    one writer, same value hashes."""
    from delta_crdt_ex_tpu.utils.hashing import key_hash64_batch, value_hash32_batch

    rows, _gids, _ctx = rep.canonical_state()
    keys = list(expect)
    kh = np.asarray(key_hash64_batch(keys), np.uint64)
    vh = np.asarray(value_hash32_batch([expect[k] for k in keys]), np.uint64)
    order = np.argsort(kh, kind="stable")
    require(np.array_equal(rows[:, 0], kh[order]), "canonical keys != oracle keys")
    require(bool((rows[:, 1] == np.uint64(gid)).all()), "canonical writer gid")
    require(np.array_equal(rows[:, 3], vh[order]), "canonical values != oracle")


# ---------------------------------------------------------------------------
# phases


def roots_phase(seed: int, depth: int = TREE_DEPTH, n: int = 64) -> None:
    """``batched_roots_pallas`` against the per-level XLA fold."""
    import jax
    import jax.numpy as jnp

    from delta_crdt_ex_tpu.ops.binned import tree_from_leaves
    from delta_crdt_ex_tpu.ops.pallas_tree import batched_roots_fn

    L = 1 << depth
    leaves = jnp.asarray(
        np.random.default_rng(seed).integers(0, 1 << 32, (n, L), dtype=np.uint32)
    )
    fn, tag = batched_roots_fn(L)
    require(tag == "pallas", f"batched_roots_fn chose {tag} on the chip")
    got = np.asarray(jax.jit(fn)(leaves))
    want = np.asarray(jax.jit(jax.vmap(lambda lf: tree_from_leaves(lf)[0][0]))(leaves))
    require(np.array_equal(got, want), "pallas roots != XLA fold")
    log(f"digest-tree roots [{n}, {L}]: batched_roots_fn's {tag} kernel "
        f"== XLA fold bit for bit")


def replica_phase(
    store: str, seed: int, n_keys: int = N_KEYS, depth: int = TREE_DEPTH,
    churn: int = CHURN, sample: int = SAMPLE, load_batch: int = LOAD_BATCH,
) -> None:
    """A replica of ``n_keys`` keys gossips to a peer B; both are checked
    against the oracle, and B is read through its front door."""
    import jax

    import delta_crdt_ex_tpu as dc
    from delta_crdt_ex_tpu.runtime.transport import LocalTransport
    from delta_crdt_ex_tpu.utils import jitcache
    from delta_crdt_ex_tpu.utils.pyref import PyAWLWWMap

    rng = np.random.default_rng([seed, int(store == "hash")])
    keys = rng.permutation(4 * n_keys)[:n_keys] + 1
    absent = -(np.arange(sample // 4) + 1)
    t = LocalTransport()
    mk = lambda name, node_id: dc.start_link(
        dc.AWLWWMap, threaded=False, store=store, transport=t, name=name,
        node_id=node_id, capacity=LANES_PER_KEY[store] * n_keys, tree_depth=depth,
    )
    a, b = mk(f"{store}-a", 1001), mk(f"{store}-b", 1002)
    dc.set_neighbours(a, [b])
    dc.set_neighbours(b, [a])
    oracle, ts = PyAWLWWMap(), 0

    def write(batch: list) -> None:
        nonlocal oracle, ts
        for f in ("add", "remove"):
            items = [[k, v] if f == "add" else [k] for op, k, v in batch if op == f]
            if items:
                dc.mutate_batch(a, f, items)
        oracle = oracle_apply(oracle, batch, "a", ts)
        ts += len(batch)

    def gossip() -> str:
        times = []
        while True:
            t0 = time.perf_counter()
            a.sync_to_all()
            b.sync_to_all()
            t.pump()
            times.append(time.perf_counter() - t0)
            if bool(jax.numpy.array_equal(a.state.leaf, b.state.leaf)):
                return (f"{len(times)} rounds (median {np.median(times):.3f} s, "
                        f"max {max(times):.3f} s)")
            require(len(times) < 10_000, "gossip did not converge")

    with Phase(f"{store}: load {n_keys} keys into A"):
        for lo in range(0, n_keys, load_batch):
            write([("add", int(k), int(k) * 7) for k in keys[lo : lo + load_batch]])
    with Phase(f"{store}: gossip A -> B"):
        log(f"{store}: converged in {gossip()}")
    touched = rng.choice(n_keys, 2 * churn, replace=False)
    over, gone = keys[touched[:churn]], keys[touched[churn:]]
    with Phase(f"{store}: {churn} overwrites + {churn} removes, propagate"):
        write([("add", int(k), f"v2-{k}") for k in over])
        write([("remove", int(k), None) for k in gone])
        log(f"{store}: converged in {gossip()}")
    expect = oracle.read()
    with Phase(f"{store}: {sample} point reads through {b.name}'s front door"):
        q = [int(k) for k in np.concatenate(
            [over[: sample // 4], gone[: sample // 4],
             np.setdiff1d(keys, np.concatenate([over, gone]))[: sample // 4],
             absent]
        )]
        got = dc.frontdoor(b).read_keys(q)
        require(got == {k: expect[k] for k in q if k in expect},
                "front-door reads != oracle")
    with Phase(f"{store}: full reads and canonical state vs oracle"):
        require(dc.read(b) == expect, "B's full read != oracle")
        require(a.canonical_state_bytes() == b.canonical_state_bytes(),
                "canonical state A != B")
        check_canonical(b, expect, 1001)
    if store == "hash":
        hash_point_read_check(b, q)
    for r in (a, b):
        h = r.health()
        require(h["ok"], f"{r.name} unhealthy: {h}")
    compiles = jitcache.compile_counts()
    probe = compiles.get("hash_probe_winners", 0)
    log(f"{store}: {len(expect)} live keys on both replicas == oracle; "
        f"point-read kernel: "
        + (f"pallas probe ({probe} compiles), jnp reference compiles "
           f"{compiles.get('hash_winners_for_keys', 0)}"
           if store == "hash" else "XLA winners_for_keys"))
    if store == "hash":
        require(probe > 0 and not compiles.get("hash_winners_for_keys"),
                "hash point reads did not run the Pallas probe kernel")
    a.stop()
    b.stop()


def hash_point_read_check(rep, q: list) -> None:
    """The Pallas probe kernel against the jnp reference on the replica's
    own table: found flags equal, winners equal bit for bit where found."""
    import jax
    import jax.numpy as jnp

    from delta_crdt_ex_tpu.ops import hash_map
    from delta_crdt_ex_tpu.utils.hashing import key_hash64_batch

    kh = jnp.asarray(np.asarray(key_hash64_batch(q), np.uint64))
    got = jax.jit(hash_map.probe_winners)(rep.state, kh)
    ref = jax.jit(hash_map.winners_for_keys)(rep.state, kh)
    found = np.asarray(ref.found)
    require(np.array_equal(np.asarray(got.found), found), "probe found flags")
    for col in ("gid", "ctr", "valh", "ts"):
        require(
            np.array_equal(np.asarray(getattr(got, col))[found],
                           np.asarray(getattr(ref, col))[found]),
            f"probe {col} != jnp reference",
        )
    log(f"hash: pallas probe == jnp reference on {len(q)} keys "
        f"({int(found.sum())} found)")


def relabelled_canonical(rep, gid_map: dict) -> bytes:
    """``canonical_state_bytes()`` with every writer gid renamed through
    ``gid_map`` and re-sorted as the replica sorts it. Two fleets started
    apart draw their own random node ids; renamed, their canonical
    states must agree."""
    rows, gids, ctx = rep.canonical_state()
    rename = lambda col: np.array([gid_map[int(x)] for x in col], np.uint64)
    rows = rows.copy()
    rows[:, 1] = rename(rows[:, 1])
    rows = rows[np.lexsort(rows.T[::-1])]
    gids = rename(gids)
    order = np.argsort(gids, kind="stable")
    return rows.tobytes() + gids[order].tobytes() + ctx[:, order].tobytes()


def read_mismatch(got: dict, expect: dict) -> str:
    """How a read differs from the expected map ("" when it does not)."""
    missing = sorted(expect.keys() - got.keys())
    extra = sorted(got.keys() - expect.keys())
    changed = sorted(k for k in expect.keys() & got.keys() if got[k] != expect[k])
    if not (missing or extra or changed):
        return ""
    return (f"{len(got)} keys read, {len(missing)} missing {missing[:3]}, "
            f"{len(extra)} extra {extra[:3]}, {len(changed)} changed {changed[:3]}")


def lane_devices(fleet) -> set:
    """Devices holding the members' fleet-resident state lanes."""
    devices = set()
    for r in fleet.replicas:
        if r._fleet_src is None:  # materialised solo copy, not a lane
            continue
        stacked, lane = r._fleet_src
        for shard in stacked.leaf.addressable_shards:
            rows = range(*shard.index[0].indices(stacked.leaf.shape[0]))
            if lane in rows:
                devices.add(shard.device)
    return devices


def four_chip_phase(
    seed: int, n: int = 8, keys_per_member: int = 4096, depth: int = 10,
    shards: int = 4,
) -> None:
    """A mesh-sharded fleet against its vmap twin on the same ops."""
    import delta_crdt_ex_tpu as dc
    from delta_crdt_ex_tpu.runtime.clock import LogicalClock
    from delta_crdt_ex_tpu.runtime.transport import LocalTransport
    from delta_crdt_ex_tpu.utils.devices import fleet_mesh

    rng = np.random.default_rng(seed)
    keys = rng.permutation(8 * n * keys_per_member)[: n * keys_per_member] + 1
    mine = lambda i: keys[i * keys_per_member : (i + 1) * keys_per_member]

    def fleet(mesh):
        f = dc.start_fleet(
            n, dc.AWLWWMap, threaded=False, mesh=mesh,
            transport=LocalTransport(), clock=LogicalClock(),
            names=[f"member{i}" for i in range(n)],
            capacity=4 * keys_per_member, tree_depth=depth,
        )
        for i, r in enumerate(f.replicas):
            dc.set_neighbours(r, [f.replicas[(i + 1) % n], f.replicas[(i + 2) % n]])
        return f

    mesh, twin = fleet(fleet_mesh(shards)), fleet(None)
    placed = set()
    with Phase(f"four chips: {n} members x {keys_per_member} keys, mesh and twin"):
        for f in (mesh, twin):
            for i, r in enumerate(f.replicas):
                dc.mutate_batch(r, "add", [[int(k), int(k)] for k in mine(i)])
                dc.mutate_batch(r, "remove", [[int(k)] for k in mine(i)[::16]])
        for rounds in range(1, 200):
            for f in (mesh, twin):
                f.sync_tick()
                f.drain()
            placed |= lane_devices(mesh)
            if all(
                len({r.canonical_state_bytes() for r in f.replicas}) == 1
                for f in (mesh, twin)
            ):
                break
        else:
            raise SystemExit("chip_smoke: FAILED: mesh fleet did not converge")
    expect = {
        int(k): int(k)
        for k in np.setdiff1d(keys, np.concatenate([mine(i)[::16] for i in range(n)]))
    }
    gid_map = {
        m.node_id: t.node_id for m, t in zip(mesh.replicas, twin.replicas)
    }
    wrong = [
        f"{tag} {r.name}: {w}"
        for tag, f in (("mesh", mesh), ("twin", twin))
        for r in f.replicas
        if (w := read_mismatch(dc.read(r), expect))
    ]
    require(not wrong, "reads != expected map: " + "; ".join(wrong))
    for m, t in zip(mesh.replicas, twin.replicas):
        require(relabelled_canonical(m, gid_map)
                == t.canonical_state_bytes(),
                f"{m.name}: mesh canonical state != vmap twin")
    ms = mesh.stats()["mesh"]
    log(f"four chips: converged in {rounds} sync ticks; mesh == vmap twin "
        f"canonical state on all {n} members ({len(expect)} keys)")
    log(f"four chips: member lanes on {len(placed)} devices {sorted(map(str, placed))}; "
        f"intra_entries {ms['intra_entries']}, fallback_entries "
        f"{ms['fallback_entries']}, permuted_bytes {ms['permuted_bytes']}")
    require(len(placed) == shards, f"member states on {len(placed)} devices, not {shards}")
    require(ms["intra_entries"] > 0, "no entries rode the mesh plane")
    for f in (mesh, twin):
        f.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-fleet phase, on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend: {devices[0].platform}); "
              "this smoke test runs on the chip only", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from delta_crdt_ex_tpu import native
    from delta_crdt_ex_tpu.utils.devices import enable_compilation_cache

    log(f"device: {len(devices)} x {devices[0].device_kind}")
    log(f"compile cache: {enable_compilation_cache()}")
    log(f"native hashing loaded: {native.available()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        with Phase("roots kernel"):
            roots_phase(args.seed)
        replica_phase("binned", args.seed)
        replica_phase("hash", args.seed)
    log(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
