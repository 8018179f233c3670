"""The ``join`` driver: empty replicas join a loaded one, one after
another, as nodes joining a cluster receive the whole map from a member.

The mix's file gives ``poll_s`` (how often the benchmark looks whether
the joiner has caught up) and ``stall_s`` (a joiner with no key after
that long has stalled). Rows merged in the window are the keys the
window's joiners hold once the window closes: each starts empty inside
the window, and the one still joining at the close is cut off from the
source and stopped, so at most the slice it is merging then lands after
the close.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness, reference
from benchmark.harness import log


def tier(n: int, floor: int = 8) -> int:
    """The program's wire tier: row counts round up in x4 steps."""
    c = floor
    while c < n:
        c *= 4
    return c


def blank(arrays: dict, rows: int, gids: np.ndarray) -> dict:
    """An entries body shaped like ``arrays`` but with ``rows`` bucket
    rows, every one padding (``-1``), so the merge drops them all, and
    the writer table ``gids``."""
    out = {"ctx_gid": gids}
    for c, a in arrays.items():
        a = np.asarray(a)
        if c == "rows":
            out[c] = np.full(rows, -1, a.dtype)
        elif c in ("ctx_rows", "ctx_lo"):
            out[c] = np.zeros((rows, len(gids)), a.dtype)
        elif c != "ctx_gid":
            out[c] = np.zeros((rows,) + a.shape[1:], a.dtype)
    return out


def writers(n: int, width: int) -> np.ndarray:
    """A writer table of ``width`` slots, the first ``n`` of them taken."""
    gids = np.zeros(width, np.uint64)
    gids[:n] = np.arange(1, n + 1, dtype=np.uint64) + np.uint64(2**62)
    return gids


def warm_slice_tiers(rep, arrays: dict, max_sync_size: int) -> list:
    """Compile every slice shape a join can dispatch, on ``rep``'s state
    (results dropped), and return the row tiers.

    A slice of n bucket rows is extracted and merged alone at
    ``tier(n)`` rows, n up to ``max_sync_size``, with the sender's whole
    writer table (``replica_capacity`` wide), or only its own writer when
    it pushes its own writes. The joiner's ingest merges up to
    ``rep.max_coalesce`` queued slices in one call, whose rows round up
    to the tier of their sum, and whose writer table is the union of the
    slices' writers plus one, rounded up to a power of two, up to the
    sender's width: every joiner the source has seen is a writer there.
    ``arrays`` is a real slice's body, for the entry columns' widths and
    types."""
    model, state = rep.model, rep.state
    width = int(state.ctx_gid.shape[0])
    top = tier(max_sync_size)
    singles = [t for t in (8 * 4 ** i for i in range(16)) if t <= top]
    groups = [t for t in (8 * 4 ** i for i in range(1, 16))
              if t <= tier(rep.max_coalesce * top)]
    for t in singles:
        jax.block_until_ready(model.extract_rows(state, jnp.full(t, -1, jnp.int32)))
        for w in (1, width):
            a = blank(arrays, t, writers(1, w))
            sl = model.RowSlice(**{c: jnp.asarray(a[c]) for c in model.RowSlice._fields})
            jax.block_until_ready(model.merge_rows_into(state, sl)[0])
    for t in groups:
        n = 1
        while 2 * n <= width:  # n writers: n + 1 rounded up to a power of two
            half = blank(arrays, t // 2, writers(n, width))
            jax.block_until_ready(model.merge_group_into(state, [half, half])[0])
            n *= 2
    return singles + groups


def run(spec, seed, seconds, trace_dir, control, run):
    import delta_crdt_ex_tpu as dc
    from delta_crdt_ex_tpu.runtime.sync import EntriesMsg
    from delta_crdt_ex_tpu.runtime.transport import LocalTransport

    cfg, mix = spec["config"], spec["traffic"]
    t_setup = time.perf_counter()
    keys, values = harness.make_data(cfg, seed)
    t = LocalTransport()
    on = [control]
    joiners: list = []
    receivers: set = set()
    if control:
        harness.restamp_on_receipt(t, receivers, on)
    sample: list = []  # one real slice body, for the warm-up of merge shapes
    send = t.send

    def keep_one(to, msg):
        if not sample and isinstance(msg, EntriesMsg) and isinstance(msg.arrays["key"], np.ndarray):
            sample.append(msg.arrays)
        return send(to, msg)

    t.send = keep_one
    src = harness.start_replica(cfg, t, "source", cfg["node_ids"][0])
    harness.load(src, keys, values, cfg["load_batch"])
    log(f"setup: loaded {len(keys)} keys ({time.perf_counter() - t_setup:.1f} s)")

    def spare():
        name = f"joiner{len(joiners)}"
        receivers.add(name)
        j = harness.start_replica(cfg, t, name, cfg["node_ids"][1] + len(joiners))
        joiners.append({"rep": j, "name": name, "start": None, "end": None})
        return joiners[-1]

    def begin(j):
        j["start"] = time.perf_counter()
        dc.set_neighbours(src, [j["rep"]])
        dc.set_neighbours(j["rep"], [src])

    def cut_off(j):
        """Stop ``j`` joining: the source forgets it, and its mailbox
        and event loop go."""
        dc.set_neighbours(src, [])
        dc.set_neighbours(j["rep"], [])
        j["rep"].stop()

    # warm-up: one whole join runs every program a join runs, then every
    # slice shape is compiled; that joiner is dropped, and the window's
    # all start empty
    warm = spare()
    begin(warm)
    # under the control a joiner never agrees with the source: give the
    # warm-up as long as a stall, then go on
    if not harness.wait_converged([src, warm["rep"]], mix["stall_s"] if control else 900) \
            and not control:
        raise RuntimeError("the warm-up joiner did not converge during set-up")
    cut_off(warm)
    receivers.discard(warm["name"])
    if not sample:
        raise RuntimeError("the warm-up join shipped no slice")
    tiers = warm_slice_tiers(warm["rep"], sample[0], cfg["replica"]["max_sync_size"])
    cur = spare()
    setup_s = time.perf_counter() - t_setup
    log(f"setup: warm-up done (slice row tiers {tiers}); set-up {setup_s:.1f} s")
    run.roles = {"joiners": receivers, "source": {"source"}}

    def body():
        nonlocal cur
        run.t0 = time.perf_counter()
        run.t1 = run.t0 + seconds
        begin(cur)
        nxt = spare()
        while time.perf_counter() < run.t1:
            time.sleep(max(min(mix["poll_s"], run.t1 - time.perf_counter()), 0))
            with jax.profiler.TraceAnnotation("bench.join_poll"):
                done = harness.digest_equal(src, cur["rep"])
            if done and time.perf_counter() < run.t1:
                with jax.profiler.TraceAnnotation("bench.join_switch"):
                    cur["end"] = time.perf_counter()
                    dc.set_neighbours(cur["rep"], [])
                    begin(nxt)
                    cur["rep"].stop()
                    cur, nxt = nxt, spare()
        cut_off(cur)

    compiles = harness.window(run, trace_dir, body)
    memory_peak = harness.device_memory_peak()
    active = [j for j in joiners[1:] if j["start"] is not None]

    # -- the reference: the loaded map -----------------------------------
    loaded = reference.loaded_map(keys, values)
    checks = {}
    got = src.read(timeout=600)
    checks["source_bad_keys"] = len(got.keys() ^ loaded.keys()) + sum(
        1 for k, v in got.items() if loaded.get(k) != v
    )
    src_rows, src_gids, src_ctx = src.canonical_state()
    rows = wrong = missing = foreign = stalled = canon_diff = 0
    for j in active:
        rep = j["rep"]
        view = rep.read(timeout=600)
        rows += len(view)
        wrong += sum(1 for k, v in view.items() if loaded.get(k) != v)
        jrows, gids, ctx = rep.canonical_state()
        foreign += harness.rows_missing_from(jrows, src_rows)
        if j["end"] is not None:
            missing += len(loaded.keys() - view.keys())
            canon_diff += 0 if (np.array_equal(jrows, src_rows) and np.array_equal(gids, src_gids)
                                and np.array_equal(ctx, src_ctx)) else 1
        if (j["end"] or run.t1) - j["start"] >= mix["stall_s"] and not view:
            stalled += 1
    checks.update({
        "joiner_wrong_values": wrong,
        "joiner_missing_keys": missing,
        "joiner_rows_not_in_source": foreign,
        "finished_joiners_canonical_differing": canon_diff,
        "stalled_joiners": stalled,
    })
    log(f"window: {len(active)} joiners, {sum(j['end'] is not None for j in active)} "
        f"finished, {rows} rows merged in {seconds} s")
    for r in [src] + [j["rep"] for j in joiners]:
        r.stop()
    return {
        "run": run, "setup_s": setup_s,
        "metrics": {"catchup_rows_per_s": rows / seconds},
        "checks": checks, "attempted": len(active), "failed": stalled,
        "compiles": compiles, "memory_peak_bytes": memory_peak,
    }
