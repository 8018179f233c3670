"""Auxiliary subsystems: interval checkpointing, telemetry, gc."""

import time

from delta_crdt_ex_tpu import AWLWWMap, MemoryStorage
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.runtime import telemetry


def mk(transport, clock, **opts):
    opts.setdefault("capacity", 64)
    opts.setdefault("tree_depth", 6)
    return start_link(AWLWWMap, threaded=False, transport=transport, clock=clock, **opts)


def test_telemetry_sync_done_counts(transport, shared_clock):
    events = []
    telemetry.attach(telemetry.SYNC_DONE, lambda e, m, md: events.append((m, md)))
    try:
        c = mk(transport, shared_clock, name="telem")
        c.mutate("add", ["a", 1])
        c.mutate("add", ["a", 1])  # same value, NEW dot: internal change
        c.mutate("remove", ["missing"])  # no internal change
        counts = [m["keys_updated_count"] for m, md in events if md["name"] == "telem"]
        assert counts == [1, 1, 0]
    finally:
        telemetry.detach(telemetry.SYNC_DONE, events.append)


def test_interval_checkpointing_rehydrates(transport, shared_clock):
    store = MemoryStorage()
    c = start_link(
        AWLWWMap,
        transport=transport,
        clock=shared_clock,
        name="ickpt",
        storage_module=store,
        storage_mode="interval",
        checkpoint_interval=0.05,
        sync_interval=0.02,
        capacity=64,
        tree_depth=6,
    )
    c.mutate("add", ["k", "v"])
    deadline = time.monotonic() + 5
    while store.read("ickpt") is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert store.read("ickpt") is not None, "interval checkpoint never fired"
    c.stop()

    c2 = mk(transport, shared_clock, name="ickpt", storage_module=store)
    assert c2.read() == {"k": "v"}


def test_gc_prunes_dead_payloads(transport, shared_clock):
    c = mk(transport, shared_clock)
    for i in range(10):
        c.mutate("add", [f"k{i}", i])
    for i in range(5):
        c.mutate("remove", [f"k{i}"])
    assert len(c._payloads) >= 10  # dead dots still held
    c.gc()
    assert len(c._payloads) == 5
    assert len(c._key_terms) == 5
    assert c.read() == {f"k{i}": i for i in range(5, 10)}

def test_file_storage_rehydrates_across_processes(tmp_path, transport, shared_clock):
    """FileStorage survives a full process loss (unlike MemoryStorage):
    a fresh replica with the same name rehydrates node id and state from
    disk (reference crash-rehydrate contract, causal_crdt_test.exs:87-102)."""
    from delta_crdt_ex_tpu.api import start_link
    from delta_crdt_ex_tpu.runtime.storage import FileStorage

    store = FileStorage(str(tmp_path))
    c = start_link(
        threaded=False, transport=transport, clock=shared_clock,
        storage_module=store, name="file_store", capacity=64, tree_depth=6,
    )
    c.mutate("add", [("tuple", "key"), {"v": 1}])
    c.mutate("add", ["k2", b"bytes"])
    node_id = c.node_id
    c.transport.unregister(c.addr)  # crash — no terminate sync

    store2 = FileStorage(str(tmp_path))  # fresh handle, same directory
    c2 = start_link(
        threaded=False, transport=transport, clock=shared_clock,
        storage_module=store2, name="file_store", capacity=64, tree_depth=6,
    )
    assert c2.node_id == node_id  # dot-namespace continuity
    assert c2.read() == {("tuple", "key"): {"v": 1}, "k2": b"bytes"}
    # dot continuity holds: new writes keep converging with a peer
    c3 = start_link(
        threaded=False, transport=transport, clock=shared_clock,
        capacity=64, tree_depth=6,
    )
    c2.set_neighbours([c3])
    c2.mutate("add", ["k3", 3])
    for _ in range(4):
        c2.sync_to_all()
        transport.pump()
    assert c3.read() == c2.read()


def test_rehydrate_rejects_foreign_layout(transport, shared_clock):
    """A snapshot written by a different engine layout must fail with a
    descriptive error, not an opaque KeyError (ADVICE r1)."""
    import dataclasses

    import pytest

    store = MemoryStorage()
    c = mk(transport, shared_clock, name="laytag", storage_module=store)
    c.mutate("add", ["k", "v"])
    snap = store.read("laytag")
    assert snap.layout == "binned-v2"
    c.stop()
    c.transport.unregister("laytag")
    store.write("laytag", dataclasses.replace(snap, layout="flat-v0"))
    with pytest.raises(ValueError, match="engine layout"):
        mk(transport, shared_clock, name="laytag", storage_module=store)

    # the real legacy case: a snapshot pickled BEFORE the tag existed has
    # no 'layout' in its instance dict, and unpickling falls back to the
    # dataclass default — the guard must read __dict__, not getattr
    untagged = dataclasses.replace(snap)
    del untagged.__dict__["layout"]
    store.write("laytag", untagged)
    with pytest.raises(ValueError, match="engine layout"):
        mk(transport, shared_clock, name="laytag", storage_module=store)
