"""A two-"node" cluster over real sockets, with persistence and a diff
feed — the capabilities a reference user reaches for in production:
`{name, node}`-style remote addressing, `on_diffs` change feed,
`storage_module` crash recovery.

Run: python examples/tcp_cluster.py
(runs on the default JAX backend; JAX_PLATFORMS=cpu runs it on the CPU)
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from examples._util import wait_until


import delta_crdt_ex_tpu as dc
from delta_crdt_ex_tpu.runtime.storage import FileStorage
from delta_crdt_ex_tpu.runtime.tcp_transport import TcpTransport

node_a, node_b = TcpTransport(), TcpTransport()
state_dir = tempfile.mkdtemp(prefix="crdt-demo-")

changes = []
a = dc.start_link(
    dc.AWLWWMap,
    transport=node_a,
    name="users",
    sync_interval=0.02,
    storage_module=FileStorage(state_dir),
)
b = dc.start_link(
    dc.AWLWWMap,
    transport=node_b,
    name="users",
    sync_interval=0.02,
    on_diffs=changes.append,
)
# one-way edges, set symmetrically — {name, (host, port)} addressing
a.set_neighbours([node_b.remote_addr("users")])
b.set_neighbours([node_a.remote_addr("users")])

dc.mutate(a, "add", ["alice", {"role": "admin"}])
dc.mutate(a, "add", ["bob", {"role": "dev"}])

# a remove only kills OBSERVED entries (observed-remove semantics, same
# as the reference): wait until node B has seen bob before removing him
wait_until(lambda: dc.read(b).get("bob") is not None, "bob reaching node B")
dc.mutate(b, "remove", ["bob"])

want = {"alice": {"role": "admin"}}
wait_until(lambda: dc.read(a) == dc.read(b) == want, "remove propagating")
print("node A:", dc.read(a))
print("node B:", dc.read(b))
print("diff feed at B:", changes)

# crash node A (no clean stop) and rehydrate from disk: same node id,
# same state, sync continues
node_a.close()
node_a2 = TcpTransport()
a2 = dc.start_link(
    dc.AWLWWMap,
    transport=node_a2,
    name="users",
    sync_interval=0.02,
    storage_module=FileStorage(state_dir),
)
a2.set_neighbours([node_b.remote_addr("users")])
b.set_neighbours([node_a2.remote_addr("users")])
dc.mutate(a2, "add", ["carol", {"role": "ops"}])
wait_until(lambda: dc.read(b).get("carol") is not None, "post-rehydrate sync")
print("after crash+rehydrate, node B:", dc.read(b))
for r in (a2, b):
    r.stop()
node_a2.close()
node_b.close()
