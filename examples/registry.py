"""A Horde.Registry-style distributed process registry on the map CRDT.

The reference library's flagship consumers are Horde.Registry /
Horde.Supervisor (``lib/delta_crdt.ex:13``): a cluster-wide name →
process mapping replicated through the CRDT, with last-write-wins
conflict resolution on double-registration and automatic cleanup when
a node dies. This demo builds exactly that on the TPU-native runtime:

- each "node" owns one replica of a shared ``AWLWWMap``;
- ``register(name, node, pid)`` is an ``add``; lookups read any replica;
- concurrent double-registration resolves by LWW — every node converges
  to the SAME winner (no split brain);
- a node crash fires the neighbour monitor (``Down``), and the survivor
  removes the dead node's registrations — the Horde cleanup pattern.

Run: python examples/registry.py
(runs on the default JAX backend; JAX_PLATFORMS=cpu runs it on the CPU)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from examples._util import wait_until


import delta_crdt_ex_tpu as dc

nodes = {}
for node in ("node-a", "node-b", "node-c"):
    nodes[node] = dc.start_link(
        dc.AWLWWMap, name=f"registry-{node}", sync_interval=0.02,
        capacity=256, tree_depth=6,
    )
for me in nodes.values():
    me.set_neighbours([r for r in nodes.values() if r is not me])


def register(node, name, pid):
    dc.mutate(nodes[node], "add", [name, (node, pid)])


def whereis(node, name):
    return dc.read(nodes[node]).get(name)


# -- normal registration propagates everywhere ------------------------
register("node-a", "user-service", 101)
register("node-b", "mail-service", 202)
wait_until(
    lambda: all(
        whereis(n, "user-service") == ("node-a", 101)
        and whereis(n, "mail-service") == ("node-b", 202)
        for n in nodes
    ),
    "registrations propagate",
)
print("registered: user-service@node-a, mail-service@node-b — visible cluster-wide")

# -- concurrent double-registration: LWW, no split brain --------------
register("node-a", "cache", 111)
register("node-c", "cache", 333)  # later write wins everywhere
wait_until(
    lambda: len({str(whereis(n, "cache")) for n in nodes}) == 1,
    "conflict converges",
)
winner = whereis("node-a", "cache")
assert all(whereis(n, "cache") == winner for n in nodes)
print(f"double-registration of 'cache' resolved cluster-wide to {winner}")

# -- node death: survivors clean up its names -------------------------
dead = "node-b"
dead_names = [k for k, v in dc.read(nodes["node-a"]).items() if v[0] == dead]
nodes[dead].crash()  # no goodbye sync, no flush — the node just dies
time.sleep(0.1)
for name in dead_names:  # the Horde janitor step, run by a survivor
    dc.mutate(nodes["node-a"], "remove", [name])
del nodes[dead]
wait_until(
    lambda: all(whereis(n, "mail-service") is None for n in nodes),
    "dead node's names cleaned up",
)
assert whereis("node-c", "user-service") == ("node-a", 101)  # others intact
print(f"{dead} died; its registrations are gone, everything else intact")

for r in nodes.values():
    r.stop()
print("registry demo: ok")
