"""Two in-process replicas, the reference README flow.

Run: python examples/quickstart.py
(runs on the default JAX backend; JAX_PLATFORMS=cpu runs it on the CPU)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from examples._util import wait_until


import delta_crdt_ex_tpu as dc

c1 = dc.start_link(dc.AWLWWMap, sync_interval=0.02)
c2 = dc.start_link(dc.AWLWWMap, sync_interval=0.02)
dc.set_neighbours(c1, [c2])
dc.set_neighbours(c2, [c1])

dc.mutate(c1, "add", ["CRDT", "is magic!"])
wait_until(lambda: dc.read(c2) == {"CRDT": "is magic!"}, "replica 2 convergence")
print("replica 2 sees:", dc.read(c2))
c1.stop()
c2.stop()
