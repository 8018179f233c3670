"""True cross-process sync: a replica in a child interpreter converges
with one in this process over the TCP transport — the closest analog of
the reference's multi-node distribution (SURVEY §4: the reference tests
distribution logically in one BEAM; we additionally cross a real process
boundary here).

Sync edges are one-way (the setter's data flows to the neighbour,
``delta_crdt.ex:84-95``), and the parent does not know the child's
ephemeral endpoint — so the child bootstraps membership *through the
CRDT*: it publishes its endpoint under a well-known key, and the parent
adds the reverse edge when it sees it (exactly how Horde builds cluster
membership on top of this library).
"""

import os
import subprocess
import sys
import time

from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.runtime.tcp_transport import TcpTransport

CHILD = r"""
import sys, time
import delta_crdt_ex_tpu  # enables x64
from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.runtime.tcp_transport import TcpTransport

parent_host, parent_port = sys.argv[1], int(sys.argv[2])
t = TcpTransport()
c = start_link(AWLWWMap, threaded=False, transport=t, name="child",
               capacity=64, tree_depth=6)
c.set_neighbours([("parent", (parent_host, parent_port))])
c.mutate("add", ["from_child", "hello"])
c.mutate("add", ["child_endpoint", list(t.endpoint)])  # membership via the CRDT
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    c.sync_to_all()
    t.pump()
    time.sleep(0.02)
    if c.read().get("from_parent") == "hi":
        print("CHILD_CONVERGED", flush=True)
        sys.exit(0)
sys.exit(3)
"""


def test_cross_process_convergence(tmp_path):
    t = TcpTransport()
    try:
        parent = start_link(
            AWLWWMap, threaded=False, transport=t, name="parent",
            capacity=64, tree_depth=6,
        )
        parent.mutate("add", ["from_parent", "hi"])
        host, port = t.endpoint

        script = tmp_path / "child.py"
        script.write_text(CHILD)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
        child = subprocess.Popen(
            [sys.executable, str(script), str(host), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True,
        )
        try:
            linked = False
            deadline = time.monotonic() + 90
            # keep serving sync rounds until the child process reports
            # ITS convergence and exits (stopping as soon as the parent
            # converges would starve the child of the reverse direction)
            while time.monotonic() < deadline and child.poll() is None:
                parent.sync_to_all()
                t.pump()
                time.sleep(0.02)
                if not linked:
                    got = parent.read()
                    if "child_endpoint" in got:
                        # reverse edge learned through the CRDT itself
                        ch_host, ch_port = got["child_endpoint"]
                        parent.set_neighbours([("child", (ch_host, ch_port))])
                        linked = True
            out, err = child.communicate(timeout=60)
            assert "CHILD_CONVERGED" in out, f"child failed: {err[-2000:]}"
            got = parent.read()
            assert got["from_child"] == "hello" and got["from_parent"] == "hi"
        finally:
            if child.poll() is None:
                child.kill()
    finally:
        t.close()
