"""Replicas pinned to devices of one mesh: the device data plane.

Each replica's state lives on its own jax device; anti-entropy slices
between them are placed directly on the receiver's device
(`jax.device_put` — free on the same chip, ICI between chips) while the
control plane stays on host. On a CPU host this runs over virtual
devices; the same program on a TPU pod keeps slice bytes off the host
entirely.

Run: python examples/device_plane.py
(one replica per device JAX finds; on a CPU host,
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4
gives it four virtual devices)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import delta_crdt_ex_tpu as dc
from examples._util import wait_until

devices = jax.devices()
print(f"mesh devices: {devices}")

replicas = [
    dc.start_link(
        dc.AWLWWMap,
        name=f"shard-{i}",
        sync_interval=0.02,
        capacity=256,
        tree_depth=6,
        device=d,
    )
    for i, d in enumerate(devices)
]
for r in replicas:
    dc.set_neighbours(r, [p for p in replicas if p is not r])

# every replica writes its own keys; the device plane moves the slices
for i, r in enumerate(replicas):
    for k in range(10):
        dc.mutate_async(r, "add", [f"d{i}/k{k}", (i, k)])

want = {f"d{i}/k{k}": (i, k) for i in range(len(replicas)) for k in range(10)}
wait_until(lambda: all(dc.read(r) == want for r in replicas),
           "all-device convergence", timeout=60)
print(f"converged: {len(want)} keys on all {len(replicas)} devices")
for r in replicas:
    assert r.state.leaf.devices() == {r.device}, "state strayed off its device"
    r.stop()
print("states stayed pinned — device plane ok")
