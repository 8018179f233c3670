"""Multi-controller SPMD gossip: two PROCESSES, four virtual devices
each, one global 8-device mesh — ``gossip_delta_step``'s ppermutes cross
the process boundary through jax.distributed's backend (the DCN analog;
on real hardware the same program rides ICI within a pod and DCN across
hosts). This is the multi-host validation of SURVEY §5.8: the SPMD data
plane is not limited to one process's devices.
"""

import os
import socket
import subprocess
import sys
import time

import pytest

#: minimal reproduction of the capability the real test needs: two
#: jax.distributed processes running ONE global (cross-process) jitted
#: computation on the forced-CPU backend. Some jaxlib builds reject this
#: outright ("Multiprocess computations aren't implemented on the CPU
#: backend") — an environment property, not a code regression, so the
#: real test must skip (not fail) there.
PROBE = r"""
import sys
pid, nproc, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=nproc, process_id=pid)
import numpy as np
from jax.experimental import multihost_utils
# report the detected device/mesh shape FIRST: when the probe fails,
# the skip reason can then say what the environment actually offered
# (chip-window logs otherwise show a bare skip with no why)
shape = (
    f"platform={jax.default_backend()}"
    f" global_devices={len(jax.devices())}"
    f" local_devices={len(jax.local_devices())}"
    f" processes={jax.process_count()}"
)
print("PROBE_SHAPE", shape, flush=True)
assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4, shape
# a global computation spanning both processes' devices — the exact
# operation class the gossip drive's ppermutes need
out = multihost_utils.process_allgather(np.int32(pid), tiled=False)
assert sorted(np.asarray(out).ravel().tolist()) == list(range(nproc))
print("PROBE_OK", flush=True)
"""

WORKER = r"""
import dataclasses, os, sys
pid, nproc, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=nproc, process_id=pid)
import numpy as np
import jax.numpy as jnp
import jax.tree_util as tu
from jax.experimental import multihost_utils

import delta_crdt_ex_tpu  # enables x64
from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.ops.apply import OP_ADD
from delta_crdt_ex_tpu.parallel.mesh_gossip import (
    gossip_delta_drive, make_mesh, replica_sharding,
)

n = len(jax.devices())
assert n == 8, f"expected 8 global devices, got {n}"
assert len(jax.local_devices()) == 4, "each process contributes 4"
mesh = make_mesh()
sharding = replica_sharding(mesh)
L = 64

# identical host-side construction in every process; each process then
# contributes only its addressable shards
states = []
for i in range(n):
    st = BinnedStore.new(L, 8, 4)  # writer table undersized on purpose
    st = dataclasses.replace(st, ctx_gid=st.ctx_gid.at[0].set(jnp.uint64(100 + i)))
    states.append(st)
host = tu.tree_map(lambda *xs: np.stack([np.asarray(x) for x in xs]), *states)

def gput(x):
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

stacked = tu.tree_map(gput, host)
self_slot = gput(np.zeros(n, np.int32))

from functools import partial
from tests.test_parallel import grouped_mutations

gather = partial(multihost_utils.process_allgather, tiled=True)

def batches(ops_per_replica):
    # same wire shapes as the in-process mesh tests; re-place each array
    # as a global (process-spanning) sharded array
    return tuple(
        gput(np.asarray(a)) for a in grouped_mutations(n, L, ops_per_replica)
    )

# a multi-op wave per replica; the writer table starts at 4 slots (< n
# writers), so full gossip MUST grow it through gossip_delta_drive's
# grow-and-replay path — across the process boundary
grown = []
seed = batches(
    [[(OP_ADD, 1000 + 97 * i + j, i, 1 + i * 10 + j) for j in range(4)] for i in range(n)]
)
stacked, roots, n_diff, retiers = gossip_delta_drive(
    mesh, stacked, self_slot, *seed,
    gather=gather, on_grow=lambda st: grown.append(st.replica_capacity),
)

# heal with empty batches; the gathered per-step divergence must decay
# to zero (ring propagation: each step moves entries one hop)
empty = batches([[] for _ in range(n)])
decay = [int(np.asarray(gather(n_diff)).max())]
for _ in range(2 * n):
    stacked, roots, n_diff, retiers_h = gossip_delta_drive(
        mesh, stacked, self_slot, *empty,
        gather=gather, on_grow=lambda st: grown.append(st.replica_capacity),
    )
    retiers += retiers_h
    decay.append(int(np.asarray(gather(n_diff)).max()))
    if decay[-1] == 0:
        break

assert decay[0] > 0, f"seed wave produced no divergence: {decay}"
assert decay[-1] == 0, f"divergence left after ring heal: {decay}"
assert max(grown, default=0) >= n, (
    f"writer table never grew to mesh size across processes: {grown}"
)
roots_g = gather(roots)
assert (np.asarray(roots_g) == np.asarray(roots_g).ravel()[0]).all(), "roots diverged"
print(
    f"MULTIHOST_OK pid={pid} roots={np.asarray(roots_g).ravel()[0]} "
    f"decay={decay} grown={grown} retiers={retiers}",
    flush=True,
)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # substitute only the device-count flag; preserve ambient XLA flags
    import re

    flag = "xla_force_host_platform_device_count"
    flags = env.get("XLA_FLAGS", "")
    if flag in flags:
        flags = re.sub(rf"--{flag}=\d+", f"--{flag}=4", flags)
    else:
        flags = f"{flags} --{flag}=4".strip()
    env["XLA_FLAGS"] = flags
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_pair(script: str, timeout_s: float) -> list:
    """Spawn the two-process worker pair; returns [(rc, out, err), ...]."""
    coord = f"127.0.0.1:{_free_port()}"
    env = _worker_env()
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), "2", coord],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True,
        )
        for pid in range(2)
    ]
    try:
        deadline = time.monotonic() + timeout_s
        outs = []
        for p in procs:
            remaining = max(5.0, deadline - time.monotonic())
            out, err = p.communicate(timeout=remaining)
            outs.append((p.returncode, out, err))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


#: probe verdict cache: None = not yet probed, else (ok, reason)
_PROBE_RESULT: "tuple[bool, str] | None" = None


def _global_cpu_mesh_capability(tmp_path) -> "tuple[bool, str]":
    """Can this container run a cross-process global computation on the
    forced-CPU backend? Probed ONCE per session with a minimal two-
    process allgather; failures return the diagnostic line so the skip
    reason is honest about what the environment refused."""
    global _PROBE_RESULT
    if _PROBE_RESULT is None:
        script = tmp_path / "probe.py"
        script.write_text(PROBE)
        try:
            outs = _run_pair(script, timeout_s=120)
        except subprocess.TimeoutExpired:
            _PROBE_RESULT = (False, "capability probe timed out")
            return _PROBE_RESULT
        # detected device/mesh shape, whichever process reported one —
        # recorded into the skip reason so chip-window logs show WHAT
        # the environment offered, not just that the legs skipped
        shapes = {
            line.split("PROBE_SHAPE ", 1)[1]
            for _rc, out, _err in outs
            for line in out.splitlines()
            if line.startswith("PROBE_SHAPE ")
        }
        shape = "; ".join(sorted(shapes)) if shapes else "no device shape reported"
        bad = [(rc, err) for rc, out, err in outs if rc != 0 or "PROBE_OK" not in out]
        if bad:
            rc, err = bad[0]
            tail = err.strip().splitlines()[-1] if err.strip() else f"exit {rc}"
            _PROBE_RESULT = (False, f"{tail[-300:]} [detected: {shape}]")
        else:
            _PROBE_RESULT = (True, "")
    return _PROBE_RESULT


def test_two_process_global_mesh_gossip(tmp_path):
    ok, why = _global_cpu_mesh_capability(tmp_path)
    if not ok:
        pytest.skip(
            "container cannot form a two-process global CPU mesh "
            f"(probe: {why})"
        )
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    outs = _run_pair(script, timeout_s=240)
    for rc, out, err in outs:
        assert rc == 0 and "MULTIHOST_OK" in out, f"worker failed: {err[-3000:]}"
    # both controllers computed the same converged digest root
    roots = {o.split("roots=")[1].split()[0] for _, o, _ in outs}
    assert len(roots) == 1, roots
