"""Compile-cache audit (ISSUE 12): the runtime cross-check of the
SHAPE family's static discipline.

The named-jit registry must count one compile per distinct operand
geometry, surface those counts through telemetry → the metrics bridge
(``crdt_jit_compiles_total{name=...}``), and — THE gate — a fleet
driven through mixed-occupancy tick cycles must compile each entry
root at most once per distinct bucket geometry, with **zero**
steady-state compiles once the tier vocabulary is warm. If the padding
discipline regressed (SHAPE001's subject), this is the test that
watches it happen at runtime.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.models.binned import pow2_tier
from delta_crdt_ex_tpu.runtime import telemetry
from delta_crdt_ex_tpu.runtime.clock import LogicalClock
from delta_crdt_ex_tpu.runtime.fleet import Fleet
from delta_crdt_ex_tpu.runtime.transport import LocalTransport
from delta_crdt_ex_tpu.utils import jitcache
from tests.test_ingest_coalesce import entries_only


def test_cache_size_probe_supported():
    """The audit leans on the jitted callable's tracing-cache counter —
    if a jax upgrade drops it, this fails loudly instead of letting the
    bench gates go vacuously green."""
    assert jitcache.supported()


def test_named_jit_counts_one_compile_per_geometry():
    jitted = jitcache.named_jit(lambda x: x + 1, name="probe_add_one")
    jitted(jnp.zeros(4))
    jitted(jnp.zeros(4))  # warm: same geometry, no new executable
    assert jitcache.compile_counts()["probe_add_one"] == 1
    jitted(jnp.zeros(8))
    assert jitcache.compile_counts()["probe_add_one"] == 2


def test_audit_emits_jit_compile_telemetry():
    jitted = jitcache.named_jit(lambda x: x * 2, name="probe_double")
    jitted(jnp.zeros(2))
    seen: list = []
    handler = lambda _e, meas, meta: seen.append((meta["name"], meas["compiles"]))
    telemetry.attach(telemetry.JIT_COMPILE, handler)
    try:
        jitcache.audit()
        assert ("probe_double", 1) in seen
        # absolute counts, re-published every audit: any plane's gauge
        # set is idempotent, and a bridge attaching mid-process still
        # exports the true totals
        seen.clear()
        jitcache.audit()
        assert ("probe_double", 1) in seen
        # a new geometry moves the published absolute count
        jitted(jnp.zeros(16))
        seen.clear()
        jitcache.audit()
        assert ("probe_double", 2) in seen
    finally:
        telemetry.detach(telemetry.JIT_COMPILE, handler)


def test_runtime_roots_are_registered():
    """The hot entry roots created through named_jit at import time —
    the audit is useless if the kernel modules bypass it."""
    counts = jitcache.compile_counts()
    for root in ("merge_rows", "row_apply", "fleet_merge_rows",
                 "stack_pytrees", "tree_from_leaves"):
        assert root in counts, root


def _mk(transport, clock, **kw):
    kw.setdefault("sync_timeout", 600.0)
    return start_link(
        AWLWWMap, threaded=False, transport=transport, clock=clock,
        capacity=64, tree_depth=6, **kw,
    )


def test_fleet_mixed_occupancy_compiles_bounded():
    """THE runtime gate: a fleet driven through mixed-occupancy tick
    cycles (occupancies 5/3/2 → pow2 lane tiers 8/4/2) compiles
    ``fleet_merge_rows`` at most once per distinct bucket geometry, and
    a warm fleet re-running the same occupancy pattern compiles NOTHING
    — the dynamic mirror of SHAPE001's static discipline."""
    transport = LocalTransport()
    clock = LogicalClock()
    n = 5
    senders = [_mk(transport, clock, name=f"jc_s{i}") for i in range(n)]
    fleet = Fleet([
        _mk(transport, clock, name=f"jc_f{i}", node_id=4000 + i)
        for i in range(n)
    ])
    for i, s in enumerate(senders):
        s.set_neighbours([fleet.replicas[i]])

    base = jitcache.compile_counts()
    occupancies = [5, 3, 2]

    def cycle(m: int, wave: int) -> None:
        # the same keys per occupancy each wave: one bucket geometry
        # per occupancy by construction
        for i in range(m):
            for j in range(2):
                senders[i].mutate("add", [1000 * i + j, wave])
            senders[i].sync_to_all()
        for i in range(m):
            entries_only(transport, fleet.replicas[i].addr)
        fleet.drain()
        for s in senders:
            transport.drain(s.addr)  # walk back-traffic: not the subject

    # warmup: two full patterns populate the tier vocabulary (first
    # contact may retier writer tables; the second pass is warm)
    for wave in range(2):
        for m in occupancies:
            cycle(m, wave)

    warm = jitcache.compile_counts()
    st = fleet.stats()
    tiers = {pow2_tier(occ, floor=2) for occ in st["occupancy_hist"]}
    compiled = warm.get("fleet_merge_rows", 0) - base.get("fleet_merge_rows", 0)
    assert compiled >= 1, "the fleet never batched — the gate saw nothing"
    assert compiled <= len(tiers), (
        f"fleet_merge_rows compiled {compiled}x for {len(tiers)} distinct "
        f"bucket lane tiers {sorted(tiers)} — padding discipline regressed "
        f"(occupancy hist {st['occupancy_hist']})"
    )

    # steady state: the same pattern again compiles ZERO new executables
    # across EVERY named root
    for m in occupancies:
        cycle(m, 2)
    steady = jitcache.compile_counts()
    moved = {
        k: (warm.get(k, 0), v)
        for k, v in steady.items()
        if v != warm.get(k, 0)
    }
    assert moved == {}, f"steady-state XLA compiles after warmup: {moved}"


def test_varz_snapshot_shape():
    doc = jitcache.varz()
    assert doc["kind"] == "jitcache"
    assert isinstance(doc["stats"]["compiles"], dict)


def test_register_rejects_name_collision():
    """Silently evicting an earlier root on a name collision would
    blind the audit (and the bench zero-compile gates) to whichever
    object keeps being dispatched — a collision with a DIFFERENT
    callable must raise; re-registering the same object is idempotent."""
    j = jitcache.named_jit(lambda x: x - 1, name="probe_collide")
    jitcache.register("probe_collide", j)  # same object: fine
    with pytest.raises(ValueError, match="probe_collide"):
        jitcache.named_jit(lambda x: x - 2, name="probe_collide")


@pytest.mark.parametrize("env_dir", [True, False])
def test_compilation_cache_placement(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` places the persistent cache and the
    helper sets nothing over it; without it the cache is the repo's
    fixed ``.jax_cache``. Checked in a fresh process, since JAX reads
    the variable when it is imported."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from delta_crdt_ex_tpu.utils.devices import enable_compilation_cache\n"
        "path = enable_compilation_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == path, path\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n"
        "print(path)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    path = out.stdout.strip().splitlines()[-1]
    if env_dir:
        assert path == str(tmp_path)
        assert any(name.startswith("jit_") for name in os.listdir(tmp_path))
    else:
        assert path == os.path.join(repo, ".jax_cache")
