"""Test harness config.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths
(`parallel/`) are exercised without TPU hardware. The platform is pinned
through ``jax.config.update`` as well as the env var, so the pin holds
even where jax was imported before this file runs.
"""

from delta_crdt_ex_tpu.utils.devices import enable_compilation_cache, force_cpu_devices

force_cpu_devices(8)
enable_compilation_cache()

import pytest  # noqa: E402

from delta_crdt_ex_tpu.runtime.clock import LogicalClock  # noqa: E402
from delta_crdt_ex_tpu.runtime.storage import MemoryStorage  # noqa: E402
from delta_crdt_ex_tpu.runtime.transport import LocalTransport  # noqa: E402


@pytest.fixture
def transport():
    return LocalTransport()


@pytest.fixture(autouse=True)
def _clean_memory_storage():
    yield
    MemoryStorage.clear()


@pytest.fixture
def shared_clock():
    """One logical clock shared by all replicas in a test: global LWW order
    is then deterministic (ts strictly increases across the whole test)."""
    return LogicalClock()


def converge(transport, replicas, rounds: int = 6):
    """Deterministic convergence driver: repeated full sync rounds with
    message pumping — the "sync now / quiesce" hook SURVEY §4 calls for
    instead of the reference's flaky ``Process.sleep`` waits."""
    for _ in range(rounds):
        for r in replicas:
            r.sync_to_all()
        transport.pump()
