"""One run of one cell: set-up, the measured window, the checks.

Everything that belongs to one deployment, one traffic mix or one
per-layer metric lives in a file of its own that this module finds by
name: ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` (plus an optional ``metrics/<metric>.json`` of
data for it). The mix's ``driver`` names the module under ``drivers/``
that runs it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np

import jax

from benchmark import trace as tracemod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: every number ``correct`` compares counts answers or rows that
#: disagree with the reference: an exact comparison
LIMIT = 0


def correct(checks: dict) -> bool:
    return all(v <= LIMIT for v in checks.values())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` at the checkout's root (a fixed
    path, so every run of a checkout finds what the first compiled)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def cell_spec(workload: str, bench: "dict | None" = None) -> dict:
    """The cell's entry, configuration, mix and metrics, from
    ``BENCHMARK.json`` and the files it names."""
    bench = bench or read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    pick = lambda ms: [m for m in ms if workload in m.get("workloads", [workload])]
    return {
        "cell": cell,
        "config": read_json(ROOT, cfg["file"]),
        "traffic": read_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": pick(bench["end_to_end"]),
        "per_layer": pick(bench["per_layer"]),
    }


def load_module(*parts):
    """Import the benchmark file ``parts`` (relative to this directory)
    by its path."""
    path = os.path.join(HERE, *parts)
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# what the metric readers read


class Telemetry:
    """Records the program's ``SYNC_ROUND`` telemetry with the host time
    it arrived: ``(t, event, measurements, replica name)``."""

    EVENTS = ("SYNC_ROUND",)

    def __init__(self):
        from delta_crdt_ex_tpu.runtime import telemetry

        self._tm = telemetry
        self.records: list = []
        self._handlers = []
        for ev in self.EVENTS:
            h = lambda _e, meas, meta, ev=ev: self.records.append(
                (time.perf_counter(), ev, meas, meta.get("name"))
            )
            telemetry.attach(getattr(telemetry, ev), h)
            self._handlers.append((getattr(telemetry, ev), h))

    def close(self) -> None:
        for ev, h in self._handlers:
            self._tm.detach(ev, h)

    def select(self, event: str, names=None, t0=None, t1=None) -> list:
        return [
            (t, m, n) for t, e, m, n in self.records
            if e == event and (names is None or n in names)
            and (t0 is None or t >= t0) and (t1 is None or t <= t1)
        ]


class Run:
    """What one run leaves for the per-layer metric readers: the window
    ``[t0, t1]`` (perf_counter seconds), the telemetry, the replicas by
    role, the transfer ledger around the window, the reduced trace
    (``None`` without ``--trace 1``) and the chip's peaks."""

    def __init__(self, spec, peaks):
        self.spec, self.peaks = spec, peaks
        self.t0 = self.t1 = 0.0
        self.telemetry: "Telemetry | None" = None
        self.roles: dict = {}
        self.transfers = ({}, {})
        self.trace: "tracemod.Trace | None" = None

    def events(self, event: str, role: str) -> list:
        return self.telemetry.select(event, self.roles.get(role), self.t0, self.t1)

    def slices_merged(self, role: str) -> int:
        return len(self.events("SYNC_ROUND", role))

    def entries_merged(self, role: str) -> int:
        return sum(m["entries"] for _t, m, _n in self.events("SYNC_ROUND", role))

    def crossings(self) -> int:
        before, after = self.transfers
        return sum(v["count"] - before.get(k, {"count": 0})["count"] for k, v in after.items())


def read_metric(name: str, run: Run):
    """Load ``metrics/<name>.py`` and return what its ``read(run, data)``
    returns, ``data`` being ``metrics/<name>.json`` where it exists
    (``None``: nothing to read in this run)."""
    mod = load_module("metrics", name + ".py")
    data = os.path.join(HERE, "metrics", name + ".json")
    return mod.read(run, read_json(data) if os.path.exists(data) else None)


# ---------------------------------------------------------------------------
# the deployment


def make_data(cfg: dict, seed: int):
    """The loaded keys and values: ``keys`` distinct integers drawn from
    ``1..key_space`` by the seed, each with the value ``7 * key``."""
    rng = np.random.default_rng([seed, 0])
    keys = rng.permutation(cfg["key_space"])[: cfg["keys"]].astype(np.int64) + 1
    return keys, keys * 7


def start_replica(cfg: dict, transport, name: str, node_id: int):
    import delta_crdt_ex_tpu as dc

    return dc.start_link(
        dc.AWLWWMap, threaded=True, store=cfg["store"], transport=transport,
        name=name, node_id=node_id, **cfg["replica"],
    )


def load(rep, keys, values, batch: int) -> None:
    import delta_crdt_ex_tpu as dc

    for lo in range(0, len(keys), batch):
        dc.mutate_batch(rep, "add", [[int(k), int(v)] for k, v in
                                     zip(keys[lo:lo + batch], values[lo:lo + batch])],
                        timeout=600)


def digest_equal(a, b) -> bool:
    """The replicas' digest-tree leaves agree (the anti-entropy's own
    convergence test)."""
    return bool(np.array_equal(np.asarray(a.state.leaf), np.asarray(b.state.leaf)))


def wait_converged(reps: list, timeout: float) -> bool:
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if all(digest_equal(reps[0], r) for r in reps[1:]):
            return True
        time.sleep(0.02)
    return False


def restamp_on_receipt(transport, receivers: set, on: list) -> None:
    """The control: slices delivered to ``receivers`` while ``on[0]``
    have every live entry's stamp replaced by the time of receipt,
    which breaks LWW by timestamp."""
    import dataclasses

    import jax.numpy as jnp

    from delta_crdt_ex_tpu.runtime.sync import EntriesMsg

    send = transport.send

    def restamping(to, msg):
        if on[0] and to in receivers and isinstance(msg, EntriesMsg):
            a = dict(msg.arrays)
            now = time.time_ns()
            if isinstance(a["ts"], np.ndarray):
                a["ts"] = np.where(a["alive"], np.int64(now), a["ts"])
            else:
                a["ts"] = jnp.where(a["alive"], jnp.int64(now), a["ts"])
            msg = dataclasses.replace(msg, arrays=a)
        return send(to, msg)

    transport.send = restamping


def rows_missing_from(a: np.ndarray, b: np.ndarray) -> int:
    """Rows of the canonical winner table ``a`` that ``b`` lacks."""
    va = np.ascontiguousarray(a).view(np.dtype((np.void, a.dtype.itemsize * a.shape[1])))
    vb = np.ascontiguousarray(b).view(np.dtype((np.void, b.dtype.itemsize * b.shape[1])))
    return int((~np.isin(va, vb)).sum())


def device_memory_peak() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


# ---------------------------------------------------------------------------
# the cell


def run_cell(spec: dict, seed: int, seconds: float, trace_dir=None,
             control: bool = False, peaks=None) -> dict:
    """Set up, measure, check, through the mix's driver
    (``drivers/<driver>.py``, whose ``run`` takes the arguments here and
    a :class:`Run`). Returns the result's fields (without ``device``)
    plus the :class:`Run` under ``"run"``."""
    driver = load_module("drivers", spec["traffic"]["driver"] + ".py")
    run = Run(spec, peaks)
    run.telemetry = Telemetry()
    try:
        return driver.run(spec, seed, seconds, trace_dir, control, run)
    finally:
        run.telemetry.close()


def window(run, trace_dir, body) -> int:
    """Run ``body()`` as the measured window: compiles counted, the
    transfer ledger read around it, traced when ``trace_dir`` is set.
    Returns the compiles inside it."""
    from delta_crdt_ex_tpu.utils import jitcache, transfers

    c0 = jitcache.compile_counts()
    before = transfers.snapshot()
    if trace_dir is not None:
        with tracemod.capture(trace_dir):
            body()
    else:
        body()
    run.transfers = (before, transfers.snapshot())
    c1 = jitcache.compile_counts()
    grew = {k: n - c0.get(k, 0) for k, n in c1.items() if n != c0.get(k, 0)}
    compiles = sum(grew.values())
    log(f"compiles inside the window: {compiles} {grew}")
    print(f"compiles inside the window: {compiles} {grew}", flush=True)
    if trace_dir is not None:
        run.trace = tracemod.load(trace_dir)
    return compiles
