"""Device↔host transfer ledger (crdtlint v5, TRANSFER family runtime half).

The "device-resident data plane" campaign (ROADMAP) retires host
round-trips one measured step at a time. Before any retirement can be
*evidence* rather than vibes, every crossing needs to be counted: this
module is the jitcache-registry pattern applied to transfers — every
hot-path device↔host crossing goes through an **audited site**
(:func:`register` returns a :class:`TransferSite` handle whose
:meth:`~TransferSite.get`/:meth:`~TransferSite.put` wrap
``jax.device_get``/``jax.device_put``), and the ledger counts crossings
and bytes per site label. crdtlint TRANSFER001 makes any *raw* crossing
in a hot module red; TRANSFER002 cross-checks the declared site labels
against use (ghost labels, collisions) the way OBS001 cross-checks
declared telemetry events. Every audited ``get`` also opens a
``crdt.readback.<label>`` profiler span, so a trace shows how long the
host waited on each site as well as how often it crossed.

Zero overhead when observability is disabled: the hot path pays two
integer adds under an uncontended lock (negligible next to the transfer
it accounts); telemetry export is deferred to scrape time —
:func:`audit` emits ``TRANSFER`` events carrying ABSOLUTE per-site
totals only when a handler is attached, and the metrics bridge folds
them into ``crdt_transfers_total{site=...}`` /
``crdt_transfer_bytes_total{site=...}`` with idempotent gauge sets
(the ``crdt_jit_compiles_total`` precedent: monotone by construction,
hence the ``_total`` names despite the set-to-absolute primitive).

Site labels are registered once, at module import, and a label
collision from a **different** call site raises (the jitcache
name-collision guard): two sites silently merging counts would blind
every bench gate diffing ledger snapshots. Re-evaluating the same
``register`` statement (module reload) is idempotent.

Bench gates (`bench.py --ingest/--fleet/--mesh/--serve/--tree`) pin
steady-state per-round crossing deltas by diffing :func:`snapshot`
around their measured rounds, exactly like the zero-compile gates diff
``jitcache.compile_counts()``.
"""

from __future__ import annotations

import sys
import threading

import jax

_lock = threading.Lock()
#: site label -> TransferSite (insertion = module import order)
_sites: dict[str, "TransferSite"] = {}


class TransferSite:
    """One audited crossing site: a label, the registering call site,
    and the running (crossings, bytes) tally. Handles are module-level
    constants by convention — crdtlint TRANSFER001 recognises
    ``<handle>.get/.put`` as the audited crossing form."""

    __slots__ = ("label", "origin", "count", "bytes", "span")

    def __init__(self, label: str, origin: tuple) -> None:
        self.label = label
        self.origin = origin
        self.count = 0
        self.bytes = 0
        # the profiler span every get opens, so a trace shows the time
        # the host waited on this site (TraceAnnotation directly: the
        # runtime's tracing helper sits above utils in the import graph)
        self.span = "crdt.readback." + label

    def note(self, n_bytes: int, crossings: int = 1) -> None:
        """Manual accounting for a crossing the wrappers cannot wrap
        (e.g. an implicit operand transfer that is contractual)."""
        with _lock:
            self.count += crossings
            self.bytes += int(n_bytes)

    def get(self, value):
        """Audited ``jax.device_get``: one counted crossing for the
        whole pytree (host leaves pass through, like ``device_get``),
        inside a ``crdt.readback.<label>`` profiler span."""
        self.note(_nbytes(value))
        with jax.profiler.TraceAnnotation(self.span):
            return jax.device_get(value)

    def put(self, value, device=None):
        """Audited ``jax.device_put`` (``device`` may be a Device or a
        Sharding, forwarded verbatim; None = default placement)."""
        self.note(_nbytes(value))
        if device is None:
            return jax.device_put(value)
        return jax.device_put(value, device)


def _nbytes(value) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def register(label: str) -> TransferSite:
    """Register ``label`` and return its :class:`TransferSite` handle.

    The same label registered again from the SAME file:line returns the
    existing handle (module reload); from a DIFFERENT call site it
    raises — silently merging two sites' counts would corrupt every
    ledger delta a bench gate diffs (the jitcache collision guard)."""
    if not isinstance(label, str) or not label:
        raise ValueError(f"transfer site label must be a non-empty str, got {label!r}")
    frame = sys._getframe(1)
    origin = (frame.f_code.co_filename, frame.f_lineno)
    with _lock:
        prior = _sites.get(label)
        if prior is not None:
            if prior.origin != origin:
                raise ValueError(
                    f"transfers: site label {label!r} already registered at "
                    f"{prior.origin[0]}:{prior.origin[1]} — pick a unique "
                    f"label per call site (ledger counts must not merge)"
                )
            return prior
        site = _sites[label] = TransferSite(label, origin)
        return site


def audited_get(value, site: TransferSite):
    """Function form of :meth:`TransferSite.get` (call-through sugar
    for sites threaded as parameters)."""
    return site.get(value)


def audited_put(value, site: TransferSite, device=None):
    """Function form of :meth:`TransferSite.put`."""
    return site.put(value, device)


def snapshot() -> dict:
    """``{label: {"count": crossings, "bytes": bytes_moved}}`` for every
    registered site, in sorted label order — the ledger image bench
    gates diff and ``Replica.stats()``/``Fleet.stats()`` surface."""
    with _lock:
        return {
            label: {"count": s.count, "bytes": s.bytes}
            for label, s in sorted(_sites.items())
        }


def counts() -> dict:
    with _lock:
        return {label: s.count for label, s in sorted(_sites.items())}


def delta(before: dict, after: dict) -> dict:
    """Per-site ``{label: {"count": Δ, "bytes": Δ}}`` between two
    :func:`snapshot` images, zero-delta sites omitted."""
    out: dict = {}
    for label, cur in after.items():
        prev = before.get(label, {"count": 0, "bytes": 0})
        dc = cur["count"] - prev["count"]
        db = cur["bytes"] - prev["bytes"]
        if dc or db:
            out[label] = {"count": dc, "bytes": db}
    return out


def audit() -> dict:
    """Read every site's tally and emit ``TRANSFER`` telemetry carrying
    the ABSOLUTE per-site totals — the metrics bridge folds those into
    ``crdt_transfers_total{site=...}`` / ``crdt_transfer_bytes_total``
    with idempotent gauge sets, so a bridge attaching mid-process still
    exports true totals. The observability plane runs this as a
    scrape-time collector; with no handler attached it is a snapshot
    read and nothing more (zero-overhead-when-disabled). Returns the
    current snapshot."""
    # deferred import: utils sits below the runtime layer in the import
    # graph (runtime modules register their sites at import time), so a
    # top-level runtime import would cycle through runtime/__init__
    from delta_crdt_ex_tpu.runtime import telemetry

    snap = snapshot()
    if not telemetry.has_handlers(telemetry.TRANSFER):
        return snap
    for label, tally in snap.items():
        telemetry.execute(
            telemetry.TRANSFER,
            {"crossings": tally["count"], "bytes": tally["bytes"]},
            {"site": label},
        )
    return snap


def varz() -> dict:
    """``/varz`` source: the ledger's unified snapshot envelope."""
    return {"kind": "transfers", "stats": snapshot()}
