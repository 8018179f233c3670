"""Named host spans (SURVEY §5.1).

:func:`annotate` opens a ``jax.profiler.TraceAnnotation``: a span on
the profiler's host plane, on the same clock as the device's
operations, and a no-op cost when no trace is being captured. The
replica names its spans ``crdt.<step>`` (its loop's ``crdt.sync_tick``
and ``crdt.drain``, the anti-entropy steps, the merges), and every
audited readback of the transfer ledger opens ``crdt.readback.<site>``
(``utils/transfers.py``), so a trace puts each stretch in which the
device waits down to the host step that held it.
"""

from __future__ import annotations

import jax


def annotate(name: str):
    """Named span visible in device traces (no-op cost when not tracing)."""
    return jax.profiler.TraceAnnotation(name)
