"""Artifact freshness classification (stdlib only): ``benchmarks.common``
uses it for ``load_partial`` to resume a killed multi-cell run.
"""

from __future__ import annotations

import datetime
import json


def artifact_status(path: str, max_age_s: float = 43200, with_data: bool = False):
    """Classify a results artifact: ``missing`` (absent/unreadable),
    ``stale`` (emitted outside the freshness window), ``partial``
    (fresh, mid-run checkpoint), or ``fresh`` (fresh and complete).
    With ``with_data=True`` returns ``(status, dict | None)`` from ONE
    read of the file, so callers never re-open it (the artifact can be
    atomically replaced between reads by a concurrent run)."""
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return ("missing", None) if with_data else "missing"

    def done(status):
        return (status, d) if with_data else status

    try:
        t = datetime.datetime.fromisoformat(d["utc"])
        if t.tzinfo is None:
            t = t.replace(tzinfo=datetime.timezone.utc)
        age = (datetime.datetime.now(datetime.timezone.utc) - t).total_seconds()
    except (KeyError, TypeError, ValueError):
        return done("stale")
    if not (0 <= age < max_age_s):
        return done("stale")
    return done("partial" if d.get("partial") else "fresh")


if __name__ == "__main__":
    import sys

    print(artifact_status(sys.argv[1]))
