"""Shared helpers for the runnable demos."""

import sys
import time


def wait_until(pred, what: str, timeout: float = 30.0) -> None:
    """Poll ``pred`` until true, or exit non-zero — a demo must never
    print success-shaped output for a run that failed to converge."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    sys.exit(f"FAILED: {what} did not happen within {timeout:.0f}s")
