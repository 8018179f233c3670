"""runtime/tracing.py coverage: ``annotate`` spans nest, re-enter and
close on exceptions. That the replica's spans reach a captured trace is
checked in ``tests/test_spans.py``."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from delta_crdt_ex_tpu.runtime import tracing


def test_annotate_is_a_reusable_span():
    with tracing.annotate("test.span"):
        x = jnp.arange(8).sum()
    assert int(x) == 28
    # nesting and re-entry both work (TraceAnnotation is per-use)
    with tracing.annotate("outer"), tracing.annotate("inner"):
        pass


def test_annotate_survives_exceptions():
    with pytest.raises(RuntimeError):
        with tracing.annotate("test.boom"):
            raise RuntimeError("boom")
