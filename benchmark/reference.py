"""The plain reference the benchmark's ``correct`` is decided against.

It imports nothing of the program under test and takes nothing the
program made: :class:`AWLWWMap` is a pure-Python AWLWWMap (add-wins by
observed remove, LWW by timestamp; a dot store over a compressed causal
context, as in delta_crdt_ex's ``aw_lww_map.ex``), applied one op at a
time at the node that takes it. The join cell's source replica holds
exactly its map after the load.
"""

from __future__ import annotations

from typing import Any, Hashable

# ---------------------------------------------------------------------------
# AWLWWMap


class AWLWWMap:
    """One replica's state: ``entries[key]`` maps each live dot
    ``(node, counter)`` of the key to its ``(value, ts)``; ``ctx`` is the
    causal context, the highest counter seen per node."""

    def __init__(self):
        self.entries: dict[Hashable, dict[tuple, tuple]] = {}
        self.ctx: dict[Any, int] = {}

    def add(self, key, value, node, ts) -> None:
        """An add at ``node``: a fresh dot replaces every dot of the key
        the node has observed (add-wins)."""
        ctr = self.ctx.get(node, 0) + 1
        self.ctx[node] = ctr
        self.entries[key] = {(node, ctr): (value, ts)}

    def read(self) -> dict:
        """Each key's value with the highest stamp (LWW)."""
        return {k: max(dots.values(), key=lambda vt: vt[1])[0]
                for k, dots in self.entries.items()}


def loaded_map(keys, values, node: str = "loader") -> dict:
    """The map a replica holds after adding ``keys[i] -> values[i]`` in
    order."""
    m = AWLWWMap()
    for ts, (k, v) in enumerate(zip(keys.tolist(), values.tolist()), 1):
        m.add(k, v, node, ts)
    return m.read()
