"""crdtlint: golden fixtures per rule family + end-to-end over the real
package.

The fixture tests build throwaway mini-packages on disk and assert each
rule family fires on its positive snippet and stays silent on the
negative one. The end-to-end tests run the real CLI over
``delta_crdt_ex_tpu`` (must be clean: zero unsuppressed findings) and —
via the engine's source overlay — re-lint mutated copies of real
modules to prove the pass actually *detects* the bug classes it claims
to (every ``with self._lock:`` deletion in replica.py, an unannotated
``.item()`` in ops/join.py), not just that the tree happens to be
quiet.

Pure-stdlib under test: no jax/numpy import happens in the linter, so
these tests are cheap enough for tier-1.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.crdtlint.engine import (  # noqa: E402
    Finding,
    load_baseline,
    run_lint,
    write_baseline,
)

PKG = "delta_crdt_ex_tpu"


def make_pkg(root: Path, modules: dict[str, str]) -> Path:
    """Write a mini-package; keys are slash paths under the package dir
    (e.g. "ops/kern.py"), values module source."""
    pkg = root / "fixpkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in modules.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        init = path.parent / "__init__.py"
        if not init.exists():
            init.write_text("")
        path.write_text(textwrap.dedent(src))
    return pkg


def lint(pkg: Path, **kw) -> list[Finding]:
    new, _baselined, _allowed = run_lint([pkg], **kw)
    return new


def rules_of(findings: list[Finding]) -> set[str]:
    return {f.rule for f in findings}


# ----------------------------------------------------------------------
# LOCK001 — lock discipline


LOCKED_CLASS = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._stop = threading.Event()

        def put(self, x):
            with self._lock:
                self._items.append(x)

        def {body}
"""


def test_lock_unguarded_public_read_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {"box.py": LOCKED_CLASS.format(body="size(self):\n            return len(self._items)")},
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK001"}
    assert "_items" in found[0].message and "size" in found[0].message


def test_lock_guarded_access_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": LOCKED_CLASS.format(
                body=(
                    "size(self):\n"
                    "            with self._lock:\n"
                    "                return len(self._items)"
                )
            )
        },
    )
    assert lint(pkg) == []


_HELPER_CLASS = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def put(self, x):
            with self._lock:
                self._put(x)

        def _put(self, x):
            self._items.append(x)
"""


def test_lock_private_helper_inherits_caller_lock(tmp_path):
    # a private helper called only under the lock is clean; the same
    # helper reached from a lock-free public path is flagged
    pkg = make_pkg(tmp_path, {"box.py": _HELPER_CLASS})
    assert lint(pkg) == []

    dirty = _HELPER_CLASS + (
        "\n"
        "        def put_fast(self, x):\n"
        "            self._put(x)\n"
    )
    pkg2 = make_pkg(tmp_path / "b", {"box.py": dirty})
    found = lint(pkg2)
    assert rules_of(found) == {"LOCK001"}


def test_lock_thread_entry_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                    self._thread = threading.Thread(target=self._loop)

                def bump(self):
                    with self._lock:
                        self._n += 1

                def _loop(self):
                    while True:
                        print(self._n)
            """
        },
    )
    found = lint(pkg)
    # the thread-entry read is both a discipline violation (LOCK001:
    # guarded attr, unguarded path) and an actual race (RACE001: caller
    # writes, thread reads, no common lock) — both families fire
    assert rules_of(found) == {"LOCK001", "RACE001"}
    assert any(f.rule == "LOCK001" and "_loop" in f.message for f in found)


def test_lock_acquire_wrapper_recognised(tmp_path):
    # Replica's _acquire idiom: helper acquires, caller releases
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._items = []

                def _acquire(self):
                    if not self._lock.acquire(timeout=1):
                        raise TimeoutError

                def put(self, x):
                    self._acquire()
                    try:
                        self._items.append(x)
                    finally:
                        self._lock.release()
            """
        },
    )
    assert lint(pkg) == []


def test_lock_threadsafe_attrs_exempt(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import queue
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()
                    self._wake = threading.Event()
                    self._data = {}

                def put(self, x):
                    with self._lock:
                        self._data[x] = x
                        self._q.put(x)

                def poke(self):
                    self._q.put_nowait(None)
                    self._wake.set()
            """
        },
    )
    assert lint(pkg) == []


def test_lock_init_does_not_mint_guards(tmp_path):
    # attributes only ever written in __init__ are pre-publication state
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._name = "box"
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)

                def name(self):
                    return self._name
            """
        },
    )
    assert lint(pkg) == []


# ----------------------------------------------------------------------
# SYNC001 / SYNC002 — host-sync leaks


def test_sync_item_in_jit_reachable_cross_module(tmp_path):
    # entry registered in one module, offending body in another: the
    # rule must walk the import graph, not the file it found jit() in
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            def combine(x, y):
                return x + y

            def fold(x):
                bad = combine(x, x).item()
                return bad
            """,
            "models/model.py": """
            import jax

            from fixpkg.ops import kern

            jit_fold = jax.jit(kern.fold)
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SYNC001"}
    assert found[0].path.endswith("ops/kern.py")


def test_sync_unreachable_function_not_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            def helper(x):
                return x.tolist()
            """,
        },
    )
    assert lint(pkg) == []


def test_sync_int_coercion_flagged_static_shape_exempt(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            import jax

            @jax.jit
            def fold(x):
                n = int(x.shape[0])      # static: fine
                v = int(x.sum())         # traced: host sync
                return n + v
            """,
        },
    )
    found = lint(pkg)
    assert len(found) == 1 and found[0].rule == "SYNC001"
    assert "int()" in found[0].message


def test_sync_np_asarray_and_decorated_partial_jit(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "parallel/mesh.py": """
            from functools import partial

            import jax
            import numpy as np

            @partial(jax.jit, static_argnames=("k",))
            def step(x, k=1):
                return np.asarray(x) + k
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SYNC001"}
    assert "np.asarray" in found[0].message


def test_sync_shard_map_body_reached_via_nested_def(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "parallel/mesh.py": """
            import jax
            from jax import shard_map

            @jax.jit
            def gossip(x):
                def step(local):
                    return local.block_until_ready()
                return shard_map(step, mesh=None, in_specs=None, out_specs=None)(x)
            """,
        },
    )
    assert "SYNC001" in rules_of(lint(pkg))


def test_sync_block_until_ready_in_op_module_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            import jax

            def probe(f, x):
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SYNC002"}


def test_sync_allow_comment_suppresses(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync] probe must synchronise by design
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    new, _baselined, allowed = run_lint([pkg])
    assert new == [] and len(allowed) == 1


def test_sync_allow_comment_does_not_bleed_to_next_line(tmp_path):
    # a trailing allow on line N must not suppress a finding on N+1
    pkg = make_pkg(
        tmp_path,
        {
            "ops/kern.py": """
            import jax

            def probe(f, x):
                a = jax.jit(f)(x).block_until_ready()  # crdtlint: allow[host-sync] why
                b = jax.jit(f)(x).block_until_ready()
                return a, b
            """,
        },
    )
    new, _baselined, allowed = run_lint([pkg])
    assert len(allowed) == 1 and len(new) == 1
    assert new[0].rule == "SYNC002"


def test_lock_reentrant_with_does_not_release_outer_hold(tmp_path):
    # RLock reentrancy: an inner `with self._lock:` exiting must not make
    # the rest of the outer critical section look unguarded
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._items = []

                def put(self, x):
                    with self._lock:
                        with self._lock:
                            self._items.append(x)
                        self._items.append(x)
            """
        },
    )
    assert lint(pkg) == []


def test_sync_block_until_ready_outside_op_modules_ignored(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "runtime/driver.py": """
            import jax

            def hibernate(state):
                jax.block_until_ready(state)
            """,
        },
    )
    assert lint(pkg) == []


# ----------------------------------------------------------------------
# PURE001–PURE003 — lattice-op purity


def test_purity_arg_mutation_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/join2.py": """
            def join(local, remote):
                local.ctx = remote.ctx
                return local
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"PURE001"}


def test_purity_mutator_call_flagged_at_indexer_exempt(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "models/m.py": """
            def merge_contexts(a, b):
                out = a.at[0].set(b[0])   # functional jax update: fine
                a.update(b)               # in-place: flagged
                return out
            """,
        },
    )
    found = lint(pkg)
    assert len(found) == 1 and found[0].rule == "PURE001"
    assert "update" in found[0].message


def test_purity_impure_calls_and_global(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/j.py": """
            import random
            import time

            _CACHE = {}

            def delta_of(state):
                global _CACHE
                _CACHE = {}
                return state

            def merge(a, b):
                if random.random() < 0.5:
                    return a
                return b, time.time()
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"PURE002", "PURE003"}
    assert sum(f.rule == "PURE003" for f in found) == 2


def test_purity_scope_limited_to_ops_models(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "runtime/r.py": """
            import time

            def merge(a, b):
                a.x = time.time()
                return a
            """,
        },
    )
    assert lint(pkg) == []


def test_purity_nonmatching_names_ignored(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import time

            def stamp(a):
                return time.time()
            """,
        },
    )
    assert lint(pkg) == []


# ----------------------------------------------------------------------
# DONATE001 — donation hygiene


def test_donation_reuse_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def grow(state):
                return state

            jit_grow = jax.jit(grow, donate_argnums=(0,))

            def driver(state):
                out = jit_grow(state)
                return out, state.shape
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"DONATE001"}
    assert "'state'" in found[0].message


def test_donation_rebind_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def grow(state):
                return state

            jit_grow = jax.jit(grow, donate_argnums=(0,))

            def driver(state):
                state = jit_grow(state)
                return state
            """,
        },
    )
    assert lint(pkg) == []


def test_donation_cross_module_call_site(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def grow(state):
                return state

            jit_grow = jax.jit(grow, donate_argnums=(0,))
            """,
            "runtime/r.py": """
            from fixpkg.ops.k import jit_grow

            def driver(state):
                out = jit_grow(state)
                return out, state
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"DONATE001"}
    assert found[0].path.endswith("runtime/r.py")


def test_lock_conditional_acquire_does_not_leak_held_state(tmp_path):
    # a lock acquired in only one branch is NOT held after the join
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)

                def maybe(self, cond, x):
                    if cond:
                        self._lock.acquire()
                    self._items.append(x)
                    if cond:
                        self._lock.release()
            """
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK001"}


def test_sync_similar_name_helper_not_flagged(tmp_path):
    # SYNC002 must match the exact name, not a substring
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            def safe_block_until_ready(x):
                return x

            def driver(x):
                return safe_block_until_ready(x)
            """,
        },
    )
    assert lint(pkg) == []


def test_donation_early_return_branch_not_flagged(tmp_path):
    # `return state` only runs when the donating branch was NOT taken
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def grow(state):
                return state

            jit_grow = jax.jit(grow, donate_argnums=(0,))

            def driver(state, flag):
                if flag:
                    out = jit_grow(state)
                    return out
                return state
            """,
        },
    )
    assert lint(pkg) == []


def test_cli_select_rejects_unknown_rule(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", PKG, "--select", "SYNC01"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2 and "unknown rule" in proc.stderr


def test_donation_multiline_call_not_flagged(tmp_path):
    # the donor's own Name node on a continuation line is the donation
    # itself, not a read after the call
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def grow(state):
                return state

            jit_grow = jax.jit(grow, donate_argnums=(0,))

            def driver(state):
                out = jit_grow(
                    state,
                )
                return out
            """,
        },
    )
    assert lint(pkg) == []


def test_sync_same_name_host_function_not_flagged(tmp_path):
    # reachability is keyed by node identity: an untraced host-side
    # function sharing a jit entry's name must not be flagged
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            @jax.jit
            def kernel(x):
                return x + 1

            class HostProbe:
                def kernel(self, x):
                    return x.item()
            """,
        },
    )
    assert lint(pkg) == []


# ----------------------------------------------------------------------
# baseline workflow


def test_baseline_roundtrip_and_count_semantics(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {"box.py": LOCKED_CLASS.format(body="size(self):\n            return len(self._items)")},
    )
    found = lint(pkg)
    assert len(found) == 1
    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, found)
    data = json.loads(bl_path.read_text())
    assert data["version"] == 1 and len(data["findings"]) == 1

    # baselined finding no longer reported as new
    new, baselined, _ = run_lint([pkg], baseline=load_baseline(bl_path))
    assert new == [] and len(baselined) == 1

    # a second finding site: the baseline absorbs only what it records
    # (the size() fingerprint); the new peek() site is reported as new
    extra = LOCKED_CLASS.format(
        body=(
            "size(self):\n"
            "            return len(self._items)\n\n"
            "        def peek(self):\n"
            "            return len(self._items)"
        )
    )
    pkg2 = make_pkg(tmp_path / "b", {"box.py": extra})
    new2, baselined2, _ = run_lint([pkg2], baseline=load_baseline(bl_path))
    assert len(new2) + len(baselined2) == 2 and len(baselined2) <= 1


def test_write_baseline_with_select_preserves_other_rules(tmp_path):
    # selective rewrite must carry over accepted debt of unselected rules
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)

                def size(self):
                    return len(self._items)

            def merge(a, b):
                a.update(b)
                return a
            """,
        },
    )
    bl = tmp_path / "bl.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", str(pkg),
         "--baseline", str(bl), "--write-baseline"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    full = load_baseline(bl)
    assert {r for (_p, r, _m) in full} == {"LOCK001", "PURE001"}
    # selective rewrite of just PURE001 must not drop the LOCK001 entry
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", str(pkg),
         "--baseline", str(bl), "--select", "PURE001", "--write-baseline"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert {r for (_p, r, _m) in load_baseline(bl)} == {"LOCK001", "PURE001"}


# ----------------------------------------------------------------------
# end-to-end over the real package


def _cli(*argv: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", *argv],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_e2e_package_is_clean():
    """The tier-1 gate: zero unsuppressed findings on the real tree."""
    proc = _cli(PKG)
    assert proc.returncode == 0, f"crdtlint found:\n{proc.stdout}{proc.stderr}"
    assert "0 finding(s)" in proc.stdout


def test_e2e_list_rules_and_bad_package():
    assert "LOCK001" in _cli("--list-rules").stdout
    assert _cli("no_such_pkg").returncode == 2


def test_e2e_every_lock_deletion_in_replica_is_caught():
    """Acceptance: deleting any single ``with self._lock:`` from
    runtime/replica.py must produce a finding."""
    rel = f"{PKG}/runtime/replica.py"
    src = (REPO_ROOT / rel).read_text()
    lines = src.splitlines(keepends=True)
    sites = [i for i, l in enumerate(lines) if l.strip() == "with self._lock:"]
    assert len(sites) >= 10, "replica.py lost its lock regions?"
    for site in sites:
        mutated = lines[:]
        indent = len(lines[site]) - len(lines[site].lstrip())
        mutated[site] = " " * indent + "if True:\n"
        new, _, _ = run_lint(
            [REPO_ROOT / PKG], overlay={rel: "".join(mutated)}
        )
        assert any(f.rule == "LOCK001" for f in new), (
            f"deleting the lock at replica.py:{site + 1} went undetected"
        )


def test_e2e_unannotated_item_in_join_is_caught():
    """Acceptance: an unannotated .item() in ops/join.py must fail."""
    rel = f"{PKG}/ops/join.py"
    src = (REPO_ROOT / rel).read_text()
    anchor = "    n_killed = jnp.sum((local.alive & ~alive1).astype(jnp.int32))"
    assert anchor in src
    mutated = src.replace(anchor, anchor + "\n    _dbg = n_killed.item()")
    new, _, _ = run_lint([REPO_ROOT / PKG], overlay={rel: mutated})
    assert any(
        f.rule == "SYNC001" and f.path.endswith("ops/join.py") for f in new
    )


def test_e2e_real_tree_clean_via_engine():
    new, _baselined, allowed = run_lint([REPO_ROOT / PKG])
    assert new == []
    assert allowed
    # the kernels are chosen by platform, never by a synchronising
    # probe: the Pallas modules need no host-sync allowance
    assert not any(
        f.path.endswith(("ops/pallas_tree.py", "ops/hash_map.py")) for f in allowed
    )


# ----------------------------------------------------------------------
# WIRE001–WIRE005 — wire-protocol drift (fixtures)


WIRE_PKG = {
    "runtime/sync.py": """
    import dataclasses


    @dataclasses.dataclass
    class PingMsg:
        frm: str
        seq: int


    @dataclasses.dataclass
    class PongMsg:
        frm: str
        seq: int
    """,
    "runtime/node.py": """
    from fixpkg.runtime import sync


    class Node:
        def handle(self, msg):
            if isinstance(msg, sync.PingMsg):
                pass
            elif isinstance(msg, sync.PongMsg):
                pass
    """,
}


def test_wire_complete_protocol_clean(tmp_path):
    pkg = make_pkg(tmp_path, WIRE_PKG)
    assert lint(pkg) == []


def test_wire_unhandled_message_flagged(tmp_path):
    mods = dict(WIRE_PKG)
    mods["runtime/sync.py"] += (
        "\n\n    @dataclasses.dataclass\n    class LostMsg:\n        frm: str\n"
    )
    pkg = make_pkg(tmp_path, mods)
    found = lint(pkg)
    assert rules_of(found) == {"WIRE001"}
    assert "LostMsg" in found[0].message


def test_wire_duplicate_and_ghost_arms_flagged(tmp_path):
    mods = dict(WIRE_PKG)
    mods["runtime/node.py"] = """
    from fixpkg.runtime import sync


    class Node:
        def handle(self, msg):
            if isinstance(msg, sync.PingMsg):
                pass
            elif isinstance(msg, sync.PongMsg):
                pass
            elif isinstance(msg, sync.PingMsg):
                pass
            elif isinstance(msg, sync.GhostMsg):
                pass
    """
    pkg = make_pkg(tmp_path, mods)
    found = lint(pkg)
    assert rules_of(found) == {"WIRE002"}
    msgs = " | ".join(f.message for f in found)
    assert "already handled" in msgs and "missing" in msgs


def test_wire_unserializable_field_flagged(tmp_path):
    mods = dict(WIRE_PKG)
    mods["runtime/sync.py"] = """
    import dataclasses
    import threading
    from typing import Callable


    @dataclasses.dataclass
    class PingMsg:
        frm: str
        notify: Callable


    @dataclasses.dataclass
    class PongMsg:
        frm: str
        gate: threading.Lock
    """
    pkg = make_pkg(tmp_path, mods)
    found = [f for f in lint(pkg) if f.rule == "WIRE003"]
    assert len(found) == 2
    assert "Callable" in found[0].message and "Lock" in found[1].message


def test_wire_frame_kind_sent_but_not_decoded(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "runtime/codec.py": """
            _MSG = 0
            _PING = 1
            _LOST = 2


            def _send_frame(sock, kind, payload):
                sock.sendall(bytes([kind]) + payload)


            def client(sock):
                _send_frame(sock, _MSG, b"x")
                _send_frame(sock, _PING, b"")
                _send_frame(sock, _LOST, b"?")


            def serve(sock, kind, payload):
                if kind == _MSG:
                    return payload
                elif kind == _PING:
                    return b"pong"
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"WIRE004"}
    assert "_LOST" in found[0].message


def test_wire_manifest_drift_flagged(tmp_path):
    from tools.crdtlint.rules.wire import write_manifest

    pkg = make_pkg(tmp_path, WIRE_PKG)
    manifest = tmp_path / "manifest.json"
    # recorded manifest: PingMsg with the OLD field list, PongMsg absent
    write_manifest(manifest, {
        "fixpkg": {
            "module": "fixpkg/runtime/sync.py",
            "messages": {
                "PingMsg": {"fields": [["frm", "str"]], "sha256": "stale"},
                "GoneMsg": {"fields": [], "sha256": "x"},
            },
        },
    })
    found = [
        f for f in lint(pkg, manifest=manifest) if f.rule == "WIRE005"
    ]
    msgs = " | ".join(f.message for f in found)
    assert "PingMsg" in msgs and "drifted" in msgs        # hash mismatch
    assert "PongMsg" in msgs and "not in the protocol" in msgs
    assert "GoneMsg" in msgs and "no longer defined" in msgs


def test_wire_manifest_in_sync_clean(tmp_path):
    from tools.crdtlint.engine import Project
    from tools.crdtlint.rules.wire import compute_manifest, write_manifest

    pkg = make_pkg(tmp_path, WIRE_PKG)
    manifest = tmp_path / "manifest.json"
    write_manifest(manifest, {"fixpkg": compute_manifest(Project(pkg))})
    assert lint(pkg, manifest=manifest) == []


# ----------------------------------------------------------------------
# LOCK002 / LOCK003 — lock order + blocking under lock (fixtures)


def test_lockorder_inverted_pair_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    with self._a:
                        with self._b:
                            pass

                def backward(self):
                    with self._b:
                        with self._a:
                            pass
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK002"}
    assert "deadlock" in found[0].message


def test_lockorder_consistent_order_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
            """,
        },
    )
    assert lint(pkg) == []


def test_lockorder_three_lock_rotation_cycle_flagged(tmp_path):
    # no inverted PAIR anywhere — the deadlock is the 3-cycle a->b->c->a
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._c = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def bc(self):
                    with self._b:
                        with self._c:
                            pass

                def ca(self):
                    with self._c:
                        with self._a:
                            pass
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK002"}
    assert "_a" in found[0].message and "_c" in found[0].message


def test_lockorder_interprocedural_held_state_edge(tmp_path):
    # the second lock is taken in a helper that is only ever CALLED with
    # the first held — the edge must come from the propagated entry
    # state, not the helper's lexical context
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    with self._a:
                        self._grab_b()

                def _grab_b(self):
                    with self._b:
                        pass

                def backward(self):
                    with self._b:
                        with self._a:
                            pass
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK002"}


def test_lockorder_reentrant_rlock_not_a_cycle(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._items = []

                def outer(self):
                    with self._lock:
                        self._inner()

                def _inner(self):
                    with self._lock:
                        self._items.append(1)
            """,
        },
    )
    assert lint(pkg) == []


def test_blocking_under_lock_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import os
            import time
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._fd = 3

                def slow(self):
                    with self._lock:
                        time.sleep(1.0)

                def sync(self):
                    with self._lock:
                        os.fsync(self._fd)

                def fine(self):
                    time.sleep(1.0)
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK003"}
    assert len(found) == 2  # slow() + sync(); fine() holds nothing


def test_blocking_via_constructed_member_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "wal.py": """
            import os


            class Wal:
                def __init__(self, fd):
                    self._fd = fd

                def commit(self):
                    self._write_out()

                def _write_out(self):
                    os.fsync(self._fd)
            """,
            "rep.py": """
            import threading

            from fixpkg.wal import Wal


            class Rep:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._wal = Wal(3)

                def mutate(self):
                    with self._lock:
                        self._wal.commit()
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK003"}
    assert "via Wal.commit" in found[0].message
    assert found[0].path.endswith("rep.py")


def test_blocking_via_module_import_constructed_member(tmp_path):
    # `self._wal = wal.Wal(...)` — constructor through a MODULE import
    # must resolve like the from-import form
    pkg = make_pkg(
        tmp_path,
        {
            "wal.py": """
            import os


            class Wal:
                def __init__(self, fd):
                    self._fd = fd

                def commit(self):
                    os.fsync(self._fd)
            """,
            "rep.py": """
            import threading

            from fixpkg import wal


            class Rep:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._wal = wal.Wal(3)

                def mutate(self):
                    with self._lock:
                        self._wal.commit()
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK003"}
    assert "via Wal.commit" in found[0].message


def test_wire_malformed_manifest_is_a_finding_not_a_crash(tmp_path):
    pkg = make_pkg(tmp_path, WIRE_PKG)
    manifest = tmp_path / "manifest.json"
    manifest.write_text('{"version": 1, "packages": null}\n')
    found = lint(pkg, manifest=manifest)
    assert rules_of(found) == {"WIRE005"}
    assert "malformed" in found[0].message


def test_blocking_thread_join_receiver_typed(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = threading.Thread(target=self._run)

                def _run(self):
                    pass

                def stop_bad(self):
                    with self._lock:
                        self._t.join()

                def stop_good(self):
                    self._t.join()

                def strings_fine(self):
                    with self._lock:
                        return ", ".join(["a", "b"])
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"LOCK003"}
    assert len(found) == 1 and "Thread.join" in found[0].message


# ----------------------------------------------------------------------
# WAL001 / WAL002 — record-kind exhaustiveness (fixtures)


WAL_PKG = {
    "wal.py": """
    class Log:
        def append_batch(self, seq, ops):
            self._stage({"kind": "batch", "seq": seq, "ops": ops})

        def append_slice(self, seq, arrays):
            self._stage({"kind": "entries", "seq": seq, "arrays": arrays})

        def _stage(self, rec):
            pass
    """,
    "rep.py": """
    class Rep:
        def _replay(self, records):
            for rec in records:
                if rec["kind"] == "batch":
                    pass
                elif rec["kind"] == "entries":
                    pass

        def _scan_log_rows(self, records):
            for rec in records:
                kind = rec.get("kind")
                if kind == "batch":
                    pass
                elif kind == "entries":
                    pass
    """,
}


def test_wal_kinds_covered_clean(tmp_path):
    pkg = make_pkg(tmp_path, WAL_PKG)
    assert lint(pkg) == []


def test_wal_new_kind_must_reach_both_dispatchers(tmp_path):
    mods = dict(WAL_PKG)
    mods["wal.py"] += (
        "\n"
        "        def append_clear(self, seq):\n"
        '            self._stage({"kind": "clear", "seq": seq})\n'
    )
    pkg = make_pkg(tmp_path, mods)
    found = lint(pkg)
    assert rules_of(found) == {"WAL001", "WAL002"}
    assert all("'clear'" in f.message for f in found)


def test_wal_membership_classification_counts(tmp_path):
    # `kind in ("a", "b")` is an explicit classification, same as ==
    mods = dict(WAL_PKG)
    mods["wal.py"] += (
        "\n"
        "        def append_clear(self, seq):\n"
        '            self._stage({"kind": "clear", "seq": seq})\n'
    )
    mods["rep.py"] = """
    class Rep:
        def _replay(self, records):
            for rec in records:
                if rec["kind"] in ("batch", "entries", "clear"):
                    pass

        def _scan_log_rows(self, records):
            for rec in records:
                kind = rec.get("kind")
                if kind in ("clear",):
                    pass  # explicit barrier
                elif kind == "batch":
                    pass
                elif kind == "entries":
                    pass
    """
    pkg = make_pkg(tmp_path, mods)
    assert lint(pkg) == []


def test_wal_missing_replay_dispatcher_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"wal.py": WAL_PKG["wal.py"]})
    found = lint(pkg)
    assert rules_of(found) == {"WAL001", "WAL002"}
    assert any("no recovery replay" in f.message for f in found)


# ----------------------------------------------------------------------
# SUPPRESS001 / SUPPRESS002 — stale-suppression hygiene


def test_stale_allow_comment_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync] probe must synchronise
                jax.jit(f)(x).block_until_ready()
                y = x  # crdtlint: allow[donation] nothing donated here
                return f, y
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SUPPRESS001"}
    assert "allow[donation]" in found[0].message


def test_stale_baseline_entry_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {"box.py": LOCKED_CLASS.format(body="size(self):\n            return len(self._items)")},
    )
    baseline = {
        ("fixpkg/box.py", "LOCK001", "long-gone finding message"): 1,
    }
    found = [f for f in lint(pkg, baseline=baseline) if f.rule == "SUPPRESS002"]
    assert len(found) == 1
    assert "long-gone finding message" in found[0].message


def test_hygiene_skipped_under_select(tmp_path):
    # a --select run cannot distinguish stale from not-run: no SUPPRESS
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            def f(x):
                return x  # crdtlint: allow[purity] speculative
            """,
        },
    )
    assert lint(pkg, select={"LOCK001"}) == []
    assert rules_of(lint(pkg)) == {"SUPPRESS001"}


def test_multiline_justification_comment_projects_past_continuation(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync] the justification of this
                # probe spans several comment lines before the call
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    new, _baselined, allowed = run_lint([pkg])
    assert new == [] and len(allowed) == 1


# ----------------------------------------------------------------------
# mutation tests — every new rule family proves it turns the gate red
# on the REAL tree (engine overlay, working tree untouched)


def _overlay_lint(rel: str, mutate) -> list[Finding]:
    src = (REPO_ROOT / rel).read_text()
    new, _, _ = run_lint([REPO_ROOT / PKG], overlay={rel: mutate(src)})
    return new


def test_mutation_deleted_dispatch_arm_is_caught():
    """Acceptance: deleting a dispatch arm in replica.py turns the gate
    red (WIRE001: the message is no longer handled anywhere)."""
    rel = f"{PKG}/runtime/replica.py"
    arm = (
        "            elif isinstance(msg, sync_proto.GetLogMsg):\n"
        "                self._handle_get_log(msg)\n"
    )
    assert arm in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(arm, ""))
    assert any(
        f.rule == "WIRE001" and "GetLogMsg" in f.message for f in new
    )


def test_mutation_unserializable_ackmsg_field_is_caught():
    """Acceptance: adding an unserializable field to AckMsg turns the
    gate red (WIRE003 type check + WIRE005 manifest drift)."""
    rel = f"{PKG}/runtime/sync.py"
    anchor = "    clear_addr: Hashable"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel, lambda s: s.replace(anchor, anchor + "\n    waiter: 'threading.Event'")
    )
    assert any(f.rule == "WIRE003" and "AckMsg" in f.message for f in new)
    assert any(f.rule == "WIRE005" and "AckMsg" in f.message for f in new)


def test_mutation_reordered_wire_fields_is_caught():
    """Acceptance: reordering DiffMsg fields without bumping the
    manifest turns the gate red (WIRE005 — order is wire contract)."""
    rel = f"{PKG}/runtime/sync.py"
    src = (REPO_ROOT / rel).read_text()
    a = "    originator: Hashable\n    frm: Hashable\n"
    assert a in src
    new = _overlay_lint(
        rel, lambda s: s.replace(a, "    frm: Hashable\n    originator: Hashable\n", 1)
    )
    assert any(f.rule == "WIRE005" and "DiffMsg" in f.message for f in new)


def test_mutation_undecoded_frame_kind_is_caught():
    """A frame kind sent by the TCP codec without a receive-path decode
    arm turns the gate red (WIRE004)."""
    rel = f"{PKG}/runtime/tcp_transport.py"
    new = _overlay_lint(
        rel,
        lambda s: s.replace("_MSGB = 5", "_MSGB = 5\n_TRACE = 7").replace(
            '_send_frame(sock, _PING, b"")',
            '_send_frame(sock, _TRACE, b"");  _send_frame(sock, _PING, b"")',
            1,
        ),
    )
    assert any(f.rule == "WIRE004" and "_TRACE" in f.message for f in new)


def test_mutation_inverted_lock_pair_is_caught():
    """Acceptance: an inverted lock-acquisition pair in replica.py turns
    the gate red (LOCK002)."""
    rel = f"{PKG}/runtime/replica.py"
    probe = (
        "\n"
        "    def probe_setup(self):\n"
        "        self._probe_lock = threading.Lock()\n"
        "\n"
        "    def probe_forward(self):\n"
        "        with self._lock:\n"
        "            with self._probe_lock:\n"
        "                pass\n"
        "\n"
        "    def probe_backward(self):\n"
        "        with self._probe_lock:\n"
        "            with self._lock:\n"
        "                pass\n"
    )

    def mutate(s: str) -> str:
        cls_end = s.rindex("\n    def stop(self)")
        tail_end = s.index("self.transport.unregister(self.name)", cls_end)
        tail_end = s.index("\n", tail_end) + 1
        return s[:tail_end] + probe + s[tail_end:]

    new = _overlay_lint(rel, mutate)
    assert any(f.rule == "LOCK002" for f in new)


def test_mutation_invented_wal_kind_is_caught():
    """Acceptance: a WAL record kind written by a producer without
    replay/serving arms turns the gate red (WAL001 + WAL002)."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = '"kind": "entries",'
    src = (REPO_ROOT / rel).read_text()
    assert anchor in src
    new = _overlay_lint(
        rel, lambda s: s.replace(anchor, '"kind": "tombstone",', 1)
    )
    assert any(f.rule == "WAL001" and "'tombstone'" in f.message for f in new)
    assert any(f.rule == "WAL002" and "'tombstone'" in f.message for f in new)


def test_mutation_host_sync_in_fleet_transition_is_caught():
    """Acceptance (ISSUE 6): an injected host sync in the fleet's pure
    batched-transition path turns the gate red (SYNC001) — every
    function in ``runtime/transition.py`` is a jit entry root by
    contract, so the leak is caught even with no caller jit-wrapping
    the mutated function."""
    rel = f"{PKG}/runtime/transition.py"
    anchor = "    return jax.vmap(binned_ops.merge_rows)(states, slices)"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor, "    _n = states.fill.sum().item()\n" + anchor, 1
        ),
    )
    assert any(
        f.rule == "SYNC001" and f.path.endswith("runtime/transition.py")
        for f in new
    )
    # int() coercion of a traced value is the same leak class
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor, "    _n = int(states.fill.sum())\n" + anchor, 1
        ),
    )
    assert any(
        f.rule == "SYNC001" and f.path.endswith("runtime/transition.py")
        for f in new
    )


def test_mutation_host_sync_in_fleet_egress_extraction_is_caught():
    """Acceptance (ISSUE 10): an injected ``.item()`` in the batched
    egress extraction (``fleet_extract_rows``) turns the gate red
    (SYNC001) — the new egress functions are jit entry roots by the
    same module contract as the merge forms."""
    rel = f"{PKG}/runtime/transition.py"
    anchor = "    return jax.vmap(binned_ops.extract_rows)(states, rows)"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor, "    _n = rows.sum().item()\n" + anchor, 1
        ),
    )
    assert any(
        f.rule == "SYNC001" and f.path.endswith("runtime/transition.py")
        for f in new
    )


def test_mutation_fleet_frame_wire_drift_is_caught():
    """Acceptance (ISSUE 10): FleetFrameMsg is manifest-locked — adding
    a wire field without ``--write-protocol-manifest`` turns the gate
    red (WIRE005), exactly the reviewed-bump workflow this PR used to
    land the message."""
    rel = f"{PKG}/runtime/sync.py"
    anchor = "    entries: list  # [(to_addr, message), ...] in send order"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel, lambda s: s.replace(anchor, anchor + "\n    hops: int = 0", 1)
    )
    assert any(
        f.rule == "WIRE005" and "FleetFrameMsg" in f.message for f in new
    )


def test_mutation_impure_fleet_transition_is_caught():
    """An in-place argument mutation (PURE001) or a clock read
    (PURE003) injected into the fleet merge transition turns the gate
    red — the vmapped lattice ops are purity-scoped like ops/ and
    models/ joins."""
    rel = f"{PKG}/runtime/transition.py"
    anchor = "    return jax.vmap(binned_ops.merge_rows)(states, slices)"
    new = _overlay_lint(
        rel,
        lambda s: s.replace(anchor, "    states.key = slices\n" + anchor, 1),
    )
    assert any(f.rule == "PURE001" for f in new)
    new = _overlay_lint(
        rel,
        lambda s: s.replace(anchor, "    _t = time.time()\n" + anchor, 1),
    )
    assert any(f.rule == "PURE003" for f in new)


def test_mutation_host_sync_in_hash_kernel_is_caught():
    """Acceptance (ISSUE 8): an injected ``.item()`` in the hash-store
    kernel module turns the gate red (SYNC001) — ``ops/hash_map.py`` is
    a jit-entry-root module by contract like ``runtime/transition.py``,
    so the leak is caught with no caller jit-wrapping the function."""
    rel = f"{PKG}/ops/hash_map.py"
    anchor = "    v = _slice_view(state, sl)"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor, "    _n = state.alive.sum().item()\n" + anchor, 1
        ),
    )
    assert any(
        f.rule == "SYNC001" and f.path.endswith("ops/hash_map.py") for f in new
    )


def test_mutation_impure_rehash_is_caught():
    """Acceptance (ISSUE 8): an impure rehash turns the gate red —
    every function in ``ops/hash_map.py`` is purity-scoped whatever its
    name (rehash rebuilds anti-entropy state that must replicate
    bit-for-bit), so an in-place argument mutation (PURE001) and a
    clock read (PURE003) are both caught."""
    rel = f"{PKG}/ops/hash_map.py"
    anchor = "    H_old = state.table_size"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(anchor, "    state.arr = state.ctr\n" + anchor, 1),
    )
    assert any(
        f.rule == "PURE001" and f.path.endswith("ops/hash_map.py") for f in new
    )
    new = _overlay_lint(
        rel,
        lambda s: s.replace(anchor, "    _t = time.time()\n" + anchor, 1),
    )
    assert any(
        f.rule == "PURE003" and f.path.endswith("ops/hash_map.py") for f in new
    )


def test_mutation_impure_class_method_in_hash_kernel_is_caught():
    """A CLASS-based kernel helper gets no gate bypass: methods of a
    top-level class in a whole-module/transition-root module are their
    own purity and host-sync roots (they have no enclosing function
    whose ast.walk would cover them — the hole a nested-def skip keyed
    on ``parts[-2]`` alone would leave open)."""
    rel = f"{PKG}/ops/hash_map.py"
    helper = (
        "class _KernelHelper:\n"
        "    def merge_rows_extra(self, state):\n"
        "        return time.time()\n"
        "    def scan(self, state):\n"
        "        return state.alive.sum().item()\n"
    )
    new = _overlay_lint(rel, lambda s: s + "\n\n" + helper)
    assert any(
        f.rule == "PURE003" and f.path.endswith("ops/hash_map.py") for f in new
    ), "class-method clock read escaped the whole-module purity gate"
    assert any(
        f.rule == "SYNC001" and f.path.endswith("ops/hash_map.py") for f in new
    ), "class-method .item() escaped the transition-root host-sync gate"


def test_mutation_stale_allow_is_caught():
    """A freshly stale allow comment (rule fixed, comment left behind)
    turns the gate red (SUPPRESS001)."""
    rel = f"{PKG}/runtime/wal.py"
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            "import dataclasses",
            "import dataclasses  # crdtlint: allow[purity] speculative",
            1,
        ),
    )
    assert any(
        f.rule == "SUPPRESS001" and f.path.endswith("runtime/wal.py")
        for f in new
    )


# ----------------------------------------------------------------------
# RACE001–005 — happens-before race detection (fixtures)


RACY_COUNTER = """
    import threading

    class Box:
        def __init__(self):
            self._n = 0
            self._thread = threading.Thread(target=self._loop)
            self._thread.start()

        def _loop(self):
            while True:
                self._n += 1

        def size(self):
            return self._n
"""


def test_race_cross_thread_counter_flagged(tmp_path):
    """A completely lock-free cross-thread counter: LOCK001 is blind
    (no lock anywhere means no guard to infer) — RACE001 is the rule
    that sees it."""
    pkg = make_pkg(tmp_path, {"box.py": RACY_COUNTER})
    found = lint(pkg)
    assert rules_of(found) == {"RACE001"}
    assert any("_n" in f.message and "_loop" in f.message for f in found)


def test_race_counter_with_common_lock_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                    self._thread = threading.Thread(target=self._loop)
                    self._thread.start()

                def _loop(self):
                    while True:
                        with self._lock:
                            self._n += 1

                def size(self):
                    with self._lock:
                        return self._n
            """
        },
    )
    assert lint(pkg) == []


# -- happens-before edges, one fixture per edge kind -------------------


START_EDGE = """
    import threading

    class Box:
        def __init__(self):
            self._cfg = None

        def start(self):
            self._cfg = 42
            self._thread = threading.Thread(target=self._loop)
            self._thread.start()
            {post}

        def _loop(self):
            print(self._cfg)
"""


def test_hb_start_edge_orders_pre_start_writes(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": START_EDGE.format(post="return self")})
    assert lint(pkg) == []


def test_hb_write_after_start_is_published_race(tmp_path):
    pkg = make_pkg(
        tmp_path, {"box.py": START_EDGE.format(post="self._cfg = 43")}
    )
    found = lint(pkg)
    assert "RACE004" in rules_of(found)
    assert any(
        f.rule == "RACE004" and "_cfg" in f.message and "_loop" in f.message
        for f in found
    )


JOIN_EDGE = """
    import threading

    class Box:
        def __init__(self):
            self._out = []
            self._thread = threading.Thread(target=self._work)
            self._thread.start()

        def _work(self):
            self._out.append(1)

        def result(self):
            {pre}return list(self._out)
"""


def test_hb_join_edge_orders_thread_writes(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {"box.py": JOIN_EDGE.format(pre="self._thread.join()\n            ")},
    )
    assert lint(pkg) == []


def test_hb_missing_join_is_iteration_race(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": JOIN_EDGE.format(pre="")})
    found = lint(pkg)
    assert rules_of(found) == {"RACE005"}
    assert "_out" in found[0].message


EVENT_EDGE = """
    import threading

    class Box:
        def __init__(self):
            self._ready = threading.Event()
            self._result = None
            self._thread = threading.Thread(target=self._work)
            self._thread.start()

        def _work(self):
            self._result = 41
            self._ready.set()

        def read(self):
            self._ready.wait({timeout})
            return self._result
"""


def test_hb_event_set_wait_edge_orders_handoff(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": EVENT_EDGE.format(timeout="")})
    assert lint(pkg) == []


def test_hb_timed_wait_is_not_an_edge(tmp_path):
    """``Event.wait(timeout)`` can return with nothing set — pacing,
    not synchronisation. The same handoff with a timeout races."""
    pkg = make_pkg(tmp_path, {"box.py": EVENT_EDGE.format(timeout="0.5")})
    found = lint(pkg)
    assert rules_of(found) == {"RACE001"}
    assert any("_result" in f.message for f in found)


QUEUE_EDGE = """
    import queue
    import threading

    class Box:
        def __init__(self):
            self._q = queue.Queue()
            self._q2 = queue.Queue()
            self._payload = None
            self._thread = threading.Thread(target=self._work)
            self._thread.start()

        def _work(self):
            self._payload = 7
            self._q.put(None)

        def read(self):
            self._q{get_q}.get()
            return self._payload
"""


def test_hb_queue_put_get_edge_orders_handoff(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": QUEUE_EDGE.format(get_q="")})
    assert lint(pkg) == []


def test_hb_distinct_queues_do_not_synchronize(tmp_path):
    """put on one queue object and get on ANOTHER is no handoff — the
    HB channel is per-object, and blessing cross-queue pairs would hide
    real races behind unrelated queue traffic."""
    pkg = make_pkg(tmp_path, {"box.py": QUEUE_EDGE.format(get_q="2")})
    found = lint(pkg)
    assert rules_of(found) == {"RACE001"}
    assert any("_payload" in f.message for f in found)


# -- RACE002: closure escapes across the thread boundary ---------------


ESCAPE = """
    import threading

    def collect():
        acc = []

        def fill():
            acc.append(1)

        t = threading.Thread(target=fill)
        t.start()
        {mid}
        return list(acc)
"""


def test_race_closure_escape_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": ESCAPE.format(mid="pass")})
    found = lint(pkg)
    assert rules_of(found) == {"RACE002"}
    assert "'acc'" in found[0].message and "fill" in found[0].message


def test_race_closure_escape_joined_clean(tmp_path):
    pkg = make_pkg(tmp_path, {"box.py": ESCAPE.format(mid="t.join()")})
    assert lint(pkg) == []


def test_race_threadsafe_capture_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import queue
            import threading

            def collect():
                acc = queue.Queue()

                def fill():
                    acc.put(1)

                threading.Thread(target=fill).start()
                return acc.get()
            """
        },
    )
    assert lint(pkg) == []


# -- RACE003: check-then-act on version fields -------------------------


VERSION_CHECK = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._ver = 0
            self._val = None

        def bump(self, v):
            with self._lock:
                self._val = v
                self._ver += 1

        def commit(self, expect, v):
            {body}
"""


def test_race_version_check_outside_lock_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": VERSION_CHECK.format(
                body=(
                    "if self._ver != expect:\n"
                    "                return False\n"
                    "            with self._lock:\n"
                    "                self._val = v\n"
                    "            return True"
                )
            )
        },
    )
    found = lint(pkg)
    assert "RACE003" in rules_of(found)
    assert any(
        f.rule == "RACE003" and "_ver" in f.message and "commit" in f.message
        for f in found
    )


def test_race_version_check_inside_lock_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": VERSION_CHECK.format(
                body=(
                    "with self._lock:\n"
                    "                if self._ver != expect:\n"
                    "                    return False\n"
                    "                self._val = v\n"
                    "            return True"
                )
            )
        },
    )
    assert lint(pkg) == []


# -- RACE005: lock-free iteration --------------------------------------


def test_race_unlocked_iteration_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._items = {}
                    self._thread = threading.Thread(target=self._feed)
                    self._thread.start()

                def _feed(self):
                    self._items[1] = 2

                def keys(self):
                    return [k for k in self._items]
            """
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"RACE005"}
    assert "_items" in found[0].message


def test_race_locked_iteration_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = {}
                    self._thread = threading.Thread(target=self._feed)
                    self._thread.start()

                def _feed(self):
                    with self._lock:
                        self._items[1] = 2

                def keys(self):
                    with self._lock:
                        return [k for k in self._items]
            """
        },
    )
    assert lint(pkg) == []


# -- module globals (the telemetry/native shape) -----------------------


MOD_GLOBAL = """
    import threading

    _cache = {{}}
    _lock = threading.Lock()

    def start_filler():
        def fill():
            {fill_body}

        threading.Thread(target=fill, daemon=True).start()

    def peek():
        {peek_body}
"""


def test_race_module_global_cross_thread_flagged(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": MOD_GLOBAL.format(
                fill_body="_cache[1] = 2",
                peek_body="return _cache.get(1)",
            )
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"RACE001"}
    assert any("_cache" in f.message for f in found)


def test_race_module_global_locked_clean(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": MOD_GLOBAL.format(
                fill_body="with _lock:\n                _cache[1] = 2",
                peek_body="with _lock:\n            return _cache.get(1)",
            )
        },
    )
    assert lint(pkg) == []


# ----------------------------------------------------------------------
# RACE mutation tests — ≥5 distinct injected races in the REAL tree
# turn the gate red (engine overlay, working tree untouched)


def test_mutation_deleted_lock_around_cross_thread_write_is_caught():
    """Injected race 1: delete the ``with self._lock:`` around the
    fleet tick counters — the loop thread then writes what stats()
    reads with no common lock (RACE001; LOCK001 stays blind because the
    attr no longer has a guarded write to infer a guard from)."""
    rel = f"{PKG}/runtime/fleet.py"
    anchor = (
        "            with self._lock:\n"
        "                # tick/dispatch counters are read by stats() from any\n"
        "                # caller thread while the fleet loop writes them\n"
        "                # (crdtlint RACE001)\n"
    )
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel, lambda s: s.replace(anchor, "            if True:\n", 1)
    )
    assert any(
        f.rule == "RACE001" and "_ticks" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_event_wait_removed_before_dependent_read_is_caught():
    """Injected race 2: a correct Event handoff added to the real Fleet
    is green (the set→wait edge orders the publication); deleting the
    ``wait()`` turns the same read into a race (RACE001). Proves the HB
    edge is what suppresses — not an accident of the surrounding tree."""
    rel = f"{PKG}/runtime/fleet.py"
    probe = (
        "    def probe_publish(self):\n"
        "        self._probe_done = threading.Event()\n"
        "        self._probe_box = {}\n"
        "\n"
        "        def probe_fill():\n"
        "            self._probe_box[\"r\"] = 1\n"
        "            self._probe_done.set()\n"
        "\n"
        "        threading.Thread(target=probe_fill, daemon=True).start()\n"
        "        self._probe_done.wait()\n"
        "        return self._probe_box[\"r\"]\n"
        "\n"
    )
    anchor = "\ndef start_fleet(replicas"
    src = (REPO_ROOT / rel).read_text()
    assert anchor in src

    with_handoff = src.replace(anchor, "\n" + probe + anchor, 1)
    new, _, _ = run_lint([REPO_ROOT / PKG], overlay={rel: with_handoff})
    assert not any(f.rule.startswith("RACE") for f in new), "\n".join(
        f.render() for f in new
    )

    no_wait = with_handoff.replace("        self._probe_done.wait()\n", "", 1)
    new, _, _ = run_lint([REPO_ROOT / PKG], overlay={rel: no_wait})
    assert any(
        f.rule == "RACE001" and "_probe_box" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_version_check_moved_outside_lock_is_caught():
    """Injected race 3: hoist fleet_commit's ``_state_version`` check
    above the lock — the optimistic-commit recheck is then stale by
    commit time (RACE003)."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = (
        "        with self._lock:\n"
        "            if self._state_version != version:\n"
        "                return None\n"
    )
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor,
            "        if self._state_version != version:\n"
            "            return None\n"
            "        with self._lock:\n",
            1,
        ),
    )
    assert any(
        f.rule == "RACE003" and "_state_version" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_attr_init_below_thread_start_is_caught():
    """Injected race 4: move ``heartbeat_interval``'s assignment below
    the heartbeat thread's start() in TcpTransport.__init__ — the
    started thread can read the attribute before it exists (RACE004)."""
    rel = f"{PKG}/runtime/tcp_transport.py"
    init_line = "        self.heartbeat_interval = heartbeat_interval\n"
    start_line = "        self._hb_thread.start()\n"
    src = (REPO_ROOT / rel).read_text()
    assert init_line in src and start_line in src
    new = _overlay_lint(
        rel,
        lambda s: s.replace(init_line, "", 1).replace(
            start_line, start_line + init_line, 1
        ),
    )
    assert any(
        f.rule == "RACE004" and "heartbeat_interval" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_unlocked_dict_iteration_is_caught():
    """Injected race 5: drop the lock around the heartbeat loop's
    ``_monitors`` snapshot — monitor()/unregister() mutate the dict
    from caller threads mid-iteration (RACE005)."""
    rel = f"{PKG}/runtime/tcp_transport.py"
    anchor = (
        "            with self._lock:\n"
        "                remote_targets = "
        "[t for t in self._monitors if self._is_remote(t)]\n"
    )
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor,
            "            remote_targets = "
            "[t for t in self._monitors if self._is_remote(t)]\n",
            1,
        ),
    )
    assert any(
        f.rule == "RACE005" and "_monitors" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_unlocked_telemetry_handler_table_is_caught():
    """Injected race 6 (module globals over the import graph): delete
    the lock around telemetry.attach's handler-table append — attach
    runs on caller threads while execute/has_handlers read the table
    from the replica/fleet event loops (RACE001 on a module global,
    with the thread root discovered cross-module)."""
    rel = f"{PKG}/runtime/telemetry.py"
    anchor = (
        "def attach(event: tuple, handler: Callable[[tuple, dict, dict], None]) -> None:\n"
        "    with _lock:\n"
        "        _handlers[event] = _handlers[event] + (handler,)\n"
    )
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor,
            "def attach(event: tuple, handler: Callable[[tuple, dict, dict], None]) -> None:\n"
            "    _handlers[event] = _handlers[event] + (handler,)\n",
            1,
        ),
    )
    assert any(
        f.rule == "RACE001" and "_handlers" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_race_allow_tag_and_hygiene(tmp_path):
    """The ``race`` family tag suppresses any RACE00x finding with a
    stated why; once the race is fixed the leftover allow turns the
    gate red itself (SUPPRESS001) — same hygiene contract as every
    other family."""
    annotated = RACY_COUNTER.replace(
        "        def size(self):\n",
        "        def size(self):\n"
        "            # crdtlint: allow[race] approximate counter: torn\n"
        "            # reads tolerated, single writer\n",
    )
    pkg = make_pkg(tmp_path, {"box.py": annotated})
    new, _baselined, allowed = run_lint([pkg])
    assert new == []
    assert {f.rule for f in allowed} == {"RACE001"}

    # fix the race (single-threaded now) but keep the allow: stale
    fixed = annotated.replace(
        "            self._thread = threading.Thread(target=self._loop)\n"
        "            self._thread.start()\n",
        "",
    )
    pkg2 = make_pkg(tmp_path / "b", {"box.py": fixed})
    found = lint(pkg2)
    assert rules_of(found) == {"SUPPRESS001"}


def test_race_snapshot_builtin_reports_race005_only(tmp_path):
    """``list(self._x.values())`` records both an iteration and a
    method-call access on one line — the defect must surface as ONE
    RACE005 finding, not a RACE001/RACE005 double report needing two
    allow comments."""
    pkg = make_pkg(
        tmp_path,
        {
            "box.py": """
            import threading

            class Box:
                def __init__(self):
                    self._items = {}
                    self._thread = threading.Thread(target=self._feed)
                    self._thread.start()

                def _feed(self):
                    self._items[1] = 2

                def values(self):
                    return list(self._items.values())
            """
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"RACE005"}
    assert len([f for f in found if "_items" in f.message]) == 1


# ----------------------------------------------------------------------
# OBS001/OBS002 — observability-plane coverage + hot-path guards


OBS_PKG = {
    "runtime/telemetry.py": """
        SYNC_DONE = ("pkg", "sync", "done")
        WAL_FLUSH = ("pkg", "wal", "flush")

        def has_handlers(event):
            return False

        def execute(event, measurements, metadata):
            pass
    """,
    "runtime/replica.py": """
        from fixpkg.runtime import telemetry

        class Replica:
            def merge(self):
                if telemetry.has_handlers(telemetry.SYNC_DONE):
                    telemetry.execute(telemetry.SYNC_DONE, {"n": 1}, {})

            def flush(self):
                want = telemetry.has_handlers(telemetry.WAL_FLUSH)
                if want:
                    telemetry.execute(telemetry.WAL_FLUSH, {"b": 2}, {})
    """,
    "runtime/metrics.py": """
        from fixpkg.runtime import telemetry

        class Bridge:
            def _on_done(self, e, m, meta):
                pass

            def _on_flush(self, e, m, meta):
                pass

            def _table(self):
                return [
                    (telemetry.SYNC_DONE, self._on_done),
                    (telemetry.WAL_FLUSH, self._on_flush),
                ]
    """,
}


def test_obs_clean_fixture(tmp_path):
    pkg = make_pkg(tmp_path, OBS_PKG)
    assert [f for f in lint(pkg) if f.rule.startswith("OBS")] == []


def test_obs001_unemitted_event_flagged(tmp_path):
    mods = dict(OBS_PKG)
    mods["runtime/telemetry.py"] = (
        OBS_PKG["runtime/telemetry.py"]
        + '\n        GHOST = ("pkg", "ghost", "x")\n'
    )
    mods["runtime/metrics.py"] = OBS_PKG["runtime/metrics.py"].replace(
        "(telemetry.WAL_FLUSH, self._on_flush),",
        "(telemetry.WAL_FLUSH, self._on_flush),\n"
        "                    (telemetry.GHOST, self._on_flush),",
    )
    found = [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS001"]
    assert len(found) == 1 and "never emitted" in found[0].message


def test_obs001_unbridged_event_flagged(tmp_path):
    mods = dict(OBS_PKG)
    mods["runtime/metrics.py"] = OBS_PKG["runtime/metrics.py"].replace(
        "                    (telemetry.WAL_FLUSH, self._on_flush),\n", ""
    )
    found = [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS001"]
    assert len(found) == 1
    assert "WAL_FLUSH" in found[0].message and "bridge" in found[0].message


def test_obs001_missing_bridge_table_flagged(tmp_path):
    mods = dict(OBS_PKG)
    mods["runtime/metrics.py"] = "from fixpkg.runtime import telemetry\n"
    found = [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS001"]
    assert any("no metrics-bridge subscription table" in f.message for f in found)


def test_obs002_unguarded_hot_execute_flagged(tmp_path):
    mods = dict(OBS_PKG)
    mods["runtime/replica.py"] = """
        from fixpkg.runtime import telemetry

        class Replica:
            def merge(self):
                telemetry.execute(telemetry.SYNC_DONE, {"n": 1}, {})
    """
    found = [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS002"]
    assert len(found) == 1 and "SYNC_DONE" in found[0].message


def test_obs002_cold_module_execute_clean(tmp_path):
    """Unguarded execute OUTSIDE the hot module set (e.g. a storage
    module) is fine — the guard discipline is a hot-path contract."""
    mods = dict(OBS_PKG)
    mods["runtime/storage.py"] = """
        from fixpkg.runtime import telemetry

        def persist():
            telemetry.execute(telemetry.WAL_FLUSH, {"b": 1}, {})
    """
    assert [f for f in lint(make_pkg(tmp_path, mods)) if f.rule.startswith("OBS")] == []


def test_obs002_hoisted_guard_clean(tmp_path):
    """`want = telemetry.has_handlers(E)` ... `if want:` is a guard."""
    pkg = make_pkg(tmp_path, OBS_PKG)
    assert [f for f in lint(pkg) if f.rule == "OBS002"] == []


def test_obs002_guarded_closure_clean(tmp_path):
    """A nested def whose *definition* sits under a has_handlers guard
    inherits the guarded state — the deferred-emission idiom (the
    closure is parked and called later, but only ever created under
    the guard)."""
    mods = dict(OBS_PKG)
    mods["runtime/replica.py"] = """
        from fixpkg.runtime import telemetry

        class Replica:
            def merge(self):
                want = telemetry.has_handlers(telemetry.SYNC_DONE)
                if want:
                    def emit(n):
                        telemetry.execute(telemetry.SYNC_DONE, {"n": n}, {})
                    self._defer = emit

            def flush(self):
                if telemetry.has_handlers(telemetry.WAL_FLUSH):
                    telemetry.execute(telemetry.WAL_FLUSH, {"b": 2}, {})
    """
    assert [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS002"] == []


def test_obs_execute_many_counts_as_emission_and_needs_guard(tmp_path):
    """``telemetry.execute_many`` is an emission site for OBS001 (an
    event emitted ONLY through the batch form is not a dead contract)
    and is held to the same OBS002 guard discipline."""
    mods = dict(OBS_PKG)
    mods["runtime/replica.py"] = """
        from fixpkg.runtime import telemetry

        class Replica:
            def merge(self):
                if telemetry.has_handlers(telemetry.SYNC_DONE):
                    telemetry.execute_many(
                        telemetry.SYNC_DONE, [{"n": 1}, {"n": 2}], {}
                    )

            def flush(self):
                want = telemetry.has_handlers(telemetry.WAL_FLUSH)
                if want:
                    telemetry.execute(telemetry.WAL_FLUSH, {"b": 2}, {})
    """
    assert [f for f in lint(make_pkg(tmp_path, mods)) if f.rule.startswith("OBS")] == []
    # strip the guard: the batch form is red exactly like execute
    mods["runtime/replica.py"] = mods["runtime/replica.py"].replace(
        "if telemetry.has_handlers(telemetry.SYNC_DONE):\n                    telemetry.execute_many(",
        "telemetry.execute_many(",
    )
    red = tmp_path / "red"
    red.mkdir()
    found = [f for f in lint(make_pkg(red, mods)) if f.rule == "OBS002"]
    assert len(found) == 1 and "SYNC_DONE" in found[0].message


def test_obs002_unguarded_closure_flagged(tmp_path):
    """A nested def defined OUTSIDE any guard is no excuse — its
    execute is still red, and the finding names the closure."""
    mods = dict(OBS_PKG)
    mods["runtime/replica.py"] = """
        from fixpkg.runtime import telemetry

        class Replica:
            def merge(self):
                def emit(n):
                    telemetry.execute(telemetry.SYNC_DONE, {"n": n}, {})
                emit(1)
                if telemetry.has_handlers(telemetry.WAL_FLUSH):
                    telemetry.execute(telemetry.WAL_FLUSH, {"b": 2}, {})
    """
    found = [f for f in lint(make_pkg(tmp_path, mods)) if f.rule == "OBS002"]
    assert len(found) == 1 and "SYNC_DONE" in found[0].message
    assert "Replica.merge.emit" in found[0].message


def test_mutation_dropped_bridge_row_is_caught():
    """ISSUE 9 acceptance: deleting one subscription row from the REAL
    metrics bridge turns the gate red (OBS001)."""
    rel = f"{PKG}/runtime/metrics.py"
    row = "            (telemetry.CATCHUP_DONE, self._on_catchup_done),\n"
    assert row in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(row, ""))
    assert any(
        f.rule == "OBS001" and "CATCHUP_DONE" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_unguarded_hot_emission_is_caught():
    """ISSUE 9 acceptance: stripping a has_handlers guard off a
    hot-path emission in the REAL replica turns the gate red (OBS002)."""
    rel = f"{PKG}/runtime/replica.py"
    guard = "if telemetry.has_handlers(telemetry.SYNC_ROUND):"
    assert guard in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(guard, "if True:", 1))
    assert any(
        f.rule == "OBS002" and "SYNC_ROUND" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_declared_unemitted_event_is_caught():
    """A declared-but-dead event tuple in the REAL telemetry module is
    a red OBS001 (both legs: unemitted and unbridged)."""
    rel = f"{PKG}/runtime/telemetry.py"
    new = _overlay_lint(
        rel, lambda s: s + '\nGHOST_EVENT = ("delta_crdt", "ghost", "x")\n'
    )
    msgs = [f.message for f in new if f.rule == "OBS001"]
    assert any("never emitted" in m for m in msgs)
    assert any("bridge" in m for m in msgs)


def test_mutation_unguarded_serve_emission_is_caught():
    """ISSUE 14 acceptance: ``runtime/serve.py`` is an OBS002 hot-path
    module — stripping the has_handlers guard off the REAL shed
    emission turns the gate red."""
    rel = f"{PKG}/runtime/serve.py"
    guard = "if telemetry.has_handlers(telemetry.SERVE_SHED):"
    assert guard in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(guard, "if True:", 1))
    assert any(
        f.rule == "OBS002" and "SERVE_SHED" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_dropped_serve_bridge_row_is_caught():
    """ISSUE 14 acceptance: deleting the SERVE_SHED subscription row
    from the REAL metrics bridge turns the gate red (OBS001)."""
    rel = f"{PKG}/runtime/metrics.py"
    row = "            (telemetry.SERVE_SHED, self._on_serve_shed),\n"
    assert row in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(row, ""))
    assert any(
        f.rule == "OBS001" and "SERVE_SHED" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_unlocked_serve_shed_counter_read_is_caught():
    """ISSUE 14 acceptance: the serving front door sits in the
    LOCK/RACE thread graph (its admission worker is a discovered
    thread entry, its one lock mints guards) — an injected UNLOCKED
    read of the shed counter in the REAL ``runtime/serve.py`` turns
    the gate red."""
    rel = f"{PKG}/runtime/serve.py"
    probe = (
        "\n"
        "    def shed_probe(self) -> int:\n"
        "        return self._shed_ops\n"
    )
    anchor = "    def close(self) -> None:"
    src = (REPO_ROOT / rel).read_text()
    assert anchor in src
    new = _overlay_lint(rel, lambda s: s.replace(anchor, probe + "\n" + anchor, 1))
    assert any(
        f.rule in ("LOCK001", "RACE001")
        and "_shed_ops" in f.message
        and "Frontdoor" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


# ----------------------------------------------------------------------
# SHAPE001/SHAPE002 — recompile discipline (ISSUE 12)


SHAPE_REPLICA_RAW = """
    import numpy as np

    def jit_merge(state, sl):
        return state

    class Replica:
        def drain(self, msgs, state):
            n = len(msgs)
            rows = np.full(n, -1, np.int32)
            return jit_merge(state, rows)
"""

SHAPE_REPLICA_TIERED = """
    import numpy as np

    def pow2_tier(n, floor=1):
        return max(n, floor)

    def jit_merge(state, sl):
        return state

    class Replica:
        def drain(self, msgs, state):
            n = pow2_tier(len(msgs))
            rows = np.full(n, -1, np.int32)
            return jit_merge(state, rows)
"""


def test_shape001_raw_len_operand_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/replica.py": SHAPE_REPLICA_RAW})
    found = [f for f in lint(pkg) if f.rule == "SHAPE001"]
    assert len(found) == 1 and "jit_merge" in found[0].message


def test_shape001_tiered_operand_clean(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/replica.py": SHAPE_REPLICA_TIERED})
    assert [f for f in lint(pkg) if f.rule.startswith("SHAPE")] == []


def test_shape001_pad_fn_lanes_discipline(tmp_path):
    """``stack_entry_slices`` lanes= must be tier-derived; a raw
    ``len()`` (or omitting lanes entirely) is red."""
    fleet = """
        def pow2_tier(n, floor=1):
            return max(n, floor)

        def stack_entry_slices(slices, lanes=None):
            return slices, 0

        class Fleet:
            def dispatch(self, members):
                sl, _ = stack_entry_slices(
                    [m.sl for m in members], lanes={lanes}
                )
                return sl
    """
    red_raw = make_pkg(
        tmp_path / "raw",
        {"runtime/fleet.py": fleet.format(lanes="len(members)")},
    )
    found = [f for f in lint(red_raw) if f.rule == "SHAPE001"]
    assert len(found) == 1 and "raw data-dependent size" in found[0].message

    green = make_pkg(
        tmp_path / "tiered",
        {"runtime/fleet.py": fleet.format(lanes="pow2_tier(len(members), floor=2)")},
    )
    assert [f for f in lint(green) if f.rule.startswith("SHAPE")] == []

    omitted = fleet.replace(", lanes={lanes}", "").replace("\n                )", ")")
    red_omit = make_pkg(tmp_path / "omit", {"runtime/fleet.py": omitted})
    found = [f for f in lint(red_omit) if f.rule == "SHAPE001"]
    assert len(found) == 1 and "without lanes=" in found[0].message


def test_shape001_unpadded_stack_flagged(tmp_path):
    """A list stacked by ``stack_pytrees`` must be tier-padded in
    scope; the ``lst += [lst[0]] * (lanes - len(lst))`` idiom (with a
    sanitised tier) is the green form."""
    fleet = """
        def pow2_tier(n, floor=1):
            return max(n, floor)

        def jit_stack_pytrees(*trees):
            return trees

        class Fleet:
            def tick(self, items):
                leaves = [e.leaf for e in items]
{pad}
                return jit_stack_pytrees(*leaves)
    """
    red = make_pkg(tmp_path / "red", {"runtime/fleet.py": fleet.format(pad="")})
    found = [f for f in lint(red) if f.rule == "SHAPE001"]
    assert len(found) == 1 and "never padded" in found[0].message

    pad = (
        "                lanes = pow2_tier(len(items), floor=2)\n"
        "                leaves += [leaves[0]] * (lanes - len(items))"
    )
    green = make_pkg(tmp_path / "green", {"runtime/fleet.py": fleet.format(pad=pad)})
    assert [f for f in lint(green) if f.rule.startswith("SHAPE")] == []


def test_shape002_static_arg_vocabulary(tmp_path):
    """Static args at jit call sites come from the closed geometry
    vocabulary: tier calls, constants, geometry attributes, forwarded
    params — a raw ``len()`` static is red."""
    mod = """
        import jax

        def pow2_tier(n, floor=1):
            return max(n, floor)

        def extract(state, rows, lanes):
            return state

        jit_extract = jax.jit(extract, static_argnames=("lanes",))

        def ship(state, rows, msgs):
            return jit_extract(state, rows, lanes={lanes})
    """
    red = make_pkg(
        tmp_path / "red", {"models/hash_store.py": mod.format(lanes="len(msgs)")}
    )
    found = [f for f in lint(red) if f.rule == "SHAPE002"]
    assert len(found) == 1 and "lanes=" in found[0].message

    for i, lanes in enumerate(
        ("pow2_tier(len(msgs))", "32", "state.table_size * 2")
    ):
        green = make_pkg(
            tmp_path / f"green{i}",
            {"models/hash_store.py": mod.format(lanes=lanes)},
        )
        assert [f for f in lint(green) if f.rule.startswith("SHAPE")] == [], lanes


def test_shape_allow_tag(tmp_path):
    """The ``shape`` family tag suppresses with a stated why."""
    annotated = SHAPE_REPLICA_RAW.replace(
        "            return jit_merge(state, rows)",
        "            # crdtlint: allow[shape] one-shot recovery path:\n"
        "            # runs once per boot, recompiles are irrelevant\n"
        "            return jit_merge(state, rows)",
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": annotated})
    new, _baselined, allowed = run_lint([pkg])
    assert new == []
    assert {f.rule for f in allowed} == {"SHAPE001"}


# ----------------------------------------------------------------------
# LEAK001 — buffer-pinning closure captures (ISSUE 12)


#: ``{body}`` lines use ABSOLUTE indentation matching the template
#: (drain statements at 12, nested closure bodies at 16, sibling
#: methods at 8) — dedent strips the common 4-space prefix.
LEAK_REPLICA = """
    def jit_merge_rows(state, sl):
        return state

    class Replica:
        def __init__(self):
            self._defer = []
            self._state = None

        def drain(self, sl):
            res = jit_merge_rows(self._state, sl)
{body}
"""


def test_leak001_escaping_whole_result_flagged(tmp_path):
    body = (
        "            def emit():\n"
        "                return res.n_inserted\n"
        "            self._defer.append(emit)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    found = [f for f in lint(pkg) if f.rule == "LEAK001"]
    assert len(found) == 1
    assert "res" in found[0].message and "default-arg capture" in found[0].message


def test_leak001_default_arg_narrowing_clean(tmp_path):
    """The PR 9 fix idiom: default-arg capture of just the count leaves
    is green — defaults evaluate at def time, res is never held."""
    body = (
        "            def emit(ins=res.n_inserted, kill=res.n_killed):\n"
        "                return ins + kill\n"
        "            self._defer.append(emit)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    assert [f for f in lint(pkg) if f.rule == "LEAK001"] == []


def test_leak001_heavy_default_still_flagged(tmp_path):
    """``r=res`` / ``s=res.state`` as a default re-widens the capture —
    the default holds the whole pytree exactly like free capture."""
    body = (
        "            def emit(s=res.state):\n"
        "                return s\n"
        "            self._defer.append(emit)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    found = [f for f in lint(pkg) if f.rule == "LEAK001"]
    assert len(found) == 1 and "res.state" in found[0].message


def test_leak001_interprocedural_deferrer(tmp_path):
    """A closure handed to a method that parks its parameter (the
    ``_note_state_changed`` shape) escapes one call down — the
    storing-parameter fix point must see through the indirection."""
    body = (
        "            def emit():\n"
        "                return res\n"
        "            self._note(emit)\n"
        "\n"
        "        def _note(self, count_fn):\n"
        "            self._defer.append(count_fn)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    found = [f for f in lint(pkg) if f.rule == "LEAK001"]
    assert len(found) == 1 and "_note" in found[0].message


def test_leak001_self_state_capture_flagged(tmp_path):
    body = (
        "            def emit():\n"
        "                return self._state\n"
        "            self._callback = emit"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    found = [f for f in lint(pkg) if f.rule == "LEAK001"]
    assert len(found) == 1 and "self._state" in found[0].message


def test_leak001_local_closure_clean(tmp_path):
    """A closure that never escapes (called inline, handed to an
    immediately-applied combinator) may capture anything."""
    body = (
        "            def pick(lane):\n"
        "                return res\n"
        "            return pick(0)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    assert [f for f in lint(pkg) if f.rule == "LEAK001"] == []


def test_leak001_factory_result_escape(tmp_path):
    """A closure factory's call result carries the inner closure's
    captures into the sink (the fleet ``counts_for`` shape)."""
    body = (
        "            def make(lane):\n"
        "                def fn():\n"
        "                    return res\n"
        "                return fn\n"
        "            self._note(make(0))\n"
        "\n"
        "        def _note(self, count_fn):\n"
        "            self._defer.append(count_fn)"
    )
    pkg = make_pkg(tmp_path, {"runtime/replica.py": LEAK_REPLICA.format(body=body)})
    found = [f for f in lint(pkg) if f.rule == "LEAK001"]
    assert len(found) == 1 and "make" in found[0].message


def test_leak001_cold_module_clean(tmp_path):
    """The rule is a hot-path (replica/fleet) contract — a storage
    module parking closures is not its business."""
    body = (
        "        def emit():\n"
        "            return res\n"
        "        self._defer.append(emit)"
    )
    pkg = make_pkg(
        tmp_path, {"runtime/storage.py": LEAK_REPLICA.format(body=body)}
    )
    assert [f for f in lint(pkg) if f.rule == "LEAK001"] == []


# ----------------------------------------------------------------------
# SPMD001 — shard_map readiness of transition-contract modules


def test_spmd001_host_callback_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import jax

        def fleet_step(states):
            jax.debug.print("x {s}", s=states)
            return states
    """})
    found = [f for f in lint(pkg) if f.rule == "SPMD001"]
    assert len(found) == 1 and "host callback" in found[0].message


def test_spmd001_axis_branch_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        def fleet_step(states):
            if states.key.shape[0] > 4:
                return states
            return states
    """})
    found = [f for f in lint(pkg) if f.rule == "SPMD001"]
    assert len(found) == 1 and "shard" in found[0].message


def test_spmd001_axis_free_reduction_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import jax.numpy as jnp

        def fleet_total(states):
            return jnp.sum(states)
    """})
    found = [f for f in lint(pkg) if f.rule == "SPMD001"]
    assert len(found) == 1 and "axis-free reduction" in found[0].message


def test_spmd001_vmapped_and_axised_forms_clean(tmp_path):
    """Reductions inside vmapped inner functions are per-lane; explicit
    axis= names the folded axes — both survive the mesh lift."""
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import jax
        import jax.numpy as jnp

        def fleet_total(states):
            per_lane = jax.vmap(lambda s: jnp.sum(s))(states)
            return jnp.sum(states, axis=1)
    """})
    assert [f for f in lint(pkg) if f.rule == "SPMD001"] == []


def test_spmd001_cold_module_clean(tmp_path):
    """Host callbacks in the I/O shell are the shell's business."""
    pkg = make_pkg(tmp_path, {"runtime/replica.py": """
        import jax

        def drive(states):
            jax.debug.print("x {s}", s=states)
            return states
    """})
    assert [f for f in lint(pkg) if f.rule == "SPMD001"] == []


def test_spmd001_mesh_twin_axis_free_reduction_flagged(tmp_path):
    """ISSUE 13: the ``mesh_`` prefix joins the axis-function contract —
    an axis-free reduction inside a mesh-lifted kernel folds only the
    local shard and must be red."""
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import jax.numpy as jnp

        def mesh_fleet_total(mesh, states):
            return jnp.sum(states)
    """})
    found = [f for f in lint(pkg) if f.rule == "SPMD001"]
    assert len(found) == 1 and "axis-free reduction" in found[0].message


def test_spmd001_mesh_twin_axis_branch_flagged(tmp_path):
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        def mesh_fleet_step(mesh, states):
            if states.key.shape[0] > 4:
                return states
            return states
    """})
    found = [f for f in lint(pkg) if f.rule == "SPMD001"]
    assert len(found) == 1 and "shard" in found[0].message


def test_spmd001_mesh_rotate_shape_clean(tmp_path):
    """The delivery-plane rotate shape is green: the permutation is
    built from the mesh's static size (no branch), and the per-column
    permute lives in a nested def (traces with its parent)."""
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import jax

        def mesh_plane_rotate(mesh, shift, buffers):
            n = mesh.devices.size
            perm = [(i, (i + shift) % n) for i in range(n)]

            def rotate(tree):
                return jax.tree.map(
                    lambda a: jax.lax.ppermute(a, "replicas", perm), tree
                )

            return rotate(buffers)
    """})
    assert [f for f in lint(pkg) if f.rule == "SPMD001"] == []


def test_sync001_mesh_twin_is_jit_entry_root(tmp_path):
    """ISSUE 13 satellite: the shard_map wrappers live in the
    transition-contract module, so every mesh twin is a SYNC001 jit
    entry root by contract — a host sync snuck into one is red without
    any caller tracing it."""
    pkg = make_pkg(tmp_path, {"runtime/transition.py": """
        import numpy as np

        def mesh_fleet_probe(mesh, states):
            return np.asarray(states)
    """})
    found = [f for f in lint(pkg) if f.rule == "SYNC001"]
    assert len(found) == 1 and "numpy array" in found[0].message


# ----------------------------------------------------------------------
# ISSUE 12 acceptance: the new families catch real-tree regressions
# (engine overlay, working tree untouched)


def test_mutation_fleet_pad_deleted_is_caught():
    """Deleting the pow2 pad at the REAL fleet bucket stack site turns
    the gate red (SHAPE001): exact member counts mint one executable
    per occupancy."""
    rel = f"{PKG}/runtime/fleet.py"
    old = (
        "        lanes = self._lane_tier(n)\n"
        "        sl, real_rows = stack_entry_slices"
    )
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            old, "        lanes = n\n        sl, real_rows = stack_entry_slices"
        ),
    )
    assert any(
        f.rule == "SHAPE001" and "stack_entry_slices" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_egress_tree_pad_deleted_is_caught():
    """Deleting the egress tree-group pad (PR 10's review fix) is also
    red — the batched periodic path would recompile per due-set size."""
    rel = f"{PKG}/runtime/fleet.py"
    old = (
        "            leaves = [e.state.leaf for e in items]\n"
        "            leaves += [leaves[0]] * (lanes - len(items))"
    )
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(old, "            leaves = [e.state.leaf for e in items]"),
    )
    assert any(
        f.rule == "SHAPE001" and "jit_stack_pytrees" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_rewidened_deferred_closure_is_caught():
    """ISSUE 12 acceptance: re-widening the REAL grouped-commit count
    lambda to capture ``res`` (the PR 9 bug, verbatim) turns the gate
    red (LEAK001) — that bug can never return silently."""
    rel = f"{PKG}/runtime/replica.py"
    old = "            lambda ins=res.n_ins_row, kill=res.n_kill_row: (ins, kill),"
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(old, "            lambda: (res.n_ins_row, res.n_kill_row),"),
    )
    assert any(
        f.rule == "LEAK001" and "res" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_rewidened_fleet_counts_factory_is_caught():
    """Same class at the fleet commit seam: the ``counts_for`` factory
    re-widened to read ``res`` inside the parked inner fn is red."""
    rel = f"{PKG}/runtime/fleet.py"
    old = (
        "        def counts_for(lane, ins_rows=res.n_ins_row, kill_rows=res.n_kill_row):\n"
        "            def fn():\n"
        "                if not counts_cell:\n"
        "                    counts_cell.append(\n"
        "                        _TR_DISPATCH_COUNTS.get((ins_rows, kill_rows))\n"
        "                    )"
    )
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(old, (
            "        def counts_for(lane):\n"
            "            def fn():\n"
            "                if not counts_cell:\n"
            "                    counts_cell.append(\n"
            "                        _TR_DISPATCH_COUNTS.get((res.n_ins_row, res.n_kill_row))\n"
            "                    )"
        )),
    )
    assert any(
        f.rule == "LEAK001" and "counts_for" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_host_callback_in_transition_is_caught():
    """ISSUE 12 acceptance: a host callback injected into the REAL
    transition module turns the gate red (SPMD001) before the
    mesh-sharding PR would trip over it."""
    rel = f"{PKG}/runtime/transition.py"
    inject = (
        "\n\ndef fleet_debug_probe(states):\n"
        '    jax.debug.print("probe {x}", x=states)\n'
        "    return states\n"
    )
    new = _overlay_lint(rel, lambda s: s + inject)
    assert any(
        f.rule == "SPMD001" and "host callback" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_mesh_kernel_axis_free_reduction_is_caught():
    """ISSUE 13 acceptance: an axis-free reduction injected into the
    REAL mesh-lifted merge twin turns the gate red (SPMD001) — under
    shard_map it would fold only the local shard, a silent semantic
    change the static gate must catch before any parity test runs."""
    rel = f"{PKG}/runtime/transition.py"
    old = "    return _lift(mesh, fleet_merge_rows)(states, slices)"
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            old,
            "    gate = states.key.sum()\n"
            "    return _lift(mesh, fleet_merge_rows)(states, slices)",
        ),
    )
    assert any(
        f.rule == "SPMD001" and "axis-free reduction" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_adhoc_static_lanes_is_caught():
    """A novel ad-hoc static arg at the REAL hash dense-extraction site
    is red (SHAPE002) — static values outside the geometry vocabulary
    mint one executable per value."""
    rel = f"{PKG}/models/hash_store.py"
    old = "    return jit.extract_rows_packed(state, rows, lanes=_dense_lanes(counts))"
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            old,
            "    return jit.extract_rows_packed("
            "state, rows, lanes=int(np.asarray(counts).max()) + 1)",
        ),
    )
    assert any(
        f.rule == "SHAPE002" and "lanes=" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_relay_flush_lock_deleted_is_caught():
    """ISSUE 15 acceptance: deleting the relay flush's lock acquisition
    in the REAL replica turns the gate red — the relay pending/counter
    state is replica-lock-guarded, and a lock-free flush is exactly the
    unlocked-counter class LOCK001/RACE hunt for (the relay module's
    state joins the existing thread graph)."""
    rel = f"{PKG}/runtime/replica.py"
    src = (REPO_ROOT / rel).read_text()
    i = src.index("def _relay_flush")
    j = src.index("with self._lock:", i)
    anchor = "with self._lock:"
    mutated = src[:j] + "if True:        " + src[j + len(anchor):]
    new, _, _ = run_lint([REPO_ROOT / PKG], overlay={rel: mutated})
    rules = {f.rule for f in new}
    assert "LOCK001" in rules or "RACE001" in rules, rules


def test_mutation_unguarded_tree_relay_emission_is_caught():
    """ISSUE 15 acceptance: removing the ``has_handlers`` guard on the
    relay flush's TREE_RELAY emission turns the gate red (OBS002) —
    disabled telemetry would rebuild the per-re-emission measurement
    dicts on every flush."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = "if telemetry.has_handlers(telemetry.TREE_RELAY):"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(rel, lambda s: s.replace(anchor, "if True:", 1))
    assert any(
        f.rule == "OBS002" and "TREE_RELAY" in f.message for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_relay_closure_capturing_slice_is_caught():
    """ISSUE 15 acceptance: a relay-flush closure re-widened to capture
    the extracted slice pytree and parked in the drain's deferral list
    turns the gate red (LEAK001) — extraction results hold device
    buffers sliced off the live store generation, the same
    buffer-pinning class as parking a whole MergeRowsResult."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = (
        "                        self._relay_depth_hist[folded] = (\n"
        "                            self._relay_depth_hist.get(folded, 0) + 1\n"
        "                        )\n"
    )
    assert anchor in (REPO_ROOT / rel).read_text()
    inject = anchor + (
        "                    if self._telemetry_defer is not None:\n"
        "                        self._telemetry_defer.append(\n"
        "                            (lambda: sl, lambda _x: None)\n"
        "                        )\n"
    )
    new = _overlay_lint(rel, lambda s: s.replace(anchor, inject, 1))
    assert any(
        f.rule == "LEAK001" and "_relay_flush" in f.message for f in new
    ), "\n".join(f.render() for f in new)


# ----------------------------------------------------------------------
# TRANSFER001/TRANSFER002 — device↔host transfer-boundary audit


TRANSFER_HOT = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fixpkg.utils import transfers

    _TR_PROBE = transfers.register("replica.probe")


    def sink(y):
        # keeps the probe handle non-ghost regardless of the body under
        # test — the fixtures probe TRANSFER001 one crossing at a time
        return _TR_PROBE.get(y)


    def ship(x):
        dev = jnp.zeros((4,))
        {body}
"""


_TRANSFER_CASE = iter(range(1 << 20))


def _transfer_lint(tmp_path, body: str) -> list:
    pkg = make_pkg(
        tmp_path / f"case{next(_TRANSFER_CASE)}",
        {"runtime/replica.py": TRANSFER_HOT.format(body=body)},
    )
    return [f for f in lint(pkg) if f.rule.startswith("TRANSFER")]


def test_transfer_raw_device_get_flagged_audited_site_clean(tmp_path):
    new = _transfer_lint(tmp_path, "return jax.device_get(dev)")
    assert rules_of(new) == {"TRANSFER001"}
    assert "route the crossing through an audited transfer site" in new[0].message
    new = _transfer_lint(tmp_path, "return _TR_PROBE.get(dev)")
    assert new == []


def test_transfer_raw_device_put_flagged_audited_put_clean(tmp_path):
    new = _transfer_lint(tmp_path, "return jax.device_put(np.asarray(x))")
    assert rules_of(new) == {"TRANSFER001"}
    new = _transfer_lint(tmp_path, "return _TR_PROBE.put(np.asarray(x))")
    assert new == []


def test_transfer_np_asarray_on_device_value_flagged_host_clean(tmp_path):
    new = _transfer_lint(tmp_path, "return np.asarray(dev)")
    assert rules_of(new) == {"TRANSFER001"}
    assert "unaudited crossing" in new[0].message
    # np.asarray of a host value is host work, not a crossing
    new = _transfer_lint(tmp_path, "return np.asarray([1, 2, 3])")
    assert new == []
    # the audited helper form is the counted path
    new = _transfer_lint(
        tmp_path, "return transfers.audited_get(dev, _TR_PROBE)"
    )
    assert new == []


def test_transfer_item_int_and_iteration_flagged_static_shape_clean(tmp_path):
    new = _transfer_lint(tmp_path, "return dev.item()")
    assert rules_of(new) == {"TRANSFER001"}
    new = _transfer_lint(tmp_path, "return int(dev[0])")
    assert rules_of(new) == {"TRANSFER001"}
    new = _transfer_lint(
        tmp_path, "return [int(v) for v in dev]"
    )
    assert rules_of(new) == {"TRANSFER001"}
    # static shape arithmetic is host metadata, not a crossing
    new = _transfer_lint(tmp_path, "return int(dev.shape[0]) * 2")
    assert new == []


def test_transfer_taint_propagates_and_dies_at_audited_get(tmp_path):
    """Taint flows through assignment chains; an audited fetch kills it,
    so downstream host numpy on the fetched copy stays green."""
    new = _transfer_lint(
        tmp_path,
        "mid = dev * 2\n"
        "        other = mid\n"
        "        return other.tolist()",
    )
    assert rules_of(new) == {"TRANSFER001"}
    new = _transfer_lint(
        tmp_path,
        "host = _TR_PROBE.get(dev * 2)\n"
        "        return host.tolist()",
    )
    assert new == []


def test_transfer_non_hot_module_not_boundary_checked(tmp_path):
    """TRANSFER001 scopes to the hot data-plane leaves — a cold utility
    module may device_get freely (it is not on a ledger-gated path)."""
    pkg = make_pkg(
        tmp_path,
        {"util/helpers.py": """
            import jax

            def peek(x):
                return jax.device_get(x)
        """},
    )
    assert [f for f in lint(pkg) if f.rule == "TRANSFER001"] == []


def test_transfer_ledger_label_hygiene(tmp_path):
    """TRANSFER002 fires on a non-literal label, a duplicate label
    (package-wide), and a ghost handle that audits nothing."""
    pkg = make_pkg(
        tmp_path,
        {
            "runtime/replica.py": """
                from fixpkg.utils import transfers

                _LBL = "replica." + "dyn"
                _TR_DYN = transfers.register(_LBL)
                _TR_DUP = transfers.register("shared.site")
                _TR_GHOST = transfers.register("replica.ghost")


                def use(x):
                    return _TR_DYN.get(_TR_DUP.get(x))
            """,
            "runtime/fleet.py": """
                from fixpkg.utils import transfers

                _TR_ALSO = transfers.register("shared.site")


                def use(x):
                    return _TR_ALSO.get(x)
            """,
        },
    )
    new = [f for f in lint(pkg) if f.rule == "TRANSFER002"]
    msgs = "\n".join(f.message for f in new)
    assert "non-literal label" in msgs
    assert "'shared.site' already registered" in msgs
    assert "ghost label" in msgs
    assert len(new) == 3, msgs


def test_mutation_unshimmed_device_get_in_relay_flush_is_caught():
    """ISSUE 17 acceptance: a raw ``jax.device_get`` snuck into the
    relay flush path of the REAL replica turns the gate red
    (TRANSFER001) — the exact invisible-to-the-ledger crossing class
    the TRANSFER family exists to keep out of the hot modules."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = "                sl = self.model.extract_rows(self.state, jnp.asarray(rows))"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            anchor, anchor + "\n                _dbg = jax.device_get(sl)", 1
        ),
    )
    assert any(
        f.rule == "TRANSFER001" and "device_get" in f.message
        and "_relay_flush" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_audited_wal_fetch_bypass_is_caught():
    """ISSUE 17 acceptance: routing the WAL-entry fetch around its
    audited site in the REAL replica is doubly red — the raw
    ``jax.device_get`` is an unaudited crossing (TRANSFER001) AND the
    orphaned ``_TR_WAL_ENTRIES`` handle becomes a ghost label
    (TRANSFER002): the ledger would still declare the site while
    counting nothing."""
    rel = f"{PKG}/runtime/replica.py"
    anchor = "        got = _TR_WAL_ENTRIES.get(a)"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel, lambda s: s.replace(anchor, "        got = jax.device_get(a)", 1)
    )
    assert any(f.rule == "TRANSFER001" for f in new), new
    assert any(
        f.rule == "TRANSFER002" and "_TR_WAL_ENTRIES" in f.message
        and "ghost" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_meshplane_ledger_bypass_is_caught():
    """ISSUE 17 acceptance: shipping the narrow plane's dense bundle
    with a raw ``jax.device_put`` instead of the audited
    ``_TR_SHIP_DENSE`` site turns the gate red (TRANSFER001 +
    TRANSFER002 ghost) — the retirement evidence in the mesh bench
    diffs exactly this site, so an un-audited ship would silently
    zero the before/after story."""
    rel = f"{PKG}/runtime/meshplane.py"
    anchor = "        shipped = _TR_SHIP_DENSE.put(bundle)"
    assert anchor in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(anchor, "        shipped = jax.device_put(bundle)", 1),
    )
    assert any(
        f.rule == "TRANSFER001" and "device_put" in f.message for f in new
    ), "\n".join(f.render() for f in new)
    assert any(
        f.rule == "TRANSFER002" and "_TR_SHIP_DENSE" in f.message for f in new
    )


def test_mutation_unrestoring_commit_handler_is_caught():
    """ISSUE 20 acceptance: gutting the seq-rollback handler around the
    grouped-entries durability point in the REAL replica turns the gate
    red (FAULT001) — the loop keeps minting ``self._seq += 1`` while an
    injected raise at the fault point would leave the group
    half-advanced with nothing rolling it back."""
    rel = f"{PKG}/runtime/replica.py"
    old = (
        "            except BaseException as e:\n"
        "                self._commit_abort(e)\n"
        "                raise"
    )
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(
            old, "            except BaseException:\n                raise", 1
        ),
    )
    assert any(
        f.rule == "FAULT001" and "_commit_entries_group" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_publish_before_durable_is_caught():
    """ISSUE 20 acceptance: reordering publication ahead of the WAL
    append in the REAL batched-adds commit path turns the gate red
    (FAULT003) — a crash in the window loses work that lock-free
    readers already observed."""
    rel = f"{PKG}/runtime/replica.py"
    old = (
        "        try:\n"
        "            self._durable_batch(batch, ts)\n"
        "        except BaseException as e:\n"
        "            self._commit_abort(e)\n"
        "            raise\n"
        "        self._note_state_changed(lambda: n_changed, maintained)"
    )
    assert old in (REPO_ROOT / rel).read_text()
    swapped = (
        "        self._note_state_changed(lambda: n_changed, maintained)\n"
        "        try:\n"
        "            self._durable_batch(batch, ts)\n"
        "        except BaseException as e:\n"
        "            self._commit_abort(e)\n"
        "            raise"
    )
    new = _overlay_lint(rel, lambda s: s.replace(old, swapped, 1))
    assert any(
        f.rule == "FAULT003" and "_flush_batch_adds" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_ghost_fault_site_is_caught():
    """ISSUE 20 acceptance: a SITES vocabulary entry with no faultpoint
    call site is red (FAULT005) — a chaos schedule naming it could
    never trip, so the label set must stay exactly the set of program
    points."""
    rel = f"{PKG}/utils/faults.py"
    old = '    "fleet.loop",'
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel,
        lambda s: s.replace(old, old + '\n    "ghost.site",', 1),
    )
    assert any(
        f.rule == "FAULT005" and "'ghost.site'" in f.message
        and "ghost" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


def test_mutation_nonliteral_faultpoint_label_is_caught():
    """Companion FAULT005 leg: a faultpoint whose label is a variable
    (not a string literal) is red — chaos schedules key on statically
    knowable site names."""
    rel = f"{PKG}/runtime/wal.py"
    old = 'faultpoint("wal.rotate")'
    assert old in (REPO_ROOT / rel).read_text()
    new = _overlay_lint(
        rel, lambda s: s.replace(old, "faultpoint(_SITE_ROTATE)", 1)
    )
    assert any(
        f.rule == "FAULT005" and "not a string literal" in f.message
        for f in new
    ), "\n".join(f.render() for f in new)


# ----------------------------------------------------------------------
# SUPPRESS003 — allow-comment expiry (ISSUE 20)


def test_expired_allow_still_suppresses_through_suppress003(tmp_path):
    """An expired ``allow[tag expires=...]`` keeps suppressing the
    underlying finding — the gate goes red through ONE actionable
    SUPPRESS003 at the comment, not through the original finding
    popping back up at an unrelated line."""
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync expires=2000-01-01] dated
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    new, _baselined, allowed = run_lint([pkg])
    assert rules_of(new) == {"SUPPRESS003"}
    assert "expires=2000-01-01" in new[0].message
    # the original finding routed through the (expired) allow
    assert any(f.rule.startswith("SYNC") for f in allowed)


def test_future_dated_allow_is_quiet(tmp_path):
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync expires=2999-12-31] dated
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    assert lint(pkg) == []


def test_expired_and_stale_allow_reports_only_suppress003(tmp_path):
    """An expired record's SUPPRESS003 subsumes the staleness complaint
    — one actionable finding per comment, not two."""
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            def f(x):
                return x  # crdtlint: allow[donation expires=2000-01-01] old
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SUPPRESS003"}
    assert len(found) == 1


def test_malformed_expiry_date_fails_closed(tmp_path):
    """A typo'd date (month 13) counts as expired — a guard that can
    never expire because of a typo must surface, not silently live
    forever."""
    pkg = make_pkg(
        tmp_path,
        {
            "ops/k.py": """
            import jax

            def probe(f, x):
                # crdtlint: allow[host-sync expires=2026-13-01] typo
                jax.jit(f)(x).block_until_ready()
                return f
            """,
        },
    )
    found = lint(pkg)
    assert rules_of(found) == {"SUPPRESS003"}
    assert "2026-13-01" in found[0].message
