"""``correct`` against its control and against planted faults, on the
CPU at a size a test run holds. The harness's look for a chip is
skipped: these drive ``harness.run_cell`` directly.

Faults the join cell's timed path can have: a merge that keeps the
receiver's state, and a merged value altered where the joiner takes it
in. A join that merges half of a slice is repaired by the next digest
walk, so it is a fault of throughput and not of the answer; the cell
exchanges no data between chips.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import pytest

from benchmark import harness

JOIN = "kv30k-binned.join"


def tiny(workload: str) -> dict:
    config, mix_name = workload.split(".", 1)
    spec = {
        "config": harness.read_json(harness.HERE, "configs", config + ".json"),
        "traffic": harness.read_json(harness.HERE, "traffic", mix_name + ".json"),
    }
    cfg, mix = spec["config"], spec["traffic"]
    cfg.update(keys=2048, key_space=8192, load_batch=1024)
    # 32 lanes a bucket, where growth settles for these keys, as in the
    # full-size configuration
    cfg["replica"].update(capacity=8192, tree_depth=8, max_sync_size=16)
    mix.update(stall_s=2)
    return spec


def correct(workload: str, seed: int, seconds: float = 3, **kw) -> bool:
    out = harness.run_cell(tiny(workload), seed, seconds, peaks={}, **kw)
    assert out["compiles"] == 0
    return harness.correct(out["checks"])


@pytest.mark.parametrize("seed", [3_000_000_001, 3_000_000_011, 2**31 + 17])
def test_sound_run_is_correct(seed):
    """Correct, and the window compiles nothing (``correct`` asserts it)
    on seeds whose joins group slices differently."""
    assert correct(JOIN, seed)


def test_control_is_not_correct():
    assert not correct(JOIN, 3_000_000_002, control=True)


def after_setup(monkeypatch, patch):
    """Plant ``patch`` once set-up is done, so the fault sits under the
    measured window and not under the load."""
    orig = harness.window

    def planted(*a, **k):
        patch()
        return orig(*a, **k)

    monkeypatch.setattr(harness, "window", planted)


def test_merge_that_keeps_state_is_not_correct(monkeypatch):
    from delta_crdt_ex_tpu.models.binned_map import BinnedAWLWWMap
    from delta_crdt_ex_tpu.models.hash_store import HashAWLWWMap

    def patch():
        for model in (BinnedAWLWWMap, HashAWLWWMap):
            for name in ("merge_rows_into", "merge_group_into"):
                orig = getattr(model, name)

                def unchanged(state, *a, _orig=orig, **k):
                    out = _orig(state, *a, **k)
                    return (state,) + tuple(out[1:])

                monkeypatch.setattr(model, name, staticmethod(unchanged))

    after_setup(monkeypatch, patch)
    assert not correct(JOIN, 3_000_000_003)


def test_altered_merged_value_is_not_correct(monkeypatch):
    from delta_crdt_ex_tpu.runtime.replica import Replica

    def patch():
        orig = Replica._register_slice_payloads

        def altered(self, payloads):
            if self.name.startswith("joiner"):
                payloads = {d: (t, v + 1 if t % 3 == 0 else v) for d, (t, v) in payloads.items()}
            return orig(self, payloads)

        monkeypatch.setattr(Replica, "_register_slice_payloads", altered)

    after_setup(monkeypatch, patch)
    assert not correct(JOIN, 3_000_000_006)
