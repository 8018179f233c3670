"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's compiler refuses here what interpret mode and the
CPU backend accept (block shapes, SMEM layouts, i64 operands under x64).

The real widths are those of a 1M-key replica: ``L = 2**14`` sync
buckets, bin capacity 128, 8 writer slots — and for the hash store a
2**21-lane table. Nothing runs; a compile that passes is not a chip run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.models.hash_store import HashStore
from delta_crdt_ex_tpu.ops import binned as binned_ops
from delta_crdt_ex_tpu.ops import hash_map
from delta_crdt_ex_tpu.ops.pallas_tree import batched_roots_pallas
from delta_crdt_ex_tpu.runtime import transition
from delta_crdt_ex_tpu.utils.devices import fleet_mesh

L, B, R = 1 << 14, 128, 8
ROWS = 256  # pow4 wire tier of a max_sync_size=200 slice


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # no fallback: a topology that cannot be described fails every case
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n,leaves", [(2, 128), (64, L)])
def test_batched_roots_pallas_compiles(one_chip, n, leaves):
    x = jax.ShapeDtypeStruct((n, leaves), jnp.uint32, sharding=one_chip)
    compiled = _compile(batched_roots_pallas, x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "queries,window", [(2, 32), (1024, 32), (4096, 32), (1 << 20, 32), (300, 256)]
)
def test_probe_winners_compiles(one_chip, queries, window):
    st = jax.eval_shape(lambda: HashStore.new(L, B, R, probe_window=window))
    assert st.table_size == 1 << 21
    k = jax.ShapeDtypeStruct((queries,), jnp.uint64, sharding=one_chip)
    compiled = _compile(hash_map.probe_winners, _shapes(st, one_chip), k)
    assert "tpu_custom_call" in compiled.as_text()


def _hash_args(name, sharding):
    st = _shapes(jax.eval_shape(lambda: HashStore.new(L, B, R)), sharding)
    rows = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=sharding)
    if name == "winner_all":
        return (st,), {}
    if name == "extract_rows_packed":
        return (st, rows), {"lanes": B}
    sl = jax.eval_shape(lambda s, r: hash_map.extract_rows_packed(s, r, B), st, rows)
    return (st, _shapes(sl, sharding)), {}


@pytest.mark.parametrize("name", ["winner_all", "extract_rows_packed", "merge_rows"])
def test_hash_gossip_roots_sort_no_whole_table(one_chip, name):
    """The hash store's gossip and full-read roots compile for the chip
    with no sort over the whole table: a TPU sort of 2**21 lanes takes
    minutes to compile, one over a slice's entries seconds."""
    args, kw = _hash_args(name, one_chip)
    fn = getattr(hash_map, name)
    compiled = jax.jit(fn, static_argnames=tuple(kw)).lower(*args, **kw).compile()
    sorts = [ln for ln in compiled.as_text().splitlines() if " sort(" in ln]
    assert not any(f"[{1 << 21}]" in ln for ln in sorts), sorts


def _binned_args(name, sharding):
    st = _shapes(jax.eval_shape(lambda: BinnedStore.new(L, B, R)), sharding)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    if name == "merge_rows":
        sl = jax.eval_shape(binned_ops.extract_rows, st, i32(ROWS))
        return st, _shapes(sl, sharding)
    if name == "row_apply":
        m = 8
        grid = lambda dt: jax.ShapeDtypeStruct((ROWS, m), dt, sharding=sharding)
        return (st, i32(), i32(ROWS), grid(jnp.int32), grid(jnp.uint64),
                grid(jnp.uint32), grid(jnp.int64))
    return st, jax.ShapeDtypeStruct((1024,), jnp.uint64, sharding=sharding)


@pytest.mark.parametrize("name", ["merge_rows", "row_apply", "winners_for_keys"])
def test_binned_roots_compile(one_chip, name):
    compiled = _compile(getattr(binned_ops, name), *_binned_args(name, one_chip))
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_mesh_fleet_merge_rows_compiles(topo):
    mesh = fleet_mesh(4, devices=topo.devices)
    lanes = NamedSharding(mesh, PartitionSpec("replicas"))
    st, sl = _binned_args("merge_rows", None)
    stack = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((8, *s.shape), s.dtype, sharding=lanes), tree
    )
    compiled = transition.jit_mesh_fleet_merge_rows.lower(
        mesh, stack(st), stack(sl)
    ).compile()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(
        np.prod(s.shape) * s.dtype.itemsize for s in jax.tree.leaves((stack(st), stack(sl)))
    )
    # each device holds its own quarter of the 8 lanes, not all of them
    assert per_device * 4 <= whole * 1.01
