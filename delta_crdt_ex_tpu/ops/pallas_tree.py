"""Pallas TPU kernels: fused digest-tree folds over leaf digests.

The digest tree (the ``MerkleMap.update_hashes`` analog,
``causal_crdt.ex:254``) folds the maintained leaf digests into parent
levels. The XLA version materialises every level as a separate HBM
array with one fusion per level — log2(L) kernel launches and HBM round
trips. The whole working set is tiny (a replica's leaf array at
L = 2^14 is 64 KB), so a Pallas kernel can keep the entire fold in VMEM.

**The production kernel is** :func:`batched_roots_pallas` (what
:func:`batched_roots_fn` selects on TPU and the bench uses): a roll-based
strided fold over (8, L) blocks that computes one root per tree of a
batch in a single launch. It is shaped around Mosaic's TPU constraints
— 8-row blocks, full-width rolls, no reshapes (a round-1 (1, L)-block
packed-levels kernel never lowered on real TPUs and was removed; this
one exists because of that lesson). The combine mix matches
:func:`delta_crdt_ex_tpu.ops.binned.tree_from_leaves` bit for bit, so
either implementation can serve the sync walk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# plain ints (not traced jnp scalars): pallas kernels may not capture
# array constants, and uint32 arithmetic promotes python ints exactly
_P1 = 0x85EBCA6B
_P2 = 0xC2B2AE35
_GOLDEN = 0x9E3779B9


def _mix32(x):
    x = (x ^ (x >> 16)) * jnp.uint32(_P1)
    x = (x ^ (x >> 13)) * jnp.uint32(_P2)
    return x ^ (x >> 16)


def _combine(left, right):
    return (
        _mix32(left ^ jnp.uint32(_P1))
        + (_mix32(right ^ jnp.uint32(_P2)) << 1)
        + jnp.uint32(_GOLDEN)
    )


def _roots_kernel(leaf_ref, out_ref):
    """Strided in-place fold: after step s, the value of level-(depth-s)
    node i sits at lane ``i * 2**s`` (other lanes hold garbage that no
    later step reads). Every step is a full-width roll + combine — no
    reshapes or strided slices, which Mosaic's TPU lowering rejects or
    relayouts; the roll is a native lane rotation. Root lands in lane 0."""
    from jax.experimental.pallas import tpu as pltpu

    cur = leaf_ref[...]  # [NB, L]
    L = cur.shape[1]
    k = 1
    while k < L:
        # shifted[i] = cur[i + k]  (pltpu.roll wants non-negative shifts,
        # and an int32 one: a Python int would trace as i64 under x64)
        shifted = pltpu.roll(cur, jnp.int32(L - k), 1)
        cur = _combine(cur, shifted)
        k *= 2
    out_ref[...] = cur[:, : out_ref.shape[1]]


def batched_roots_pallas(leaf: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Tree roots ``uint32[N]`` for a batch of leaf arrays ``uint32[N, L]``
    in one fused kernel launch, entire fold in VMEM. The batch is padded
    to a multiple of 8 (Mosaic requires the second-to-last block dim be a
    multiple of 8; the round-1 packed-levels kernel used (1, L) blocks and
    never lowered on real TPUs)."""
    import jax
    from jax.experimental import pallas as pl

    n, L = leaf.shape
    if L < 128 or L & (L - 1):
        raise ValueError(
            f"batched_roots_pallas needs a power-of-two L >= 128 (one full "
            f"lane vector), got L={L}; use the XLA fold for smaller trees"
        )
    nb = 8
    n_pad = -(-n // nb) * nb
    if n_pad != n:
        leaf = jnp.concatenate(
            [leaf, jnp.zeros((n_pad - n, L), jnp.uint32)], axis=0
        )
    out = pl.pallas_call(
        _roots_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, 128), jnp.uint32),
        grid=(n_pad // nb,),
        # int32 block indices: a Python 0 would trace as i64 under x64
        in_specs=[pl.BlockSpec((nb, L), lambda i: (i, jnp.int32(0)))],
        out_specs=pl.BlockSpec((nb, 128), lambda i: (i, jnp.int32(0))),
        interpret=interpret,
    )(leaf)
    return out[:n, 0]


def batched_roots_fn(num_leaves: int):
    """A jittable ``uint32[N, L] -> uint32[N]`` batched-roots function,
    chosen by platform: the fused roll-fold kernel on TPU (for trees of
    at least one 128-lane vector), the per-level XLA fold elsewhere.
    Returns ``(fn, tag)``."""
    from delta_crdt_ex_tpu.ops.binned import tree_from_leaves as xla_tree

    if jax.default_backend() == "tpu" and num_leaves >= 128:
        return batched_roots_pallas, "pallas"
    return jax.vmap(lambda lf: xla_tree(lf)[0][0]), "xla"
