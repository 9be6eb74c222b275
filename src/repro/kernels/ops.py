"""Jitted public wrappers for the Pallas kernels.

The caller chooses ``interpret``: ``True`` runs the kernel body as plain
JAX ops (the only mode the CPU backend supports), validating the exact
computation the TPU grid would run; ``False`` compiles it via Mosaic.
Nothing here guesses the mode from the backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.pi_search import pi_search
from repro.kernels.bitonic_sort import bitonic_sort


@partial(jax.jit, static_argnames=("fanout", "tile_q", "interpret"))
def pi_search_op(storage: jnp.ndarray, queries: jnp.ndarray,
                 fanout: int = 8, tile_q: int = 256, *,
                 interpret: bool) -> jnp.ndarray:
    """Floor positions of `queries` in the sorted padded `storage` array."""
    return pi_search(storage, queries, fanout=fanout, tile_q=tile_q,
                     interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_op(keys: jnp.ndarray, vals: jnp.ndarray, *,
                    interpret: bool):
    """Ascending (key, val) lexicographic sort of a power-of-two batch."""
    return bitonic_sort(keys, vals, interpret=interpret)


def sort_queries_kernel(ops: jnp.ndarray, keys: jnp.ndarray,
                        vals: jnp.ndarray, *, interpret: bool):
    """Paper Def. 3: sort a query batch by key, stable on arrival order.

    Packs the arrival index into the tie-break lane so the bitonic network
    reproduces a stable sort, then unpacks the permutation and applies it
    to the full (op, key, val) triplet.
    """
    B = keys.shape[0]
    arrival = jnp.arange(B, dtype=jnp.int32)
    _, perm = bitonic_sort_op(keys, arrival, interpret=interpret)
    return perm, ops[perm], keys[perm], vals[perm]
