"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh
axis via shard_map + collective_permute.

Completes the parallelism matrix (DP/FSDP/TP/EP/SP live in sharding.py;
PP lives here): layers are split into S stages along a mesh axis
("pod" on the multi-pod mesh — DCI crossings become one boundary
activation permute per microbatch, the classic reason to map PP to the
slowest link), and M ≥ S microbatches stream through with the standard
GPipe schedule (bubble fraction (S−1)/(M+S−1)).

The implementation is the rotating-buffer shard_map formulation (as in
praxis/MaxText): each step every stage runs its layer block on its
current microbatch slot, then activations rotate one stage forward with
``collective_permute``; outputs accumulate on the last stage.  The loop
body is one compiled step → HLO stays compact (scan over steps).

`pipelined_forward` is generic over a per-stage apply function, so dense /
MoE / SSM stage blocks all work; tests validate S×M grids against the
unpipelined reference on a forced-host-device mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def pipelined_forward(mesh: Mesh, axis: str, stage_fn: Callable,
                      stage_params, x_microbatches):
    """Run M microbatches through S pipeline stages.

    Args:
      mesh/axis: the mesh axis carrying stages (size S).
      stage_fn:  (stage_params_for_one_stage, x) → x  (one stage's layers).
      stage_params: pytree with leading dim S on every leaf.
      x_microbatches: (M, mb, ...) activations, M ≥ S.

    Returns (M, mb, ...) outputs, numerically identical to applying the
    stages sequentially to each microbatch.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    assert M >= S, f"need at least S={S} microbatches, got {M}"
    n_steps = M + S - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def body(params, xs):
        # params leaves: (1, ...) block for this stage; xs: (M, mb, ...)
        p_local = jax.tree.map(lambda a: a[0], params)
        sidx = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)          # current slot
        out = jnp.zeros_like(xs)

        def step(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (if still available)
            feed = xs[jnp.clip(t, 0, M - 1)]
            buf = jnp.where((sidx == 0) & (t < M), feed, buf)
            y = stage_fn(p_local, buf)
            # last stage emits microbatch t-(S-1)
            emit = t - (S - 1)
            out = jax.lax.cond(
                (sidx == S - 1) & (emit >= 0) & (emit < M),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(emit, 0, M - 1), 0),
                lambda o: o, out)
            # rotate activations one stage forward
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out),
                                     jnp.arange(n_steps))
        # results live on the last stage; broadcast to all (psum of
        # one-hot contribution keeps it collective-clean)
        contrib = jnp.where(sidx == S - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(contrib, axis)

    specs_p = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs_p, P()), out_specs=P(),
        check_vma=False)(stage_params, x_microbatches)


def stage_split(params, n_stages: int):
    """Reshape (L, ...) stacked layer params to (S, L/S, ...)."""
    def r(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(r, params)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
