"""Shared benchmark helpers: PI index drivers + timing + CSV output.

Paper-fidelity note: dataset sizes default to 2^14..2^18 instead of the
paper's 2M..256M, and the reported metric is query throughput
(queries/s), matching the paper's y-axes.  Every row names the device it
ran on (``emit``); a number from a CPU run is not a TPU measurement.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import data as data_mod
from repro.core import (PIConfig, build, execute, maybe_rebuild, range_agg)


def default_backend() -> str:
    """Engine backend benchmarks run with unless told otherwise.

    ``PI_BACKEND`` (xla | pallas | pallas-interpret) overrides, so every
    figure script can be re-run per backend without edits:
        PI_BACKEND=pallas-interpret python -m benchmarks.run fig7
    """
    return os.environ.get("PI_BACKEND", "xla")


def bench_backends():
    """Backends worth timing side by side on this host.

    On a TPU only ``xla``: the Pallas kernels do not compile for the TPU
    today (DESIGN.md §3) and stay opt-in (``PI_BACKEND=pallas``).  The
    interpreter runs the kernels' grid computation as plain JAX ops and is
    offered only on the CPU, where it is the one way to run them at all.
    """
    if jax.default_backend() == "tpu":
        return ["xla"]
    return ["xla", "pallas-interpret"]


def make_index(n_keys: int, fanout: int = 8, seed: int = 0,
               headroom: float = 2.0, backend: str | None = None):
    cfg = PIConfig(
        capacity=int(n_keys * headroom),
        pending_capacity=max(8192 * 4, int(0.25 * n_keys)),
        fanout=fanout,
        backend=backend or default_backend())
    ycfg = data_mod.YCSBConfig(n_keys=n_keys, seed=seed)
    keys, vals = data_mod.ycsb_dataset(ycfg)
    return build(cfg, jnp.asarray(keys), jnp.asarray(vals)), keys, ycfg


@jax.jit
def _one_batch(idx, ops, keys, vals):
    idx, res = execute(idx, ops, keys, vals)
    return maybe_rebuild(idx), res


def replay_stream(disp, col, stream, *, bulk: bool = True,
                  chunk: int | None = None, clock=time.perf_counter):
    """Saturation-replay an ArrivalStream through collector + dispatcher.

    The shared driver loop for every pipeline benchmark/example: arrivals
    are stamped with ``clock`` at admission and pushed as fast as the
    window admits.  ``bulk=True`` admits via ``Collector.offer_many`` one
    ``chunk`` at a time (default: one window's worth, so window formation
    for chunk k+1 overlaps the device executing chunk k); ``bulk=False``
    is the per-arrival ``offer`` loop — the pre-vectorization baseline the
    admission benchmark compares against.  Returns every retired
    ``WindowResult`` in retirement order.
    """
    if bulk:
        return disp.run(stream, collector=col, chunk=chunk, clock=clock)
    retired = []
    submit, take = disp.submit, col.take
    # python ints: the admission loop is the host-side cost under test
    # and numpy scalar boxing would double it
    ops, keys, vals = (stream.ops.tolist(), stream.keys.tolist(),
                       stream.vals.tolist())
    k2 = getattr(stream, "keys2", None)
    keys2 = k2.tolist() if k2 is not None else [0] * len(stream)
    offer = col.offer
    for i in range(len(stream)):
        while not offer(clock(), ops[i], keys[i], vals[i], i,
                        key2=keys2[i]):
            retired += submit(take(clock()))
    tail = take(clock())
    if tail is not None:
        retired += submit(tail)
    retired += disp.flush()
    return retired


def run_query_stream(idx, ycfg, keys, n_batches: int, warmup: int = 2):
    """Throughput of a YCSB query stream (queries/s)."""
    batches = [data_mod.ycsb_batch(ycfg, keys, step) for step in
               range(n_batches + warmup)]
    batches = [tuple(jnp.asarray(a) for a in b) for b in batches]
    for b in batches[:warmup]:
        idx, res = _one_batch(idx, *b)
    jax.block_until_ready(res)
    t0 = time.perf_counter()
    for b in batches[warmup:]:
        idx, res = _one_batch(idx, *b)
    jax.block_until_ready(res)
    dt = time.perf_counter() - t0
    qps = ycfg.batch * n_batches / dt
    return qps, idx


def emit(rows, header, fig=None, config=None):
    """Print the CSV block and write ``BENCH_<fig>.json`` next to it.

    The JSON side channel records rows + header verbatim, plus the engine
    backend, the device the rows were measured on (platform, kind and
    count, as JAX reports them) and whatever scenario config the figure
    wants recorded.  ``fig`` defaults to the
    first column of the first row (every figure script tags rows that
    way); ``BENCH_DIR`` overrides the output directory (default: cwd).
    """
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    if fig is None and rows:
        fig = str(rows[0][0])
    if fig:
        payload = {
            "fig": fig,
            "backend": default_backend(),
            "jax_backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "timestamp": time.time(),
            "header": list(header),
            "rows": [list(r) for r in rows],
            "config": config or {},
        }
        path = os.path.join(os.environ.get("BENCH_DIR", "."),
                            f"BENCH_{fig}.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        print(f"[emit] wrote {path}")
    return rows
