"""The check fails what it must: the control, and faults planted in the
timed path (CPU, tiny size).

The control is the program with one stated guarantee broken: the WAL
without fsync (``fsync="off"``), so answers come back before their
window is durable.  The faults break the timed path underneath a run:
the point step returns its state unchanged, leaves half of the batch
out, or alters the answers it produces; the range aggregate alters its
counts; the sharded step leaves out the exchange between chips.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench.tests.conftest import ROOT, run_tiny
from bench.ycsb import SEARCH

A = "ycsb64m-wal.ycsb-a"
E = "ycsb64m-wal.ycsb-e"


def failing(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


def test_control_wal_without_fsync_is_not_correct():
    r = run_tiny(A, fsync="off")
    assert r["correct"] is False
    assert failing(r) == {"unsynced"}


def _patch_step(monkeypatch, fn):
    from repro.pipeline import dispatcher
    orig = dispatcher._step_single
    monkeypatch.setattr(dispatcher, "_step_single",
                        lambda *a: fn(orig, *a))


def test_state_left_unchanged_is_caught(monkeypatch):
    def stuck(orig, index, ops, keys, vals):
        return (index,) + tuple(orig(index, ops, keys, vals)[1:])
    _patch_step(monkeypatch, stuck)
    r = run_tiny(A)
    assert r["correct"] is False
    assert {"point", "live"} <= failing(r)


def test_half_the_batch_left_out_is_caught(monkeypatch):
    def half(orig, index, ops, keys, vals):
        h = ops.shape[0] // 2
        sent = jnp.iinfo(keys.dtype).max
        ops = ops.at[h:].set(SEARCH)
        keys = keys.at[h:].set(sent)
        return orig(index, ops, keys, vals)
    _patch_step(monkeypatch, half)
    r = run_tiny(A)
    assert r["correct"] is False
    assert {"point", "live"} <= failing(r)


def test_altered_answer_is_caught(monkeypatch):
    def altered(orig, index, ops, keys, vals):
        out = list(orig(index, ops, keys, vals))
        out[2] = out[2] + 1                # every slot's value
        return tuple(out)
    _patch_step(monkeypatch, altered)
    r = run_tiny(A)
    assert r["correct"] is False
    assert failing(r) == {"point"}


def test_altered_scan_is_caught(monkeypatch):
    from repro.pipeline import dispatcher
    orig = dispatcher.execute_ranges

    def altered(index, ops, keys, keys2, max_span):
        cnt, sm = orig(index, ops, keys, keys2, max_span)
        return cnt.at[0].add(1), sm
    monkeypatch.setattr(dispatcher, "execute_ranges", altered)
    r = run_tiny(E)
    assert r["correct"] is False
    assert failing(r) == {"scan"}


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_sharded_exchange_left_out_is_caught(fault):
    patch = ("import jax\n"
             "jax.lax.all_to_all = lambda x, *a, **k: x\n"
             if fault == "no_exchange" else "")
    script = (
        "import sys, json; sys.path[:0] = ['src', '.']\n" + patch +
        "from bench.tests.conftest import run_tiny\n"
        "r = run_tiny('ycsb64m-wal.ycsb-a', shards=4)\n"
        "print(json.dumps(r['checks']))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    checks = json.loads(proc.stdout.strip().splitlines()[-1])
    bad = {k for k, c in checks.items() if c["value"] > c["limit"]}
    if fault == "none":
        assert bad == set()
    else:
        assert {"point", "live"} <= bad
