"""chip_smoke.py: its bodies at a tiny size on the CPU, its refusal to run
anywhere but a TPU, and where the compilation cache goes.

The script itself runs on the chip; here its one-chip body serves a tiny
stream through collect → WAL → dispatch → recover, and its sharded body
runs on four virtual CPU devices — both checked answer by answer against
the reference inside the body.
"""
import dataclasses
import io
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import REPO, run_with_devices

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = dict(n_keys=1 << 12, pending_capacity=1 << 9, batch=256)

SHARDED_SCRIPT = r"""
import dataclasses, io, json, sys
sys.path.insert(0, {repo!r})
import jax
import chip_smoke as cs
cfg = dataclasses.replace(cs.FOUR_CHIPS, capacity=1 << 11, **{tiny!r})
out = io.StringIO()
used = jax.devices()[:4]
r = cs.run_sharded(used, cfg, out=out)
print(json.dumps(dict(windows=r["windows"], rebuilds=len(r["rebuilds"]),
                      compiles=r["compiles_served"], points=r["points"],
                      ranges=r["ranges"], placement=r["placement"],
                      visible=len(jax.devices()),
                      result=json.loads(cs.result_line(used)),
                      log=out.getvalue())))
"""


def test_one_chip_body_matches_reference():
    cfg = dataclasses.replace(chip_smoke.ONE_CHIP, capacity=1 << 13, **TINY)
    out = io.StringIO()
    r = chip_smoke.run_one_chip(cfg, out=out)
    log = out.getvalue()
    assert r["windows"] >= 64 and r["rebuilds"]
    assert r["compiles_served"] == 0, log
    assert r["points"] + r["ranges"] == cfg.windows * cfg.batch
    assert r["ranges"] > 0 and "recover(): replayed" in log
    # the result line counts the one device the phase used
    result = json.loads(chip_smoke.result_line(jax.devices()[:1]))
    assert result == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": 1}}


def test_sharded_body_matches_reference_4_devices():
    # more devices visible than used: the result line counts the four
    out = run_with_devices(SHARDED_SCRIPT.format(repo=REPO, tiny=TINY), 8)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["visible"] == 8 and r["result"]["device"]["count"] == 4
    assert r["windows"] >= 64 and r["rebuilds"] > 0
    assert r["compiles"] == 0, r["log"]
    assert r["ranges"] > 0 and "routing drops: 0" in r["log"]
    assert len(set(r["placement"])) == 1  # equal-capacity shards, one each


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_main_refuses_a_host_without_tpu(tmp_path, where):
    """No TPU: non-zero exit and no result line, in the checkout and in a
    directory holding the script and nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


CACHE_SCRIPT = r"""
import json, os, jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, use_compile_cache
before = sorted(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR) else []
d = use_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(11)).block_until_ready()
after = sorted(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR) else []
print(json.dumps(dict(dir=d, config=jax.config.jax_compilation_cache_dir,
                      default=DEFAULT_DIR, default_untouched=before == after)))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["default"] == os.path.join(REPO, ".jax_cache")
    if from_env:
        assert r["dir"] == r["config"] == str(tmp_path)
        assert os.listdir(tmp_path), "nothing was cached in the env's dir"
        assert r["default_untouched"]
    else:
        assert r["dir"] == r["config"] == r["default"]
