"""The harness at a tiny size on the CPU: finding cells by name, the
result line, and refusing to run without the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, ycsb
from bench.tests.conftest import ROOT, tiny

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c, entry = harness.find_cell(BENCH, cell)
    cfg = harness.load_config(entry)
    assert entry["file"].startswith("bench/configs/")
    mix = ycsb.load_traffic(c["traffic"])
    assert mix["loop"] == "closed" and mix["threadcount"] > 0
    assert int(cfg.get("shards", 1)) == c["chips"] or c["chips"] == 1
    e2e = harness.metrics_for(BENCH, cell, False)
    layer = harness.metrics_for(BENCH, cell, True)
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2 and layer
    for m in e2e + layer:
        assert callable(harness.reader(m["name"]))


def test_every_file_under_paths_is_found_by_a_name():
    """Configs, mixes and readers on disk are all named in the JSON."""
    named = {e["file"] for e in BENCH["configs"]}
    on_disk = {f"bench/configs/{f}" for f in
               os.listdir(os.path.join(ROOT, "bench", "configs"))}
    assert on_disk == named
    mixes = {c["traffic"] for c in BENCH["workloads"]}
    assert {f[:-5] for f in os.listdir(ycsb.TRAFFIC_DIR)} == mixes
    metrics = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    readers = {f[:-3] for f in os.listdir(harness.METRICS_DIR)
               if f.endswith(".py")}
    assert readers == metrics


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_result_line(cell, tiny_run):
    result = tiny_run(cell)
    line = json.loads(json.dumps(result))
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"]: m["unit"] for m in
            harness.metrics_for(BENCH, cell, False)}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert set(line["checks"]) == set(harness.check.LIMITS)


def test_tiny_sharded_run_on_four_devices():
    script = (
        "import sys, json; sys.path[:0] = ['src', '.']\n"
        "from bench.tests.conftest import run_tiny\n"
        "r = run_tiny('ycsb64m-wal.ycsb-a', shards=4)\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4


def test_no_tpu_means_no_result(capsys):
    from bench import run
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


def test_benchmark_alone_is_not_enough(tmp_path):
    """A directory with only BENCHMARK.json and bench/ gives no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_importing_the_benchmark_loads_no_accelerator_library():
    mods = ("bench.run", "bench.harness", "bench.trace", "bench.check",
            "bench.reference", "bench.roofline", "bench.ycsb")
    script = ("import sys\n" + "".join(f"import {m}\n" for m in mods)
              + "print(sorted(m for m in sys.modules if m == 'jax' or "
                "m.startswith(('jax.', 'jaxlib', 'libtpu'))))\n")
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"
