"""One run of one cell: set-up, the closed loop, the check, the result.

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``; the metrics a run reports are the readers under
``metrics/`` named there.  The program is driven only through its
serving entry points: ``repro.core.build`` / ``build_sharded``,
``repro.pipeline.Collector``, ``Durability`` and ``Dispatcher``.

The load is a closed loop, as YCSB's client runs it: ``threadcount``
clients, each with one operation outstanding, issue their next operation
when the previous answer comes back.  Answers come back when the
dispatcher retires a window.  When every client is waiting and the open
window cannot fill, the loop seals it (``Collector.take``), so a window
never waits for arrivals that cannot come.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict

import numpy as np

from bench import check, roofline, trace, ycsb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS_DIR = os.path.join(ROOT, "bench", "metrics")
# a --trace 1 run profiles the first seconds of its window: a trace of
# the whole window takes longer to collect and read than a run may last
TRACE_SECONDS = 10.0


class BenchError(RuntimeError):
    """The benchmark cannot run this cell as asked."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    """(cell, configuration entry) of the workload ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def load_config(entry: dict) -> dict:
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, trace_on: bool):
    """The metric entries a run of ``cell`` reports."""
    group = bench["per_layer"] if trace_on else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chip_devices(n: int):
    """The first ``n`` TPU chips, with JAX's persistent compilation cache
    in use (``repro.compile_cache``) for every program, the eager build's
    small ones too; ``BenchError`` without them."""
    import jax
    from repro.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise BenchError(f"needs {n} chips; JAX found {len(devices)}")
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices[:n]


# ---------------------------------------------------------------------------
# instrumentation: spans, compilations, fsyncs
# ---------------------------------------------------------------------------

class Spans:
    """Host time per benchmark span; with tracing on, each span is also a
    ``TraceAnnotation`` named ``bench.<span>`` in the profiler's trace."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.total = defaultdict(float)
        self.longest = defaultdict(float)
        self.on = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.tracing:
            import jax.profiler
            ann = jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name)
        else:
            ann = contextlib.nullcontext()
        t = time.perf_counter()
        with ann:
            yield
        if self.on:
            d = time.perf_counter() - t
            self.total[name] += d
            self.longest[name] = max(self.longest[name], d)


class Profile:
    """The profiler over the window's first ``TRACE_SECONDS``, bracketed
    by a ``bench.window`` annotation; does nothing when tracing is off."""

    def __init__(self, tracing: bool, directory: str):
        self.tracing = tracing
        self.dir = directory
        self.on = False
        self.t_end = 0.0
        self._ann = None

    def start(self, seconds: float):
        if not self.tracing:
            return
        import jax.profiler
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        self._ann.__enter__()
        self.t_end = time.perf_counter() + min(seconds, TRACE_SECONDS)
        self.on = True

    def poll(self, now: float):
        if self.on and now >= self.t_end:
            self.stop()

    def stop(self):
        if self.on:
            import jax.profiler
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


class CompileLog:
    """Backend compilations JAX reports while listening."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.secs = 0.0

    def __call__(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.secs += duration

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self)


class GcLog:
    """Pauses of Python's cyclic garbage collector while listening."""

    def __init__(self):
        self.count = 0
        self.secs = 0.0
        self.longest = 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.count += 1
            self.secs += d
            self.longest = max(self.longest, d)
            self._t = None

    @contextlib.contextmanager
    def listening(self):
        gc.callbacks.append(self)
        try:
            yield self
        finally:
            gc.callbacks.remove(self)


class SetupLog:
    """Seconds of each set-up phase, and the device bytes in use after it."""

    def __init__(self, t_start: float, devices):
        self.t = t_start
        self.devices = devices
        self.phases = []

    def mark(self, name: str):
        now = time.perf_counter()
        used = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                   for d in self.devices)
        self.phases.append((name, now - self.t, used))
        self.t = now

    def __str__(self):
        return ", ".join(f"{n} {s:.3f} s ({b / 2**20:.0f} MiB in use)"
                         for n, s, b in self.phases)


class FsyncLog:
    """Every ``os.fsync`` in the process: (time it returned, path, size).

    The durability check reads the log as the disk would: bytes are
    durable once an fsync of their file has returned."""

    def __init__(self):
        self.events = []
        self._orig = None

    def _fsync(self, fd):
        path = os.readlink(f"/proc/self/fd/{fd}")
        size = os.fstat(fd).st_size
        self._orig(fd)
        self.events.append((time.perf_counter(), path, size))

    def __enter__(self):
        self._orig = os.fsync
        os.fsync = self._fsync
        return self

    def __exit__(self, *exc):
        os.fsync = self._orig


class Book:
    """Per-operation record of a run, indexed by operation id.

    Kept in fixed chunks of ``CHUNK`` operations, so that recording never
    copies what is already recorded (a copy inside the window would stall
    the loop)."""

    CHUNK = 1 << 20
    FIELDS = dict(ops=np.int32, keys=np.int32, keys2=np.int32,
                  vals=np.int32, t_issue=np.float64, t_ret=np.float64,
                  found=bool, val=np.int32, rcnt=np.int32, rsum=np.int32,
                  window=np.int64, answered=bool)

    def __init__(self):
        self.n = 0
        self.chunks = []
        self.last_ret = -np.inf

    def _rows(self, q: np.ndarray):
        """(chunk, offset) groups of the sorted operation ids ``q``."""
        c = q // self.CHUNK
        cuts = np.flatnonzero(np.diff(c)) + 1
        for part in np.split(np.arange(len(q)), cuts):
            if len(part):
                yield self.chunks[int(c[part[0]])], q[part] % self.CHUNK, \
                    part

    def issue(self, ops, keys, keys2, vals, t) -> np.ndarray:
        n = len(ops)
        while self.n + n > len(self.chunks) * self.CHUNK:
            self.chunks.append({k: np.zeros(self.CHUNK, v)
                                for k, v in self.FIELDS.items()})
        q = np.arange(self.n, self.n + n)
        for ch, off, part in self._rows(q):
            ch["ops"][off] = ops[part]
            ch["keys"][off] = keys[part]
            ch["keys2"][off] = keys2[part]
            ch["vals"][off] = vals[part]
            ch["t_issue"][off] = t
        self.n += n
        return q

    def answer(self, q: np.ndarray, t: float, seq: int, found, val,
               rcnt=None, rsum=None):
        """Record the answers of operations ``q`` (ascending) at ``t``."""
        for ch, off, part in self._rows(q):
            ch["found"][off] = found[part]
            ch["val"][off] = val[part]
            if rcnt is not None:
                ch["rcnt"][off] = rcnt[part]
                ch["rsum"][off] = rsum[part]
            ch["t_ret"][off] = t
            ch["window"][off] = seq
            ch["answered"][off] = True
        self.last_ret = t

    def __getitem__(self, k):
        return np.concatenate([c[k] for c in self.chunks])[:self.n] \
            if self.chunks else np.zeros(0, self.FIELDS[k])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def build_index(cfg: dict, data: ycsb.Dataset, devices):
    """The served index, on ``devices`` as the configuration lays it out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import PIConfig, build, build_sharded

    pcfg = PIConfig(**cfg["index"])
    shards = int(cfg.get("shards", 1))
    if shards == 1:
        with jax.default_device(devices[0]):
            index = build(pcfg, jnp.asarray(data.keys),
                          jnp.asarray(data.vals))
        return jax.block_until_ready(index), None
    mesh = Mesh(np.array(devices), ("data",))
    index = build_sharded(pcfg, shards, data.keys, data.vals, mesh=mesh)
    jax.block_until_ready(index.shards)
    return index, mesh


def warm_window(batch: int, key: int, with_range: bool):
    """A padded window holding a SEARCH (and a RANGE when the mix scans):
    the shapes of every window the loop serves."""
    from repro.pipeline import Collector, WindowConfig

    col = Collector(WindowConfig(batch=batch))
    ops = [ycsb.SEARCH] + ([ycsb.RANGE] if with_range else [])
    n = len(ops)
    col.offer_many(np.zeros(n), np.array(ops, np.int32),
                   np.full(n, key, np.int32), np.zeros(n, np.int32),
                   np.arange(n), keys2=np.full(n, key, np.int32))
    return col.take()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What metric readers read (``metrics/<name>.py``)."""

    device_kind: str
    setup_s: float
    window_s: float
    n_retired: int
    latencies: np.ndarray           # seconds, ops issued and retired
    ops: np.ndarray                 # the op code of each of those
    windows: int                    # windows sealed inside the window
    spans: dict                     # span -> seconds inside the window
    work: dict                      # least bytes per window, by kind
    reduction: object = None        # trace.Reduction of the traced run


def _segment_end(wal_dir: str):
    """(path, size) of the newest non-empty WAL segment."""
    for name in sorted(os.listdir(wal_dir), reverse=True):
        path = os.path.join(wal_dir, name)
        size = os.path.getsize(path)
        if size:
            return os.path.realpath(path), size
    raise BenchError("the WAL is empty after an append")


def window_work(book: Book, base, cfg: dict, in_window) -> dict:
    """Least bytes per window of the point work and of the scans,
    averaged over the windows that retired inside the measured window."""
    ops, keys, win = book["ops"], book["keys"], book["window"]
    sel = in_window
    n_win = max(1, len(np.unique(win[sel])))
    point = sel & (ops != ycsb.RANGE)
    wk = (win.astype(np.int64) << 32) | (keys.astype(np.int64)
                                         & 0xFFFFFFFF)
    n_point = len(np.unique(wk[point]))
    writes = point & (ops != ycsb.SEARCH)
    uw = np.unique(wk[writes])
    n_written = len(uw)
    inb, _ = base.lookup((uw & 0xFFFFFFFF).astype(np.int32)
                         .astype(np.int64))
    n_new = int((~inb).sum())
    idx = cfg["index"]
    slots, fan = int(idx["capacity"]), int(idx["fanout"])
    out = {"point_bytes": roofline.point_bytes(
        n_point, n_written, n_new, slots, fan) / n_win}
    scans = sel & (ops == ycsb.RANGE)
    if scans.any():
        n_scan_win = max(1, len(np.unique(win[scans])))
        out["scan_bytes"] = roofline.scan_bytes(
            int(scans.sum()), int(book["rcnt"][scans].astype(np.int64).sum()),
            slots, fan) / n_scan_win
    return out


def run_cell(cfg: dict, mix: dict, seed: int, seconds: float, tracing: bool,
             devices, *, t_start: float, metric_names=(),
             out=sys.stderr) -> dict:
    """Run configuration ``cfg`` under traffic ``mix`` on ``devices``;
    returns the result line's object."""
    from repro.core import collect_pairs, live_items
    from repro.pipeline import (Collector, Dispatcher, Durability,
                                WindowConfig, read_wal)

    win_cfg = cfg["window"]
    batch = int(win_cfg["batch"])
    clients = int(mix["threadcount"])
    spans = Spans(tracing)
    compiles = CompileLog()
    pauses = GcLog()
    setup = SetupLog(t_start, devices)
    setup.mark("start")                 # imports, reaching the chip
    tmp = tempfile.mkdtemp(prefix="pi_bench_")
    try:
        with compiles.listening(), FsyncLog() as fsyncs:
            # -- set-up: data, index, WAL, warm-up -------------------------
            data = ycsb.Dataset(cfg["recordcount"], cfg["key_bits"], seed)
            stream = ycsb.OpStream(mix, data)
            scans = mix.get("scanproportion", 0) > 0
            if scans:
                data.sorted_keys            # the generator reads it
            setup.mark("data")
            index, mesh = build_index(cfg, data, devices)
            setup.mark("build")
            wal_dir = os.path.join(tmp, "durable", "wal")
            dur = Durability(os.path.join(tmp, "durable"), index,
                             fsync=cfg["durability"]["fsync"])
            setup.mark("snapshot")
            disp_kw = dict(mesh=mesh, max_span=int(win_cfg["max_span"]))
            warm = Dispatcher(index, depth=0, **disp_kw)
            warm.submit(warm_window(batch, int(data.keys[0]), scans))
            del warm
            setup.mark("warm-up")
            # the step-0 snapshot is written without fsync; left dirty, the
            # kernel writes it back some 30 s later, inside the window,
            # where the WAL's fsyncs can wait behind it
            os.sync()
            setup.mark("sync")
            windows, seals, acks = {}, {}, {}

            def on_seal(w):
                with spans("wal"):
                    seq = dur.on_seal(w)
                windows[seq] = (w.ops, w.keys, w.keys2, w.vals,
                                w.occupancy)
                seals[seq] = _segment_end(wal_dir)
                return seq

            col = Collector(WindowConfig(batch=batch), on_seal=on_seal)
            disp = Dispatcher(index, depth=int(win_cfg["depth"]),
                              durability=dur, **disp_kw)
            del index
            book = Book()
            n_setup_compiles = compiles.count
            rebuilds = defaultdict(int)

            def retire(results):
                freed = 0
                for r in results:
                    now = time.perf_counter()
                    w = r.window
                    q = np.asarray(w.qids)
                    sl = np.asarray(w.slots)
                    book.answer(q, now, w.seq, r.found[sl], r.val[sl],
                                None if r.rcnt is None else r.rcnt[sl],
                                None if r.rsum is None else r.rsum[sl])
                    acks[w.seq] = now
                    if r.rebuilt:
                        rebuilds["incremental" if r.rebuilt_incremental
                                 else "repack"] += 1
                    freed += len(q)
                return freed

            def submit(ws):
                freed = 0
                for w in ws:
                    with spans("submit"):
                        res = disp.submit(w)
                    with spans("map"):
                        freed += retire(res)
                return freed

            # -- the measured window ---------------------------------------
            trace_dir = os.path.join(tmp, "trace")
            profile = Profile(tracing, trace_dir)
            profile.start(seconds)
            ready = clients
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            t_stop = t0 + seconds
            spans.on = True
            n_sealed_before = len(windows)
            # the window closes at the first answer after ``seconds``, so
            # it always ends on a retirement and holds whole windows
            with pauses.listening():
                while True:
                    now = time.perf_counter()
                    profile.poll(now)
                    if book.last_ret >= t_stop:
                        break
                    if ready:
                        with spans("generator"):
                            ops, keys, keys2, vals = stream.next(ready)
                            qids = book.issue(ops, keys, keys2, vals, now)
                        with spans("admit"):
                            _, sealed = col.offer_many(
                                np.full(ready, now), ops, keys, vals, qids,
                                keys2)
                        ready = 0
                    else:
                        with spans("admit"):
                            w = col.take()
                        sealed = [w] if w is not None else []
                        if not sealed:
                            with spans("retire"):
                                res = disp.flush()
                            with spans("map"):
                                ready += retire(res)
                            continue
                    ready += submit(sealed)
            spans.on = False
            t_end = book.last_ret
            n_window_compiles = compiles.count - n_setup_compiles
            n_sealed = len(windows) - n_sealed_before
            profile.stop()
            # -- drain: every issued operation gets its answer -------------
            w = col.take()
            submit([w] if w is not None else [])
            retire(disp.flush())
            peak = peak_bytes(devices)
            dur.close()
            final = disp.index
            del disp
            if mesh is None:
                live_k, live_v = live_items(final)
            else:
                live_k, live_v = collect_pairs(final)
            del final
        records = read_wal(wal_dir)

        # -- the comparison with the reference -----------------------------
        from bench import reference
        base = reference.Base(data.keys, data.vals)
        got = check.compare(base, book["ops"], book["keys"], book["keys2"],
                            book["vals"], book["window"], book["answered"],
                            book["found"], book["val"], book["rcnt"],
                            book["rsum"], live_k, live_v)
        digests = {s: check.window_digest(*w) for s, w in windows.items()}
        acked = sorted(acks)
        got["wal_lost"] = check.wal_lost(records, digests, acked)
        got["unsynced"] = check.unsynced(seals, fsyncs.events, acks)
        failed = got.pop("failed")
        checks = {k: {"value": got[k], "limit": lim}
                  for k, lim in check.LIMITS.items()}
        correct = all(c["value"] <= c["limit"] for c in checks.values())

        # -- metrics --------------------------------------------------------
        t_ret = book["t_ret"]
        done = book["answered"] & (t_ret <= t_end)
        run = RunData(device_kind=devices[0].device_kind, setup_s=setup_s,
                      window_s=t_end - t0, n_retired=int(done.sum()),
                      latencies=(t_ret - book["t_issue"])[done],
                      ops=book["ops"][done],
                      windows=n_sealed, spans=dict(spans.total),
                      work=window_work(book, base, cfg, done))
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        result = {"correct": correct, "attempted": int(book.n),
                  "failed": failed}
        if tracing:
            path = _xplane(trace_dir)
            planes = trace.load_xplane(path)
            dev_ids = [d.id for d in devices]
            run.reduction = trace.reduce(planes, dev_ids)
            device["busy_s"] = run.reduction.mean_busy_s()
            device["window_s"] = run.reduction.window_s
        metrics = {}
        for m in metric_names:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if tracing:
            result["breakdown"] = {"device_ops": run.reduction.top_ops,
                                   "idle_gaps": run.reduction.idle_by_span}
        result["checks"] = checks

        print(f"windows sealed in the window: {n_sealed}; compilations "
              f"inside the window: {n_window_compiles}", file=out)
        print(f"set-up {setup_s:.3f} s ({n_setup_compiles} compilations, "
              f"{compiles.secs:.1f} s compiling): {setup}", file=out)
        print(f"rebuilds by tier: {dict(rebuilds)}", file=out)
        n_arr = int(book.n)
        n_slots = sum(w[4] for w in windows.values())
        print(f"arrivals per occupied slot: {n_arr / max(n_slots, 1):.4f}",
              file=out)
        print(f"peak_bytes_in_use (fullest chip): {peak}", file=out)
        if len(run.latencies):
            q = np.percentile(run.latencies, [50, 90, 99, 99.9, 100]) * 1e3
            print(f"latency ms p50/p90/p99/p99.9/max: "
                  f"{' / '.join(f'{x:.2f}' for x in q)} over "
                  f"{len(run.latencies)} ops in {run.window_s:.3f} s",
                  file=out)
        print(f"host spans in the window (s): "
              f"{ {k: round(v, 4) for k, v in spans.total.items()} }; "
              f"longest single span (ms): "
              f"{ {k: round(v * 1e3, 2) for k, v in spans.longest.items()} }",
              file=out)
        print(f"gc pauses in the window: {pauses.count}, "
              f"{pauses.secs * 1e3:.1f} ms in all, longest "
              f"{pauses.longest * 1e3:.2f} ms", file=out)
        for k, c in checks.items():
            print(f"check {k}: {c['value']} (limit {c['limit']})", file=out)
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _xplane(trace_dir: str) -> str:
    found = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise BenchError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {found}")
    return found[0]
