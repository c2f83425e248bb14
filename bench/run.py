"""The benchmark: one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload resnet50-224-int8.offline --seed 7 \
        --seconds 20 --trace 0

A cell names a configuration (bench/configs/<config>.json) and a traffic mix
(bench/mixes/<traffic>.json).  The run builds the seeded weights and image
pool, registers the model with the serving entry CNNServeEngine on the
Pallas engines, warms up every shape the window uses, drives the closed
loop of bench/traffic.py for --seconds, and then compares a seeded sample of
the window's answers with the plain reference (bench/reference.py).

--trace 0 prints the cell's end-to-end metrics; --trace 1 traces a window
of the mix's trace_seconds with the JAX profiler and prints the cell's
per-layer metrics, each read by bench/metrics/<name>.py.  The last line of
stdout is the result; the numbers compared, each beside its limit, are the
last lines of stderr.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import contextlib
import ctypes
import gc
import json
import os
import resource
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# Profiler settings of a traced run: device operations only.  The harness
# times its own host spans (bench/trace.py SpanRecorder); collecting host
# events slowed the traced window of resnet50 single-stream from about 450
# requests/s to about 90.
TRACE_LEVELS = {"host_tracer_level": 0, "python_tracer_level": 0}
M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4


def log(msg: str) -> None:
    print(msg, flush=True)


def pin_allocator() -> bool:
    """Keep freed host memory in the process.  glibc serves a block over
    its mmap threshold (a wave buffer is tens of MB) with a fresh mapping
    and unmaps it on free, so every wave faults its pages in again; on a
    sandboxed host a fresh page costs about ten times what it does on a
    TPU VM, and the offline cells flipped between two speeds with it."""
    libc = ctypes.CDLL("libc.so.6")
    libc.mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    libc.mallopt.restype = ctypes.c_int
    return bool(libc.mallopt(M_MMAP_MAX, 0)
                and libc.mallopt(M_TRIM_THRESHOLD, 2 ** 31 - 1))


def probe_fill(shape) -> dict:
    """A fresh zeroed host buffer the size of a wave, filled, five times:
    the host cost of the engine's per-wave buffer."""
    import numpy as np
    times, flt = [], []
    for _ in range(5):
        f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        t = time.perf_counter()
        buf = np.zeros(shape, np.float32)
        buf.fill(1.0)
        times.append((time.perf_counter() - t) * 1e3)
        flt.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0)
        del buf
    return {"fill_ms_median": sorted(times)[2], "minflt_median": sorted(flt)[2],
            "bytes": int(np.prod(shape)) * 4}


def use_checkout_cache() -> None:
    """JAX's persistent compile cache at one fixed place in the checkout
    (the path is part of the cache key), every program cached however
    quickly it compiled, so a warm run compiles nothing.  JAX reads these
    when it is imported, and writes nothing into a directory that is not
    there.  No size cap: with one, JAX's eviction bookkeeping failed every
    write on the chip's host, and every run compiled everything again."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bench: dict, workload: str, traced: bool):
    """The metrics this cell reports: its end-to-end ones, or with a trace
    its per-layer ones (listed for it, or moving a metric it reports)."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def logit_err(served, ref) -> float:
    """The worst answer's RMS logit gap to the reference, over the RMS
    spread of the reference's answers around their mean: an answer that
    belongs to another image reads about sqrt(2), a constant answer 1."""
    import numpy as np
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(served)):
        return float("inf")
    spread = np.sqrt(np.mean((ref - ref.mean(axis=0)) ** 2))
    gap = np.sqrt(np.mean((served - ref) ** 2, axis=1))
    return float(np.max(gap) / spread)


class CompileCounter:
    """Counts programs traced or compiled while `on` (jax.monitoring)."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in ("/jax/core/compile/jaxpr_trace_duration",
                                 "/jax/core/compile/backend_compile_duration"):
            self.n += 1


def run(workload: str, seed: int, seconds: float, traced: bool,
        bench: dict, require_tpu: bool = True, configs=None, mixes=None):
    """One run of a cell; returns the result line as a dict, its "checks"
    last.  `configs`/`mixes` override the files by name (rehearsals at a
    small size)."""
    import jax
    import numpy as np

    from bench import model, peaks, traffic
    from bench import trace as tr
    from bench.metrics import Readings, reader
    from bench.reference import Reference

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = (configs or {}).get(cell["config"]) or model.load_config(
        cell["config"])
    mix = (mixes or {}).get(cell["traffic"]) or traffic.load_mix(
        cell["traffic"])
    split = {}
    t = time.perf_counter()

    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        print(f"{workload} needs {cell['chips']} TPU chips, found "
              f"{len(devs)} {dev.platform} devices", file=sys.stderr)
        raise SystemExit(2)
    peak = peaks.peak(dev.device_kind) if require_tpu else \
        next(iter(peaks.PEAKS.values()))

    from repro.core import engine as eng_lib
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.cnn_engine import CNNServeEngine

    log(f"compile cache {enable_compile_cache()}")
    counter = CompileCounter()
    split["backend_init"] = time.perf_counter() - t

    t = time.perf_counter()
    params = jax.block_until_ready(model.make_params(cfg, seed))
    split["params"] = time.perf_counter() - t

    t = time.perf_counter()
    pool = traffic.image_pool(cfg, mix["pool_images"], seed)
    calib = traffic.calibration_batch(cfg, seed)
    split["data"] = time.perf_counter() - t

    t = time.perf_counter()
    pcfg = model.program_config(cfg)
    srv = CNNServeEngine(eng_lib.paper_engine(backend="pallas"),
                         wave_size=mix["wave_size"])
    srv.register(pcfg, params, calib_batches=[calib])
    split["register"] = time.perf_counter() - t

    t = time.perf_counter()
    srv.program_for(pcfg.name)
    split["calibrate"] = time.perf_counter() - t

    # warm-up: two rounds of the mix's own shape compile and load every
    # program the window runs
    t = time.perf_counter()
    traffic.drive(srv, pcfg.name, pool, dict(mix, queued_waves=1), seed, 0)
    split["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    traffic.drive(srv, pcfg.name, pool, mix, seed, 0)
    split["warmup"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T0
    split["total"] = setup_s
    log(f"setup_s {json.dumps(split)}")
    wave_shape = (srv.wave_rows, cfg["input"]["size"], cfg["input"]["size"],
                  cfg["input"]["channels"])
    log(f"probe host {json.dumps(probe_fill(wave_shape))}")

    window_len = min(seconds, mix["trace_seconds"]) if traced else seconds
    annotate = None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        for k, v in TRACE_LEVELS.items():
            setattr(opts, k, v)
        recorder = tr.SpanRecorder()
        annotate = recorder.span
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    counter.on = True
    with (annotate("window") if traced else contextlib.nullcontext()):
        win = traffic.drive(srv, pcfg.name, pool, mix, seed, window_len,
                            annotate)
    counter.on = False
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    if traced:
        jax.profiler.stop_trace()
    log(f"window {json.dumps({'seconds': win.seconds, 'attempted': win.attempted, 'completed': win.completed, 'waves': win.waves})} "
        f"compiles_in_window {counter.n}")
    log(f"per_second {json.dumps(win.per_second)}")
    log("host " + json.dumps({
        "minflt": ru1.ru_minflt - ru0.ru_minflt,
        "majflt": ru1.ru_majflt - ru0.ru_majflt,
        "nvcsw": ru1.ru_nvcsw - ru0.ru_nvcsw,
        "nivcsw": ru1.ru_nivcsw - ru0.ru_nivcsw,
        "utime": ru1.ru_utime - ru0.ru_utime,
        "stime": ru1.ru_stime - ru0.ru_stime}))
    stats = srv.stats()
    log("engine " + json.dumps({k: stats[k] for k in (
        "waves", "requests", "padded_slots", "wave_fill_rate",
        "program_execs", "fused_programs")}))
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # the program's state goes before the reference runs
    del srv
    gc.collect()
    t = time.perf_counter()
    n = min(mix["check_samples"], len(win.answers))
    pick = np.sort(np.random.default_rng([seed, 4]).choice(
        np.fromiter(win.answers, int), n, replace=False))
    served = np.stack([win.answers[i] for i in pick])
    want = Reference(cfg, params)(pool[[win.image_of[i] for i in pick]])
    limit = cfg["check"]["logit_err"]
    err = logit_err(served, want)
    failed = win.attempted - win.completed
    checks = {"logit_err": {"value": err, "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    correct = bool(n > 0 and err <= limit and failed == 0)
    log(f"check_s {time.perf_counter() - t:.3f} over {n} sampled answers")

    trace = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": failed, "metrics": {}, "device": device}
    if traced:
        t = time.perf_counter()
        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        trace = tr.read_xplane(files[-1], recorder.spans)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = tr.window_s(trace)
        if trace.ops:
            result["breakdown"] = {"device_ops": tr.top_ops(trace),
                                   "idle_gaps": tr.idle_gaps(trace)}
            log(f"idle_by_span {json.dumps(tr.idle_by_label(trace))}")
        log(f"trace_read_s {time.perf_counter() - t:.3f} ops "
            f"{sum(len(v) for v in trace.ops.values())} spans "
            f"{len(trace.spans)}")
    readings = Readings(cfg=cfg, mix=mix, peak=peak, window=win,
                        setup_s=setup_s, trace=trace)
    for m in cell_metrics(bench, workload, traced):
        value = reader(m["name"])(readings)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    use_checkout_cache()
    log(f"allocator keeps freed memory: {pin_allocator()}")
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 load_benchmark())
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
