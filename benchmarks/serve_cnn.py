"""CNN serving throughput: program cache + wave batching + fused epilogues.

Evidence lines for the serving layer (serve/cnn_engine.py):

  * MODELED: the per-engine-unit overlap model (perf_model.py) -- in the
    pipelined steady state throughput is set by the busiest unit (Conv PE
    vs DWC PE vs MISC), so depthwise-heavy models gain the most from the
    Conv/DWC concurrency the schedule exposes (scheduled-vs-sequential).
  * MEASURED cache: wall-clock of a repeated-model request trace served
    cached vs uncached (capacity 0 -> every request recompiles +
    recalibrates + retraces), plus the cache hit-rate of the trace.
  * MEASURED waves: per-request latency of wave-batched vs one-by-one
    execution on the same cached program.
  * STRUCTURAL fusion: kernel launches + materialized intermediates per
    image of the served (epilogue-fused) programs vs their unfused twins,
    and per-level / time-weighted engine occupancy of the fused graphs
    under the asap vs slack leveling policies.

    PYTHONPATH=src python -m benchmarks.serve_cnn [--summary]

--summary prints the one-line program-cache + fusion summary (scripts/
check.sh appends it to the gate output) and writes the machine-readable
BENCH_serve.json snapshot next to the repo root, so the serving perf
trajectory is tracked across PRs.
"""
import json
import os
import time

import numpy as np

from benchmarks import perf_model as pm
from repro.configs.cnn_zoo import CNN_ZOO

TRACE_MODELS = ("squeezenet", "mobilenetv2", "resnet50")
TRACE_LEN = 40                              # requests over the 3 models
FAST_LEN = 10                               # --fast: one model, short trace
SERVE_HW = 32                               # reduced input for CPU wall-clock
WAVE = 4
BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")


def _reduced(name):
    import dataclasses
    return dataclasses.replace(CNN_ZOO[name], input_hw=SERVE_HW)


def _build_fleet(seed=0, models=TRACE_MODELS):
    """(cfg, float params, calibration batch) per trace model.  `models`
    entries are zoo names or ready CNNConfig objects."""
    import jax
    import jax.numpy as jnp

    from repro.models import cnn
    from repro.models.params import init_params

    fleet = []
    rng = np.random.default_rng(seed)
    for i, m in enumerate(models):
        cfg = _reduced(m) if isinstance(m, str) else m
        params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(i))
        calib = jnp.asarray(rng.normal(
            size=(2, cfg.input_hw, cfg.input_hw, cfg.input_ch)
        ).astype(np.float32) * 0.5)
        fleet.append((cfg, params, calib))
    return fleet


def fast_cfg():
    """--fast's model: the reduced squeezenet truncated to its first
    fire stages -- same op mix (stem, pool, fire blocks), a fraction of
    the param-init + XLA-compile wall that dominates the fast budget."""
    import dataclasses
    base = _reduced("squeezenet")
    return dataclasses.replace(base, name="squeezenet-fast",
                               stages=base.stages[:3])


def _trace(seed=0, models=TRACE_MODELS, length=TRACE_LEN):
    """A repeated-model request trace: each request names a model and
    carries one image.  Model repetition mirrors production traffic (a
    small working set revisited), which is what the cache monetizes."""
    rng = np.random.default_rng(seed)
    names = [models[int(i)] for i in
             rng.integers(0, len(models), length)]
    sizes = {n: _reduced(n).input_hw for n in models}
    return [(n, rng.normal(size=(sizes[n], sizes[n], 3)).astype(np.float32))
            for n in names]


def _serve_trace(engine, fleet, trace):
    for cfg, params, calib in fleet:
        engine.register(cfg, params, calib_batches=[calib])
    t0 = time.perf_counter()
    for name, img in trace:
        engine.submit(name, img)
        engine.flush()                  # request-at-a-time arrival
    return time.perf_counter() - t0


def serve_stats(wave_batch: bool = True, fleet=None, trace=None, cache=None):
    """Serve the standard trace through a cached engine; return its stats
    (the hit-rate + occupancy line check.sh prints comes from here)."""
    from repro import compiler
    from repro.core import engine as eng_lib
    from repro.serve.cnn_engine import CNNServeEngine

    fleet = _build_fleet() if fleet is None else fleet
    trace = _trace() if trace is None else trace
    engine = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                            cache_capacity=len(fleet) + 1, cache=cache)
    wall = _serve_trace(engine, fleet, trace)
    stats = engine.stats()
    stats["wall_s"] = wall
    stats["requests_per_s"] = len(trace) / wall if wall > 0 else 0.0
    # served (fused) programs: per-level + time-weighted engine occupancy
    # under each leveling policy, and launches/image vs the unfused twin
    occ = {"asap": [], "alap": [], "slack": [], "cost": []}
    tw = {"asap": [], "slack": [], "cost": []}
    launches = {}
    for cfg, _, _ in fleet:
        program = engine.program_for(cfg.name)
        g = program.graph
        unfused = compiler.build_graph(cfg)
        times = pm.cnn_node_times(g, cfg)
        for policy in occ:
            if policy == "asap":
                sched = program.schedule
            elif policy == "cost":
                sched = compiler.level_schedule(g, "cost", node_times=times)
            else:
                sched = compiler.level_schedule(g, policy)
            occ[policy].append(
                compiler.engine_occupancy(g, sched)["occupancy"])
            if policy in tw:
                tw[policy].append(compiler.time_weighted_occupancy(
                    g, sched, times)["occupancy"])
        fs = compiler.fusion_stats(g)
        launches[cfg.name] = {
            "unfused": compiler.launch_count(unfused),
            "fused": fs["launches"],
            "fused_ops": fs["fused_ops"],
            "materialized_edges": fs["materialized_edges"],
            "materialized_unfused":
                compiler.fusion_stats(unfused)["materialized_edges"],
        }
    stats["engine_occupancy"] = float(np.mean(occ["asap"]))
    stats["engine_occupancy_alap"] = float(np.mean(occ["alap"]))
    stats["engine_occupancy_slack"] = float(np.mean(occ["slack"]))
    stats["engine_occupancy_cost"] = float(np.mean(occ["cost"]))
    stats["tw_occupancy"] = float(np.mean(tw["asap"]))
    stats["tw_occupancy_slack"] = float(np.mean(tw["slack"]))
    stats["tw_occupancy_cost"] = float(np.mean(tw["cost"]))
    stats["launches"] = launches
    if wave_batch:
        # the same trace arriving all at once: full waves per model
        engine2 = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                                 cache_capacity=len(fleet) + 1,
                                 cache=engine.cache)   # warm shared cache
        for cfg, params, calib in fleet:
            engine2.register(cfg, params, calib_batches=[calib])
        for name, img in trace:
            engine2.submit(name, img)
        t0 = time.perf_counter()
        engine2.flush()
        stats["wall_batched_s"] = time.perf_counter() - t0
        stats["batched_occupancy"] = engine2.wave_stats.occupancy
    return stats


def fill_rate_stats(fleet=None, trace=None, cache=None):
    """Mixed-arrival trace, one request at a time, two batching policies:

      * pad-and-mask baseline -- flush() after every arrival: every request
        dispatches immediately in a mostly-empty padded wave;
      * continuous -- pump() after every arrival: only FULL waves dispatch,
        partial waves stay queued and REFILL from later arrivals (including
        other same-shape models, which share the slot queue); one final
        drain pads at most one wave per shape.

    The wave fill-rate (requests / physical wave slots) is the acceptance
    metric: continuous must meet or beat the baseline."""
    from repro.core import engine as eng_lib
    from repro.serve.cnn_engine import CNNServeEngine

    fleet = _build_fleet() if fleet is None else fleet
    trace = _trace() if trace is None else trace

    base = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                          cache_capacity=len(fleet) + 1, cache=cache)
    for cfg, params, calib in fleet:
        base.register(cfg, params, calib_batches=[calib])
    for name, img in trace:
        base.submit(name, img)
        base.flush()                    # pad-and-mask per arrival

    cont = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                          cache_capacity=len(fleet) + 1,
                          cache=base.cache)    # warm shared cache
    for cfg, params, calib in fleet:
        cont.register(cfg, params, calib_batches=[calib])
    for name, img in trace:
        cont.submit(name, img)
        cont.pump()                     # full waves only; partials refill
    cont.flush()                        # final drain
    b, c = base.stats(), cont.stats()
    return {
        "baseline_fill_rate": b["wave_fill_rate"],
        "continuous_fill_rate": c["wave_fill_rate"],
        "baseline_waves": b["waves"],
        "continuous_waves": c["waves"],
        "refilled_waves": c["refilled_waves"],
        "program_execs": c["program_execs"],
    }


def _measure_uncached(fleet, trace):
    """capacity=0: every request misses, recompiles, and retraces."""
    from repro.core import engine as eng_lib
    from repro.serve.cnn_engine import CNNServeEngine

    engine = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                            cache_capacity=0)
    return _serve_trace(engine, fleet, trace), engine.stats()


def run(measure: bool = True):
    rows = []
    for name, cfg in CNN_ZOO.items():
        credit = pm.overlap_credit(cfg, pm.OURS)
        fps_seq = pm.modeled_fps(cfg, pm.OURS)
        fps_pipe = pm.modeled_fps_pipelined(cfg, pm.OURS)
        rows.append((
            f"serve/model/{name}", 0.0,
            f"scheduled_fps={fps_pipe:.0f},sequential_fps={fps_seq:.0f},"
            f"overlap_credit={credit:.2f}"))
    zoo = zoo_fusion_occupancy()
    for name, z in zoo.items():
        rows.append((
            f"serve/fusion/{name}", 0.0,
            f"launches={z['launches_fused']}vs{z['launches_unfused']}"
            f"(-{100 * z['launch_reduction']:.0f}%),"
            f"fused_ops={z['fused_ops']},"
            f"occ_asap={z['occupancy']['asap']:.2f},"
            f"occ_slack={z['occupancy']['slack']:.2f},"
            f"tw_occ_slack={z['tw_occupancy_slack']:.2f}"))
    if measure:
        fleet = _build_fleet()
        trace = _trace()
        stats = serve_stats(fleet=fleet, trace=trace)
        t_uncached, _ = _measure_uncached(fleet, trace[:6])
        t_uncached_per = t_uncached / 6
        t_cached_per = stats["wall_s"] / len(trace)
        rows.append((
            f"serve/trace/cached", t_cached_per * 1e6,
            f"hit_rate={stats['cache_hit_rate']:.3f},"
            f"requests={stats['requests']},"
            f"compiles={stats['cache_misses']},"
            f"per_req={t_cached_per * 1e3:.1f}ms,"
            f"uncached_per_req={t_uncached_per * 1e3:.1f}ms,"
            f"cache_speedup={t_uncached_per / t_cached_per:.1f}x"))
        rows.append((
            f"serve/trace/waves", stats["wall_batched_s"] * 1e6,
            f"batched_wall={stats['wall_batched_s'] * 1e3:.1f}ms,"
            f"one_by_one_wall={stats['wall_s'] * 1e3:.1f}ms,"
            f"occupancy={stats['batched_occupancy']:.2f},wave={WAVE}"))
        fr = fill_rate_stats(fleet=fleet, trace=trace)
        rows.append((
            f"serve/trace/fill_rate", 0.0,
            f"continuous={fr['continuous_fill_rate']:.2f},"
            f"pad_and_mask={fr['baseline_fill_rate']:.2f},"
            f"waves={fr['continuous_waves']}vs{fr['baseline_waves']},"
            f"refilled_waves={fr['refilled_waves']}"))
        path = write_bench_json(bench_payload(fleet=fleet, trace=trace,
                                              stats=stats, fr=fr,
                                              zoo=zoo)[0])
        rows.append((f"serve/bench_json", 0.0, f"path={path}"))
    return rows


def zoo_fusion_occupancy():
    """Structural (no-execution) zoo-wide fusion + scheduling evidence:
    per model, launches/image fused vs unfused and per-level occupancy
    under asap/alap/slack on the FUSED graph.  The acceptance gate: slack
    occupancy >= asap on every model, and the ResNet-style launch drop."""
    from repro import compiler

    out = {}
    for name, cfg in CNN_ZOO.items():
        g = compiler.build_graph(cfg)
        fg, _ = compiler.fuse_epilogues(g)
        times = pm.cnn_node_times(fg, cfg)
        scheds = {p: compiler.level_schedule(fg, p)
                  for p in ("asap", "alap", "slack")}
        scheds["cost"] = compiler.level_schedule(fg, "cost",
                                                 node_times=times)
        occ = {p: compiler.engine_occupancy(fg, s)["occupancy"]
               for p, s in scheds.items()}
        unf, fus = compiler.launch_count(g), compiler.launch_count(fg)
        out[name] = {
            "launches_unfused": unf,
            "launches_fused": fus,
            "launch_reduction": 1.0 - fus / unf,
            "fused_ops": compiler.fusion_stats(fg)["fused_ops"],
            "occupancy": occ,
            "modeled_makespan_cost":
                scheds["cost"].stats.get("modeled_makespan", 0.0),
            "tw_occupancy_slack": compiler.time_weighted_occupancy(
                fg, scheds["slack"], times)["occupancy"],
            "tw_occupancy_cost": compiler.time_weighted_occupancy(
                fg, scheds["cost"], times)["occupancy"],
        }
    return out


def bench_payload(fleet=None, trace=None, stats=None, fr=None, zoo=None):
    """The machine-readable serving snapshot written to BENCH_serve.json:
    ops/s, fill rate, launches-per-image fused vs unfused, occupancy --
    the per-PR perf trajectory record.  Pass precomputed stats/fr/zoo to
    avoid re-serving the trace or re-sweeping the zoo."""
    fleet = _build_fleet() if fleet is None else fleet
    trace = _trace() if trace is None else trace
    if stats is None:
        stats = serve_stats(wave_batch=False, fleet=fleet, trace=trace)
    if fr is None:
        fr = fill_rate_stats(fleet=fleet, trace=trace)
    if zoo is None:
        zoo = zoo_fusion_occupancy()
    return {
        "trace": {"models": [cfg.name for cfg, _, _ in fleet],
                  "requests": len(trace),
                  "wave_size": WAVE, "input_hw": SERVE_HW},
        "ops_per_s": stats["requests_per_s"],
        "wall_s": stats["wall_s"],
        "latency_ms": stats["latency_ms"],
        "cache_hit_rate": stats["cache_hit_rate"],
        "fill_rate": {"continuous": fr["continuous_fill_rate"],
                      "pad_and_mask": fr["baseline_fill_rate"]},
        "launches_per_image": stats["launches"],
        "occupancy": {
            "per_level_asap": stats["engine_occupancy"],
            "per_level_alap": stats["engine_occupancy_alap"],
            "per_level_slack": stats["engine_occupancy_slack"],
            "per_level_cost": stats["engine_occupancy_cost"],
            "time_weighted_asap": stats["tw_occupancy"],
            "time_weighted_slack": stats["tw_occupancy_slack"],
            "time_weighted_cost": stats["tw_occupancy_cost"],
        },
        "zoo": zoo,
    }, stats, fr


def write_bench_json(payload, path: str = BENCH_PATH) -> str:
    """Merge-write the snapshot: top-level keys other writers own (e.g.
    serve_fleet's "fleet" block) survive a serve_cnn rewrite and vice
    versa, so the cross-PR trajectory file accretes instead of thrashing."""
    merged = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged.update(payload)
    with open(path, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def fast_payload():
    """--fast: a measured sub-10s trace subset that still emits the full
    BENCH_serve.json schema.  One model, short trace, ONE engine: the
    serve pass is flush-per-arrival (exactly the pad-and-mask baseline),
    then a second pump-per-arrival pass on the same warm engine measures
    continuous batching as scheduler-stat deltas -- no extra engines, so
    no re-tracing.  Printed to stdout; it does NOT overwrite the snapshot
    the full run records."""
    from repro.core import engine as eng_lib
    from repro.serve.cnn_engine import CNNServeEngine

    fleet = _build_fleet(models=[fast_cfg()])
    rng = np.random.default_rng(0)
    cfg0 = fleet[0][0]
    trace = [(cfg0.name,
              rng.normal(size=(cfg0.input_hw, cfg0.input_hw, 3)
                         ).astype(np.float32)) for _ in range(FAST_LEN)]
    engine = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE,
                            cache_capacity=len(fleet) + 1)
    wall = _serve_trace(engine, fleet, trace)     # pad-and-mask arrivals
    stats = engine.stats()
    stats["wall_s"] = wall
    stats["requests_per_s"] = len(trace) / wall if wall > 0 else 0.0
    base_fill = stats["wave_fill_rate"]
    base_waves = stats["waves"]
    # continuous pass on the same warm engine: full waves only, deltas
    s = engine._sched.stats
    d0, p0, r0, x0 = s.dispatched, s.padded_slots, s.refilled_waves, \
        engine.wave_stats.program_execs
    for name, img in trace:
        engine.submit(name, img)
        engine.pump()
    engine.flush()
    slots = (s.dispatched - d0) + (s.padded_slots - p0)
    fr = {
        "baseline_fill_rate": base_fill,
        "continuous_fill_rate": (s.dispatched - d0) / slots if slots else 0.0,
        "baseline_waves": base_waves,
        "continuous_waves": engine.wave_stats.waves - base_waves,
        "refilled_waves": s.refilled_waves - r0,
        "program_execs": engine.wave_stats.program_execs - x0,
    }
    # occupancy / launch stats for the one traced model
    from repro import compiler
    cfg = fleet[0][0]
    program = engine.program_for(cfg.name)
    g = program.graph
    unfused = compiler.build_graph(cfg)
    times = pm.cnn_node_times(g, cfg)
    slack = compiler.level_schedule(g, "slack")
    alap = compiler.level_schedule(g, "alap")
    cost = compiler.level_schedule(g, "cost", node_times=times)
    fs = compiler.fusion_stats(g)
    stats["engine_occupancy"] = compiler.engine_occupancy(
        g, program.schedule)["occupancy"]
    stats["engine_occupancy_alap"] = compiler.engine_occupancy(
        g, alap)["occupancy"]
    stats["engine_occupancy_slack"] = compiler.engine_occupancy(
        g, slack)["occupancy"]
    stats["engine_occupancy_cost"] = compiler.engine_occupancy(
        g, cost)["occupancy"]
    stats["tw_occupancy"] = compiler.time_weighted_occupancy(
        g, program.schedule, times)["occupancy"]
    stats["tw_occupancy_slack"] = compiler.time_weighted_occupancy(
        g, slack, times)["occupancy"]
    stats["tw_occupancy_cost"] = compiler.time_weighted_occupancy(
        g, cost, times)["occupancy"]
    stats["launches"] = {cfg.name: {
        "unfused": compiler.launch_count(unfused),
        "fused": fs["launches"],
        "fused_ops": fs["fused_ops"],
        "materialized_edges": fs["materialized_edges"],
        "materialized_unfused":
            compiler.fusion_stats(unfused)["materialized_edges"],
    }}
    zoo = zoo_fusion_occupancy()
    payload, _, _ = bench_payload(fleet=fleet, trace=trace, stats=stats,
                                  fr=fr, zoo=zoo)
    payload["fast"] = True
    return payload


def summary_line() -> str:
    payload, stats, fr = bench_payload()
    path = write_bench_json(payload)
    rn = stats["launches"].get("resnet50")
    fused_part = ""
    if rn:
        drop = 1.0 - rn["fused"] / rn["unfused"]
        fused_part = (f"fused launches/img resnet50 {rn['fused']} vs "
                      f"{rn['unfused']} unfused (-{100 * drop:.0f}%); ")
    return (f"program-cache hit-rate: {100 * stats['cache_hit_rate']:.1f}% "
            f"({stats['cache_hits']}/{stats['cache_hits'] + stats['cache_misses']} hits, "
            f"{stats['cache_misses']} compiles over {stats['requests']} "
            f"requests, {len(TRACE_MODELS)} models); "
            f"{fused_part}"
            f"per-level engine occupancy "
            f"{100 * stats['engine_occupancy']:.1f}% asap / "
            f"{100 * stats['engine_occupancy_alap']:.1f}% alap / "
            f"{100 * stats['engine_occupancy_slack']:.1f}% slack / "
            f"{100 * stats['engine_occupancy_cost']:.1f}% cost "
            f"(time-weighted {100 * stats['tw_occupancy']:.1f}% asap -> "
            f"{100 * stats['tw_occupancy_slack']:.1f}% slack -> "
            f"{100 * stats['tw_occupancy_cost']:.1f}% cost); "
            f"wave fill-rate {100 * fr['continuous_fill_rate']:.1f}% "
            f"continuous vs {100 * fr['baseline_fill_rate']:.1f}% "
            f"pad-and-mask; BENCH_serve.json: {path}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--summary", action="store_true",
                    help="one-line program-cache hit-rate only")
    ap.add_argument("--fast", action="store_true",
                    help="measured sub-10s trace subset; prints the "
                         "BENCH_serve.json schema to stdout")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.summary:
        print(summary_line())
    elif args.fast:
        print(json.dumps(fast_payload(), indent=2, sort_keys=True))
    else:
        print("name,us_per_call,derived")
        for row_name, us, derived in run(measure=True):
            print(f"{row_name},{us:.1f},{derived}")
