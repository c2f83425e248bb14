"""Multi-tenant co-serving: CNN waves + LM decode bursts on one fabric.

Evidence lines for the cost-driven scheduler + fabric interleaver
(compiler/schedule.py::merge_schedules, executor.execute_interleaved,
serve/base.py::FabricPump):

  * MEASURED co-tenancy: the same CNN image trace and LM prompt trace
    served through the FabricPump twice at EQUAL WORK -- interleaved (each
    fabric tick is ONE fused jitted call executing a CNN wave's levels
    zipped with an LM decode step's) vs serialized (the same tick issues
    the two programs as separate dispatches).  Reported: wall-clock,
    ops/s (CNN images), tokens/s (LM), per-request p50/p99.
  * BIT-IDENTITY on the measured path: CNN logits and LM token ids of
    both legs are asserted identical to each other and to isolated
    per-engine execution.
  * STRUCTURAL zoo sweep: per zoo model, the merged-schedule occupancy of
    the cost DP alignment vs the naive in-order (asap) zip against the LM
    DecodeStep program -- the `policy="cost"` time-weighted occupancy win
    the count-based slack leveling could never show.

    PYTHONPATH=src python -m benchmarks.serve_mixed [--summary|--fast]

--summary merges the "mixed" block into BENCH_serve.json and prints the
one-liner; --fast runs a smaller trace with the same schema.
"""
import time

import numpy as np

from benchmarks import perf_model as pm
from benchmarks.serve_cnn import (SERVE_HW, WAVE, _build_fleet, _reduced,
                                  write_bench_json)
from repro.configs.cnn_zoo import CNN_ZOO

CNN_MODEL = "squeezenet"
LM_ARCH = "qwen2-1.5b"
# 32 images = 8 waves of WAVE=4 next to 4 prompts x 8 tokens in one
# batch-4 admission round = 8 decode ticks: every tick of the co-tenant
# trace has both a wave and a decode step to fuse
MIXED_IMAGES = 32
MIXED_PROMPTS = 4
PROMPT_LEN = 8
NEW_TOKENS = 8
LM_BATCH = 4
MAX_SEQ = 32
FAST_IMAGES = 16      # 4 waves, matching 4 prompts x 4 tokens / batch 4
FAST_PROMPTS = 4
FAST_NEW_TOKENS = 4
# wall-clock is min over REPS timed repeats of the identical workload
# (both legs, same protocol): the traces are tens of ms, so a single
# sample is scheduler noise
REPS = 5


def _tenants(seed=0, fast=False):
    """(cnn fleet entry, lm arch/params/calib, image trace, prompt trace)."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import transformer as T
    from repro.models.params import init_params

    (cfg, params, calib), = _build_fleet(seed=seed, models=[CNN_MODEL])
    arch = configs.reduced(configs.get_arch(LM_ARCH))
    lm_params = init_params(T.lm_schema(arch), jax.random.PRNGKey(7))
    rng = np.random.default_rng(seed)
    lm_calib = [jnp.array(rng.integers(0, arch.vocab_size, (2, PROMPT_LEN))
                          .astype(np.int32))]
    n_img = FAST_IMAGES if fast else MIXED_IMAGES
    n_prm = FAST_PROMPTS if fast else MIXED_PROMPTS
    images = [rng.normal(size=(cfg.input_hw, cfg.input_hw, cfg.input_ch)
                         ).astype(np.float32) for _ in range(n_img)]
    prompts = [rng.integers(0, arch.vocab_size, size=PROMPT_LEN)
               .astype(np.int32) for _ in range(n_prm)]
    return (cfg, params, calib), (arch, lm_params, lm_calib), images, prompts


def _build_pump(cnn_entry, lm_entry, interleave: bool, merge_policy="cost"):
    from repro.core import engine as eng_lib
    from repro.core.config import EngineConfig
    from repro.serve.base import FabricPump
    from repro.serve.cnn_engine import CNNServeEngine
    from repro.serve.engine import ServeEngine

    cfg, params, calib = cnn_entry
    arch, lm_params, lm_calib = lm_entry
    cnn = CNNServeEngine(eng_lib.paper_engine(), wave_size=WAVE)
    cnn.register(cfg, params, calib_batches=[calib])
    lm = ServeEngine(arch, lm_params, EngineConfig(quant="w8a8",
                                                   backend="ref"),
                     batch_size=LM_BATCH, max_seq=MAX_SEQ,
                     calib_batches=lm_calib, prefill_len=PROMPT_LEN)
    return FabricPump(cnn, lm, merge_policy=merge_policy,
                      interleave=interleave)


def mixed_stats(fast: bool = False, seed: int = 0):
    """Serve the same two-tenant trace interleaved and serialized at equal
    work; assert output bit-identity against isolated engines; return the
    measured comparison (the BENCH "mixed" block's core)."""
    cnn_entry, lm_entry, images, prompts = _tenants(seed=seed, fast=fast)
    cfg = cnn_entry[0]
    new_tokens = FAST_NEW_TOKENS if fast else NEW_TOKENS

    def leg(interleave: bool):
        pump = _build_pump(cnn_entry, lm_entry, interleave)
        # warmup: the full workload once -- traces the prefill, decode,
        # fused-tick and solo-wave executables, then drop its clocks
        pump.run(cfg.name, images, prompts, max_new_tokens=new_tokens)
        pump.latency = pump.latency.__class__()
        pump.cnn.latency = pump.cnn.latency.__class__()
        pump.lm.latency = pump.lm.latency.__class__()
        ticks0 = pump.stats()["ticks"]
        fused0 = pump.stats()["fused_ticks"]
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            logits, tokens = pump.run(cfg.name, images, prompts,
                                      max_new_tokens=new_tokens)
            walls.append(time.perf_counter() - t0)
        wall = min(walls)
        return {
            "wall_s": wall,
            "wall_s_all": walls,
            "ops_per_s": len(images) / wall,
            "tokens_per_s": len(prompts) * new_tokens / wall,
            "latency_ms": pump.latency.percentiles(),
            "ticks": (pump.stats()["ticks"] - ticks0) // REPS,
            "fused_ticks": (pump.stats()["fused_ticks"] - fused0) // REPS,
        }, logits, tokens, pump

    inter, il_logits, il_tokens, pump = leg(True)
    serial, sr_logits, sr_tokens, _ = leg(False)

    # isolated execution: each engine alone, same requests
    iso = _build_pump(cnn_entry, lm_entry, interleave=True)
    iso_logits = [np.asarray(r) for r in
                  iso.cnn.infer(cfg.name, np.stack(images))]
    iso_tokens = list(iso.lm.generate(list(prompts),
                                      max_new_tokens=new_tokens))
    identical = True
    for a, b, c in zip(iso_logits, il_logits, sr_logits):
        identical &= bool(np.array_equal(a, b) and np.array_equal(a, c))
    for a, b, c in zip(iso_tokens, list(il_tokens.values()),
                       list(sr_tokens.values())):
        identical &= bool(np.array_equal(a, b) and np.array_equal(a, c))
    assert identical, "interleaved/serialized outputs diverged from isolated"

    merged = pump.stats().get("merged", {})
    return {
        "trace": {"cnn_model": cfg.name, "lm_arch": lm_entry[0].name,
                  "images": len(images), "prompts": len(prompts),
                  "new_tokens": new_tokens, "wave_size": WAVE,
                  "lm_batch": LM_BATCH, "input_hw": SERVE_HW},
        "interleaved": inter,
        "serialized": serial,
        "speedup": serial["wall_s"] / inter["wall_s"],
        "identical_outputs": identical,
        "merged_schedule": merged,
    }


def fabric_occupancy(lm_arch: str = LM_ARCH):
    """Structural zoo sweep: per CNN zoo model, the merged-schedule
    makespan + time-weighted occupancy of cost-DP alignment vs the naive
    in-order zip against the LM DecodeStep program.  The acceptance gate:
    cost occupancy strictly above asap's on >= 3 zoo models."""
    from repro import compiler, configs

    arch = configs.reduced(configs.get_arch(lm_arch))
    dec = compiler.compile_lm(arch, mode="decode")
    times_b = pm.lm_node_times(dec.graph, arch, LM_BATCH, 1,
                               cache_len=PROMPT_LEN + NEW_TOKENS // 2)
    out = {}
    for name in CNN_ZOO:
        cfg = _reduced(name)
        prog = compiler.compile_cnn(cfg, policy="cost")
        times_a = pm.cnn_node_times(prog.graph, cfg)
        occ = {}
        for policy in ("asap", "cost"):
            m = compiler.merge_schedules(prog.graph, prog.schedule,
                                         dec.graph, dec.schedule,
                                         times_a, times_b, policy=policy)
            occ[policy] = {"occupancy": m.stats["occupancy"],
                           "makespan": m.stats["makespan"],
                           "ticks": m.stats["ticks"]}
        out[name] = {
            "asap": occ["asap"]["occupancy"],
            "cost": occ["cost"]["occupancy"],
            "makespan_asap": occ["asap"]["makespan"],
            "makespan_cost": occ["cost"]["makespan"],
            "serialized_makespan": m.stats["serialized_makespan"],
            "cost_wins": occ["cost"]["occupancy"] > occ["asap"]["occupancy"],
        }
    return out


def bench_block(fast: bool = False):
    """The "mixed" block merged into BENCH_serve.json."""
    block = mixed_stats(fast=fast)
    fo = fabric_occupancy()
    block["fabric_occupancy"] = fo
    block["cost_beats_asap_models"] = sum(
        1 for v in fo.values() if v["cost_wins"])
    return block


def summary_line(fast: bool = False) -> str:
    block = bench_block(fast=fast)
    # the fast smoke rides its own key: it is a different trace shape, so
    # letting it overwrite "mixed" would make cross-run comparisons
    # (scripts/bench_guard.py) apples-to-oranges
    write_bench_json({"mixed_fast" if fast else "mixed": block})
    i, s = block["interleaved"], block["serialized"]
    wins = block["cost_beats_asap_models"]
    return (f"mixed co-tenancy ({block['trace']['cnn_model']}+"
            f"{block['trace']['lm_arch']}): interleaved "
            f"{i['ops_per_s']:.1f} img/s + {i['tokens_per_s']:.1f} tok/s "
            f"vs serialized {s['ops_per_s']:.1f} + {s['tokens_per_s']:.1f} "
            f"({block['speedup']:.2f}x wall), p99 "
            f"{i['latency_ms'].get('p99_ms', 0.0):.0f}ms vs "
            f"{s['latency_ms'].get('p99_ms', 0.0):.0f}ms, bit-identical "
            f"outputs={int(block['identical_outputs'])}; merged cost "
            f"occupancy beats asap zip on {wins}/{len(CNN_ZOO)} zoo models")


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--summary", action="store_true",
                    help="one-line co-tenancy summary; merges the 'mixed' "
                         "block into BENCH_serve.json")
    ap.add_argument("--fast", action="store_true",
                    help="smaller trace, same schema")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.summary:
        print(summary_line(fast=args.fast))
    else:
        print(json.dumps(bench_block(fast=args.fast), indent=2,
                         sort_keys=True))
