"""Chip smoke test: the DPUV4E serving path on a TPU, end to end.

One process drives the normal serving entry points at published widths with
random weights made from a seed, and checks what comes out:

  * CNN phase -- resnet50 (standard conv) and mobilenetv2 (depthwise) at
    224 px through CNNServeEngine with the Pallas engines compiled: 16
    images per model in waves of 8, served logits checked against the same
    static int8 program on the `ref` backend, top-1 agreement with the
    float32 model reported;
  * LM phase -- qwen2-1.5b through ServeEngine (w8a8, calibrated, paged
    KV): 8 prompts of 128 tokens, 16 new tokens each, first-step logits
    checked against the `ref` backend;
  * the number of Mosaic kernels (tpu_custom_call) in the compiled CNN and
    decode programs, which must not be zero.

With `--chips 4` it runs only the sharded serving path: data-parallel
resnet50 waves on a 4-way data mesh (Pallas engines, one program per
replica) and tensor-parallel qwen2-1.5b decode on a 4-way model mesh
(`ref` engines: XLA cannot partition Mosaic kernels), each compared with
one device.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host with four chips

The last line of stdout is one JSON object with the device JAX reports.  A
host without a TPU exits non-zero before any phase runs.  Seconds printed
per phase are bring-up observations, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
CNN_MODELS = ("resnet50", "mobilenetv2")
CNN_REQUESTS = 16
WAVE = 8
LM_ARCH = "qwen2-1.5b"
LM_BATCH = 8
PROMPT_LEN = 128
NEW_TOKENS = 16
PAGE = 16

# Served logits vs the `ref` backend, as a share of the largest |ref| logit.
# Both backends run the same static int8 program: every GEMM and depthwise
# tap accumulates exactly in int32, and attention, norms and RoPE are the
# same jnp code on both.  On the CPU the interpreted kernels agree with ref
# to 3e-7 (reduced qwen2-1.5b, first-step and teacher-forced decode
# logits).  On the chip the kernels' f32 epilogues (dequant, bias, requant
# division) are compiled by Mosaic and ref's by XLA, which may round a value
# sitting on a requant rounding boundary to the neighbouring int8 code;
# requant_witness() counts those flips at a real projection shape.  Flips
# propagate through the layers: on the CPU, moving every requant input by
# 2 ulp moved these random-weight models' logits by 1.1e-2 (resnet50),
# 5.3e-2 (mobilenetv2) and 7.3e-2 (qwen2-1.5b, first step) of the largest
# logit; each bound is about twice that.  A wrong kernel (a bad tile edge,
# a dropped tap) moves logits by their own size.
CNN_REF_TOL = 0.1
LM_REF_TOL = 0.15


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Times a phase and prints its wall seconds on exit."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"== {self.name}")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"== {self.name}: {time.perf_counter() - self.t0:.1f} s wall")
        return False


def mosaic_kernels(jitted, *args) -> int:
    """Compile `jitted` for `args` and count the Mosaic kernels in it."""
    t0 = time.perf_counter()
    text = jitted.lower(*args).compile().as_text()
    log(f"   compile {time.perf_counter() - t0:.1f} s")
    return text.count("tpu_custom_call")


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# CNN
# ---------------------------------------------------------------------------

def serve_cnn(eng, cfg, params, calib, images, mesh=None):
    """Serve `images` through a CNNServeEngine: submit + pump, then flush.
    Returns (logits [N, classes] in submission order, engine)."""
    import numpy as np
    from repro.serve.cnn_engine import CNNServeEngine

    srv = CNNServeEngine(eng, wave_size=WAVE, mesh=mesh)
    srv.register(cfg, params, calib_batches=[calib])
    tickets, done = [], {}
    for img in images:
        tickets.append(srv.submit(cfg.name, img))
        done.update(srv.pump())
    rest = [t for t in tickets if t not in done]
    done.update(zip(rest, srv.flush()))
    return np.stack([done[t] for t in tickets]), srv


def cnn_inputs(name: str):
    import jax
    import numpy as np
    from repro.configs.cnn_zoo import CNN_ZOO
    from repro.models import cnn
    from repro.models.params import init_params

    cfg = CNN_ZOO[name]
    rng = np.random.default_rng(SEED)
    hw = cfg.input_hw
    params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(SEED))
    calib = rng.uniform(-1, 1, (WAVE, hw, hw, cfg.input_ch)).astype(
        np.float32)
    images = rng.uniform(-1, 1, (CNN_REQUESTS, hw, hw, cfg.input_ch)
                         ).astype(np.float32)
    return cfg, params, calib, images


def cnn_phase(name: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import compiler
    from repro.core import engine as eng_lib
    from repro.core.config import EngineConfig
    from repro.models import cnn

    cfg, params, calib, images = cnn_inputs(name)
    with Phase(f"cnn {name} pallas ({CNN_REQUESTS} requests, waves of "
               f"{WAVE}, {cfg.input_hw} px)"):
        eng = eng_lib.paper_engine(backend="pallas")
        got, srv = serve_cnn(eng, cfg, params, calib, images)
        st = srv.stats()
        log(f"   waves {st['waves']}, program execs {st['program_execs']}")
        prog = srv.program_for(name)
        qp = compiler.fold_weight_layouts(
            prog.graph, eng_lib.quantize_params(params, eng))
        n_k = mosaic_kernels(
            jax.jit(lambda p, im: compiler.execute(prog, p, im, eng)), qp,
            jnp.zeros((WAVE,) + images.shape[1:], jnp.float32))
        log(f"   Mosaic kernels in the compiled {name} program: {n_k}")
        if n_k == 0:
            raise RuntimeError(f"{name}: no Mosaic kernel was compiled")
        del srv, qp
        gc.collect()
    with Phase(f"cnn {name} ref (same static program)"):
        want, srv = serve_cnn(eng_lib.paper_engine(backend="ref"), cfg,
                              params, calib, images)
        del srv
        gc.collect()
    with Phase(f"cnn {name} float32 model"):
        fl = np.asarray(jax.jit(lambda p, x: cnn.cnn_forward(
            p, x, cfg, EngineConfig(quant="none")))(params, images))
    if got.shape != (CNN_REQUESTS, cfg.num_classes):
        raise RuntimeError(f"{name}: logits shape {got.shape}")
    if not np.all(np.isfinite(got)):
        raise RuntimeError(f"{name}: non-finite logits")
    err = rel_err(got, want)
    top_ref = int(np.sum(got.argmax(-1) == want.argmax(-1)))
    top_fl = int(np.sum(got.argmax(-1) == fl.argmax(-1)))
    log(f"   pallas vs ref: max |diff| / max |ref| = {err:.3e} "
        f"(bound {CNN_REF_TOL}), top-1 agree {top_ref}/{CNN_REQUESTS}")
    log(f"   int8 pallas vs float32 model: top-1 agree "
        f"{top_fl}/{CNN_REQUESTS}")
    if err > CNN_REF_TOL:
        raise RuntimeError(f"{name}: pallas logits off the ref backend")


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------

def lm_inputs():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models import params as prm
    from repro.models import transformer as T

    arch = configs.get_arch(LM_ARCH)
    params = prm.init_params(T.lm_schema(arch), jax.random.PRNGKey(SEED),
                             dtype=jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    calib = rng.integers(0, arch.vocab_size, (LM_BATCH, PROMPT_LEN)
                         ).astype(np.int32)
    prompts = rng.integers(0, arch.vocab_size, (LM_BATCH, PROMPT_LEN)
                           ).astype(np.int32)
    return arch, params, calib, prompts


def lm_engine(arch, params, calib, eng, **kw):
    from repro.serve.engine import ServeEngine
    return ServeEngine(arch, params, eng, batch_size=LM_BATCH,
                       max_seq=PROMPT_LEN + NEW_TOKENS,
                       calib_batches=[calib], prefill_len=PROMPT_LEN, **kw)


def scored_logits(se, prompts, forced):
    """The served programs' logits with the generated ids forced: the paged
    prefill program fills a fresh cache (slot b owns blocks b*P .. b*P+P-1)
    and returns the first-step logits [B, V]; the decode program then
    verifies every forced id in one pass (execute_verify, no argmax
    feedback), giving teacher-forced decode logits [B, W, V] at positions
    prompt .. prompt+W-1.  No near-tie argmax can fork the two backends'
    token streams, so the decode step's numerics are compared directly."""
    import jax
    import jax.numpy as jnp
    from repro.compiler import executor as ex

    b, pages = LM_BATCH, se.kv_pages
    cache = se._empty_cache()
    cache["tables"] = jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    cache["pos"] = jnp.zeros((b,), jnp.int32)
    prefill, decode = se.prefill_program(), se.decode_program()
    logits, cache = jax.jit(lambda p, c, t: se._run_paged_prefill(
        prefill, p, c, {"tokens": t}, jnp.ones((b,), bool)))(
            se.params, cache, jnp.asarray(prompts))
    dec, _ = jax.jit(lambda p, c, t: ex.execute_verify(
        decode, p, c, t, se.eng))(se.params, cache, jnp.asarray(forced))
    return logits[:, -1], dec


def lm_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import compiler
    from repro.compiler import executor as ex
    from repro.core import engine as eng_lib
    from repro.core.config import EngineConfig
    from repro.models import transformer as T
    from repro.models.params import is_spec

    arch, params, calib, prompts = lm_inputs()
    with Phase(f"lm {LM_ARCH} pallas (w8a8, paged KV, {LM_BATCH} x "
               f"{PROMPT_LEN} prompt tokens, {NEW_TOKENS} new)"):
        eng = eng_lib.paper_engine(backend="pallas")
        se = lm_engine(arch, params, calib, eng, kv_layout="paged",
                       page_size=PAGE)
        outs = se.generate(list(prompts), max_new_tokens=NEW_TOKENS)
        st = se.stats()
        log(f"   served {len(outs)} requests, "
            f"{sum(len(o) for o in outs)} tokens")
        if len(outs) != LM_BATCH or any(len(o) != NEW_TOKENS for o in outs):
            raise RuntimeError("lm: wrong number of generated tokens")
        log(f"   ids[0][:8] {outs[0][:8].tolist()}, kv {st.get('kv_layout')}")
        prog = se.decode_program()
        cs = T.paged_cache_schema(arch, LM_BATCH, se.max_seq, eng, PAGE,
                                  num_blocks=se.alloc.num_blocks)
        cache = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), cs,
            is_leaf=is_spec)
        cache["tables"] = jax.ShapeDtypeStruct((LM_BATCH, se.kv_pages),
                                               jnp.int32)
        cache["pos"] = jax.ShapeDtypeStruct((LM_BATCH,), jnp.int32)
        n_k = mosaic_kernels(
            jax.jit(lambda p, c, t: ex.execute_decode(prog, p, c, t, eng)),
            se.params, cache, jax.ShapeDtypeStruct((LM_BATCH, 1), jnp.int32))
        log(f"   Mosaic kernels in the compiled decode program: {n_k}")
        if n_k == 0:
            raise RuntimeError("decode: no Mosaic kernel was compiled")
        got = [np.asarray(a, np.float32)
               for a in scored_logits(se, prompts, np.stack(outs))]
        del se
        gc.collect()
    with Phase(f"lm {LM_ARCH} ref (same static programs)"):
        se = lm_engine(arch, params, calib,
                       eng_lib.paper_engine(backend="ref"),
                       kv_layout="paged", page_size=PAGE)
        want = [np.asarray(a, np.float32)
                for a in scored_logits(se, prompts, np.stack(outs))]
        ref_outs = se.generate(list(prompts), max_new_tokens=NEW_TOKENS)
        del se
        gc.collect()
    with Phase(f"lm {LM_ARCH} float model (bf16 params, no quantization)"):
        prog = compiler.compile_lm(arch, mode="prefill")
        fl = np.asarray(jax.jit(lambda p, t: ex.execute(
            prog, p, t, EngineConfig(quant="none", backend="ref")))(
                params, jnp.asarray(prompts))[:, -1], np.float32)
    requant_witness()
    for name, a in (("first-step", got[0]), ("teacher-forced decode", got[1])):
        if not np.all(np.isfinite(a)):
            raise RuntimeError(f"lm: non-finite {name} logits")
    err, dec_err = rel_err(got[0], want[0]), rel_err(got[1], want[1])
    dec_top = int(np.sum(got[1].argmax(-1) == want[1].argmax(-1)))
    same = sum(int(np.array_equal(a, b)) for a, b in zip(outs, ref_outs))
    ids = sum(int(np.sum(a == b)) for a, b in zip(outs, ref_outs))
    log(f"   first-step logits pallas vs ref: max |diff| / max |ref| = "
        f"{err:.3e} (bound {LM_REF_TOL})")
    log(f"   teacher-forced decode logits pallas vs ref ({LM_BATCH} x "
        f"{NEW_TOKENS} positions): {dec_err:.3e} (bound {LM_REF_TOL}), "
        f"argmax agree {dec_top}/{LM_BATCH * NEW_TOKENS}")
    log(f"   first-step logits vs the float model: pallas "
        f"{rel_err(got[0], fl):.3e}, ref {rel_err(want[0], fl):.3e}; "
        f"argmax agree with float: pallas "
        f"{int(np.sum(got[0].argmax(-1) == fl.argmax(-1)))}/{LM_BATCH}, ref "
        f"{int(np.sum(want[0].argmax(-1) == fl.argmax(-1)))}/{LM_BATCH}")
    log(f"   generated ids equal to ref (free-running greedy): "
        f"{same}/{LM_BATCH} requests, {ids}/{LM_BATCH * NEW_TOKENS} tokens")
    if err > LM_REF_TOL:
        raise RuntimeError("lm: pallas first-step logits off the ref backend")
    if dec_err > LM_REF_TOL:
        raise RuntimeError("lm: pallas decode logits off the ref backend")


def requant_witness() -> None:
    """The same int8 operands through the Pallas Conv PE and the ref oracle
    at qwen2-1.5b's fused QKV projection (1024 prefill rows, K=1536,
    N=2048): the int32 MAC must be bitwise equal, and the requantized int8
    codes may differ only by one, where the two compilers' f32 epilogues
    round a value on a rounding boundary differently."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import conv_pe, ops, ref

    m, k, n = 1024, 1536, 2048
    rng = np.random.default_rng(SEED)
    a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    asc = jnp.asarray(rng.uniform(0.01, 0.02, (m, 1)), jnp.float32)
    wsc = jnp.asarray(rng.uniform(1e-4, 2e-4, (1, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=n), jnp.float32)
    one_m, one_n = jnp.ones((m, 1), jnp.float32), jnp.ones((1, n), jnp.float32)

    def both(*args, **kw):
        kern = jax.jit(lambda *x: conv_pe.matmul_int8_fused(
            *x, bm=256, bn=256, bk=512, interpret=ops.interpret_pallas(),
            **kw))
        oracle = jax.jit(lambda *x: ref.matmul_int8_fused(*x, **kw))
        return np.asarray(kern(*args)), np.asarray(oracle(*args))

    mac_p, mac_r = both(a, b, one_m, one_n, None)
    f_p, f_r = both(a, b, asc, wsc, bias)
    out_scale = float(np.max(np.abs(f_r))) / 127
    q_p, q_r = both(a, b, asc, wsc, bias, out_scale=out_scale)
    ulps = np.abs(f_p.view(np.int32).astype(np.int64)
                  - f_r.view(np.int32).astype(np.int64))
    dq = np.abs(q_p.astype(np.int32) - q_r.astype(np.int32))
    log(f"   requant witness (Conv PE vs ref, {m}x{k}x{n}): int32 MAC "
        f"bitwise {np.array_equal(mac_p, mac_r)}; f32 epilogue "
        f"{int(np.sum(ulps > 0))}/{ulps.size} differ, max {int(ulps.max())} "
        f"ulp; int8 codes {int(np.sum(dq > 0))}/{dq.size} differ, max "
        f"{int(dq.max())}")
    if not np.array_equal(mac_p, mac_r) or dq.max() > 1:
        raise RuntimeError("requant witness: Conv PE off the ref oracle")


# ---------------------------------------------------------------------------
# Four chips: sharded serving vs one device
# ---------------------------------------------------------------------------

def sharded_cnn_phase(name: str, n: int) -> None:
    import numpy as np
    from repro.core import engine as eng_lib
    from repro.serve.mesh_exec import make_serve_mesh

    cfg, params, calib, images = cnn_inputs(name)
    eng = eng_lib.paper_engine(backend="pallas")
    with Phase(f"cnn {name} data-parallel waves on {n} chips"):
        got, srv = serve_cnn(eng, cfg, params, calib, images,
                             mesh=make_serve_mesh(n_data=n))
        log(f"   wave rows {srv.wave_rows}, waves {srv.stats()['waves']}")
        del srv
        gc.collect()
    with Phase(f"cnn {name} one chip"):
        want, srv = serve_cnn(eng, cfg, params, calib, images)
        del srv
        gc.collect()
    err = rel_err(got, want)
    log(f"   {n} chips vs one: max |diff| / max |ref| = {err:.3e}, "
        f"bitwise {np.array_equal(got, want)}")
    if err > CNN_REF_TOL:
        raise RuntimeError(f"{name}: sharded logits off one device")


def sharded_lm_phase(n: int) -> None:
    import numpy as np
    from repro.core import engine as eng_lib
    from repro.serve.mesh_exec import make_serve_mesh

    arch, params, calib, prompts = lm_inputs()
    # XLA cannot partition Mosaic kernels, so tensor-parallel serving (and
    # the one-chip run it is compared with) runs the XLA-compiled `ref`
    # engines; ServeEngine refuses a mesh with compiled Pallas kernels
    eng = eng_lib.paper_engine(backend="ref")
    with Phase(f"lm {LM_ARCH} tensor-parallel decode on {n} chips"):
        se = lm_engine(arch, params, calib, eng,
                       mesh=make_serve_mesh(n_data=1, n_model=n))
        tp = se.generate(list(prompts), max_new_tokens=NEW_TOKENS)
        log(f"   tp placement {se.stats().get('tp_placement')}")
        del se
        gc.collect()
    with Phase(f"lm {LM_ARCH} one chip"):
        se = lm_engine(arch, params, calib, eng)
        one = se.generate(list(prompts), max_new_tokens=NEW_TOKENS)
        del se
        gc.collect()
    same = sum(int(np.array_equal(a, b)) for a, b in zip(tp, one))
    log(f"   ids equal to one chip: {same}/{LM_BATCH} requests")
    if same != LM_BATCH:
        raise RuntimeError("lm: tensor-parallel ids differ from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded serving path")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        log("no TPU found: this smoke test runs only on a TPU")
        return 2
    if len(devs) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        return 2

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"pallas interpret mode: {ops.interpret_pallas()}")
    if ops.interpret_pallas():
        return 2
    t0 = time.perf_counter()
    if args.chips == 4:
        # data parallelism is one shard_map around the whole program, the
        # same for every CNN: one model exercises it
        sharded_cnn_phase(CNN_MODELS[0], args.chips)
        sharded_lm_phase(args.chips)
    else:
        for name in CNN_MODELS:
            cnn_phase(name)
        lm_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
