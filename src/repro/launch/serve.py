"""Serving driver: quantize a model with the DPUV4E engine config and serve
batched requests (greedy decode) -- the small-scale executable twin of the
production decode program (launch/build.build_serve).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --requests 8 --new-tokens 16 --quant w8a8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core import engine as eng_lib
from repro.core.config import EngineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as prm
from repro.models import transformer as T
from repro.models import whisper as W
from repro.serve.engine import ServeEngine, throughput_probe


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--quant", default="w8a8",
                    choices=["none", "w8", "w8a8"])
    ap.add_argument("--kv", default="bf16", choices=["bf16", "int8"])
    ap.add_argument("--baseline", action="store_true")
    args = ap.parse_args(argv)

    arch = configs.get_arch(args.arch)
    if args.smoke:
        arch = configs.reduced(arch)
    enable_compile_cache()
    # the Pallas engines on a TPU; elsewhere the jnp oracle path (Pallas
    # would only run interpreted there)
    backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    eng = EngineConfig(quant=args.quant, backend=backend,
                       kv_cache_dtype=args.kv,
                       baseline=args.baseline).resolved()

    schema = (W.whisper_schema(arch, max_dec_pos=256)
              if arch.family == "audio" else T.lm_schema(arch))
    params = prm.init_params(schema, jax.random.PRNGKey(0))
    engine = ServeEngine(arch, params, eng, batch_size=args.batch,
                         max_seq=args.prompt_len + args.new_tokens + 8)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, arch.vocab_size, size=args.prompt_len)
               for _ in range(args.requests)]
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"served {len(outs)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, quant={args.quant}, kv={args.kv}, "
          f"backend={backend}, device={jax.devices()[0].device_kind})")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o[:12].tolist()}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
