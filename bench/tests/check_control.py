"""The check's control and planted faults: each must make `correct` false.

Not part of the repository's quick test suite (it builds whole models);
run it by hand:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/check_control.py

which runs both configurations at 32 px on the CPU (Pallas in interpret
mode), and on the chip at the cells' own size:

    python3 bench/tests/check_control.py --config resnet50-224-int8 \
        --seeds 11 12 13

which prints, per seed, the logit_err of the int4 control (the reference
computed in int4, the precision below the configuration's int8) and of an
int8 fake-quantized reference (a witness of what int8 alone costs) over as
many pool images as a run compares.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import pytest  # noqa: E402

CONFIGS = ("resnet50-224-int8", "mobilenetv2-224-int8")
SMALL = 32


def control_readings(cfg: dict, seed: int, n: int) -> dict:
    """logit_err of the int4 control and the int8 witness against the
    float32 reference, on n seeded pool images."""
    import jax

    from bench import model, traffic
    from bench.reference import Reference
    from bench.run import logit_err

    params = jax.block_until_ready(model.make_params(cfg, seed))
    pool = traffic.image_pool(cfg, n, seed)
    calib = traffic.calibration_batch(cfg, seed)
    want = Reference(cfg, params)(pool)
    out = {}
    for name, bits in (("int4_control", 4), ("int8_witness", 8)):
        got = Reference(cfg, params, bits=bits, calib=calib)(pool)
        out[name] = logit_err(got, want)
    return out


def small(name: str) -> dict:
    from bench import model
    cfg = model.load_config(name)
    cfg["input"]["size"] = SMALL
    return cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_int4_control_fails_the_limit(name):
    cfg = small(name)
    for seed in (1, 2, 2 ** 31 + 5):
        r = control_readings(cfg, seed, 16)
        assert r["int4_control"] > cfg["check"]["logit_err"], r


def _small_run(name: str, traffic_name: str):
    from bench import run as bench_run
    from bench import traffic
    mix = dict(traffic.load_mix(traffic_name), pool_images=16,
               check_samples=16)
    if mix["wave_size"] > 1:
        mix.update(wave_size=4, queued_waves=2)
    return bench_run.run(
        f"{name}.{traffic_name}", 2 ** 31 + 7, 0.5, False,
        bench_run.load_benchmark(), require_tpu=False,
        configs={name: small(name)}, mixes={traffic_name: mix})


def _planted(monkeypatch, fault):
    """Break the served program where it runs: wrap compiler.execute, which
    the engine's jitted wave program calls."""
    from repro import compiler
    execute = compiler.execute

    def broken(prog, params, images, eng):
        if fault == "half_batch":
            # the second half of every wave is left out: its rows are
            # served as blank images
            half = images.shape[0] // 2
            images = images.at[half:].set(0.0) if half else images * 0.0
            return execute(prog, params, images, eng)
        logits = execute(prog, params, images, eng)
        # one answer in four is altered where it is produced
        big = 10.0 * abs(logits).max()
        return logits.at[::4, 0].add(big)

    monkeypatch.setattr(compiler, "execute", broken)


def test_sound_run_is_correct():
    assert _small_run(CONFIGS[0], "offline")["correct"]


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
@pytest.mark.parametrize("traffic_name", ["offline", "single-stream"])
def test_planted_fault_is_not_correct(monkeypatch, fault, traffic_name):
    _planted(monkeypatch, fault)
    assert not _small_run(CONFIGS[0], traffic_name)["correct"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True, choices=CONFIGS)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--samples", type=int, default=256)
    args = ap.parse_args(argv)
    from bench import model
    from bench.run import use_checkout_cache
    use_checkout_cache()
    import jax
    cfg = model.load_config(args.config)
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    for seed in args.seeds:
        r = control_readings(cfg, seed, args.samples)
        print(json.dumps({"config": args.config, "seed": seed,
                          "limit": cfg["check"]["logit_err"], **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
