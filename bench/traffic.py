"""The one traffic generator: a closed loop over a pool of preloaded images.

A mix is a JSON file under bench/mixes/ with these keys:

  wave_size      -- slots per wave of the serving engine;
  queued_waves   -- full waves the client keeps queued: each round it
                    submits wave_size x queued_waves requests, pumps the
                    engine, and starts the next round once every answer of
                    this one is on the host (MLPerf Offline keeps the queue
                    full; SingleStream is 1 x 1, one request at a time);
  pool_images    -- distinct images loaded into host memory before the
                    window, as MLPerf's LoadSamplesToRam does;
  check_samples  -- answers from the window compared with the reference;
  trace_seconds  -- the window of a traced run.

Requests take the pool's images in an order drawn from the seed; every seed
serves the same sizes, so only the pixel values and the order change.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "mixes"
MIX_KEYS = {"name", "scenario", "wave_size", "queued_waves", "pool_images",
            "check_samples", "trace_seconds"}


def mix_names() -> List[str]:
    return sorted(p.stem for p in MIX_DIR.glob("*.json"))


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} under {MIX_DIR} "
                       f"(have {mix_names()})")
    mix = json.loads(path.read_text())
    if set(mix) != MIX_KEYS or mix["name"] != name:
        raise ValueError(f"{path}: keys {sorted(mix)}, want "
                         f"{sorted(MIX_KEYS)} with name {name!r}")
    return mix


def image_pool(cfg: dict, n: int, seed: int) -> np.ndarray:
    """n distinct images [n, H, W, C], float32 uniform in [-1, 1)."""
    size, ch = cfg["input"]["size"], cfg["input"]["channels"]
    rng = np.random.default_rng([seed, 1])
    pool = rng.random((n, size, size, ch), dtype=np.float32)
    pool *= 2.0
    pool -= 1.0
    return pool


def calibration_batch(cfg: dict, seed: int) -> np.ndarray:
    size, ch = cfg["input"]["size"], cfg["input"]["channels"]
    rng = np.random.default_rng([seed, 2])
    return (rng.random((cfg["calibration_images"], size, size, ch),
                       dtype=np.float32) * 2.0 - 1.0)


@dataclasses.dataclass
class Window:
    """What one measured window served."""
    seconds: float = 0.0
    attempted: int = 0
    completed: int = 0
    waves: int = 0
    latency_s: List[float] = dataclasses.field(default_factory=list)
    image_of: List[int] = dataclasses.field(default_factory=list)
    answers: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    per_second: List[int] = dataclasses.field(default_factory=list)


def drive(srv, model: str, pool: np.ndarray, mix: dict, seed: int,
          seconds: float, annotate=None) -> Window:
    """Run the closed loop for `seconds` and return what it served.

    Requests are numbered from 0 in submission order; the window ends with
    the first round that finishes after `seconds` (at least one round), so
    its time and its work both include that round.  `annotate(name)`
    returns a context manager that times a host span (in a traced run,
    bench.trace.SpanRecorder.span)."""
    span = annotate or (lambda name: contextlib.nullcontext())
    per_round = mix["wave_size"] * mix["queued_waves"]
    order = np.random.default_rng([seed, 3]).permutation(len(pool))
    w = Window()
    sent_at: Dict[int, float] = {}
    number: Dict[int, int] = {}
    waves0 = srv.wave_stats.waves
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        with span("round"):
            with span("submit"):
                for _ in range(per_round):
                    r = w.attempted
                    img = int(order[r % len(order)])
                    t = time.perf_counter()
                    ticket = srv.submit(model, pool[img])
                    sent_at[ticket] = t
                    number[ticket] = r
                    w.image_of.append(img)
                    w.attempted += 1
            with span("pump"):
                done = srv.pump()
            now = time.perf_counter()
        with span("record"):
            for ticket, logits in done.items():
                w.latency_s.append(now - sent_at.pop(ticket))
                w.answers[number.pop(ticket)] = logits
            w.completed += len(done)
            sec = int(now - t0)
            while len(w.per_second) <= sec:
                w.per_second.append(0)
            w.per_second[sec] += len(done)
        if now >= t_end:
            break
    w.seconds = now - t0
    w.waves = srv.wave_stats.waves - waves0
    return w
