"""CNN serving engine: cached programs + continuous wave batching +
concurrent PEs.

The CNN instance of the shared program-serving pipeline (serve/base.py);
the LM `ServeEngine` (serve/engine.py) rides the same base -- and the same
SlotScheduler -- for transformer prefill + decode.  One engine serves many
registered CNNs on one fabric (the f-CNNx setting):

  * compile  -- each (model, engine, calibration) triple lowers once to a
    static-int8 (or dynamic) engine program, epilogue-FUSED by default:
    conv/dwc -> {residual add, pool} chains execute as single launches
    (passes.fuse_epilogues), so a served wave dispatches ~25% fewer
    kernels per ResNet-style image with bit-identical logits;
  * cache    -- programs live in a keyed LRU ProgramCache, so a request
    trace that revisits models never re-traces or re-calibrates;
  * batch    -- incoming single-image requests queue in the shared
    SlotScheduler keyed by INPUT SHAPE, not by model: models with identical
    shapes draw slots from one queue, so a tail wave packs requests from
    several models into one buffer.  `pump()` dispatches only FULL waves
    and leaves partial waves queued for later arrivals to top up
    (continuous batching); `flush()` drains, padding the final partial
    wave per shape (the only place pad slots are charged);
  * schedule -- the programs carry the level schedule from
    compiler/schedule.py (ASAP or ALAP), so execution dispatches
    independent ops (a DWC branch next to a Conv branch, MISC alongside
    Conv) per concurrent wave;
  * fold     -- the first time a model's program is bound, its weight
    layout transforms (im2col reshape, DWC lane padding) are constant-
    folded into the param tree (passes.fold_weight_layouts), so traced
    programs stop re-laying-out weights per call;
  * shard    -- with `mesh=` the engine serves data-parallel across the
    mesh (serve/mesh_exec.py): the physical wave grows to one wave_size
    slot pool PER data replica, the buffer shards over the batch axis
    (weights replicate), and the SlotScheduler's locality-aware refill
    packs each model's requests into its sticky replica's pool first.
    Sharded logits are bit-identical to single-device (int8 GEMMs
    accumulate in int32, so replica-local rows are exact);
  * async    -- dispatch launches every wave and keeps logits as device
    arrays in flight; the host syncs (one np.asarray per wave-model
    execution) only at the response edges of pump()/flush()/infer(), so
    assembling wave N+1 overlaps the device executing wave N.

A multi-model wave executes the shared buffer once per distinct model in
it and each request reads its own slot's logits (CNN programs are
batch-row independent, so foreign slots cannot perturb a request's
output -- the wave parity test pins that).  `wave_stats.waves` counts
physical buffers; `program_execs` counts program runs.

Usage (examples/serve_cnn_int8.py is the runnable version):

    engine = CNNServeEngine(eng_lib.paper_engine(), wave_size=4)
    engine.register(cfg, params, calib_batches=[batch])
    for img in images:
        engine.submit(cfg.name, img)
        engine.pump()                # dispatch full waves only
    logits = engine.flush()          # drain; per-request, submission order
    print(engine.stats())            # cache hit-rate, wave fill-rate
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compiler
from repro.compiler.executor import Program
from repro.core import engine as eng_lib
from repro.core.config import CNNConfig, EngineConfig
from repro.serve.base import (ProgramServeBase, SlotScheduler,
                              calibration_digest)
from repro.serve.program_cache import ProgramCache

__all__ = ["CNNServeEngine", "calibration_digest"]


@dataclasses.dataclass
class _Model:
    cfg: CNNConfig
    params: object                    # float tree (calibration input)
    qparams: object                   # engine-quantized tree (execution)
    calib_batches: Optional[List[jax.Array]]
    calib_id: Optional[str]
    calibrator: str = "absmax"
    granularity: str = "per_tensor"
    folded: Optional[Tuple[Program, object]] = None   # layout-folded qparams


@dataclasses.dataclass
class WaveStats:
    requests: int = 0
    waves: int = 0                    # physical wave buffers dispatched
    padded: int = 0                   # empty slots across drained waves
    program_execs: int = 0            # program runs (>= waves: multi-model
                                      # waves run once per distinct model)

    @property
    def occupancy(self) -> float:
        """Fill-rate over physical buffers: the continuous-batching metric
        the serving benchmark compares against the pad-and-mask baseline."""
        slots = self.requests + self.padded
        return self.requests / slots if slots else 0.0


class CNNServeEngine(ProgramServeBase):
    """Serve registered CNNs as cached, batched, scheduled engine programs."""

    def __init__(self, eng: EngineConfig, wave_size: int = 4,
                 cache_capacity: int = 8, scheduled: bool = True,
                 cache: Optional[ProgramCache] = None,
                 schedule_policy: str = "asap", mesh=None):
        if wave_size < 1:
            raise ValueError("wave_size must be >= 1")
        super().__init__(eng, cache_capacity=cache_capacity,
                         scheduled=scheduled, cache=cache,
                         schedule_policy=schedule_policy, mesh=mesh)
        # wave_size is PER-DEVICE slots; with a mesh the physical wave is
        # one slot pool per data replica and the buffer shards over the
        # batch axis, so each replica executes exactly its own pool's rows
        self.wave_size = wave_size
        pools = self.mexec.n_data if self.mexec is not None else 1
        self.wave_stats = WaveStats()
        self.execs_by_model: Dict[str, int] = {}
        self._models: Dict[str, _Model] = {}
        self._sched = SlotScheduler(wave_size, pools=pools)

    @property
    def wave_rows(self) -> int:
        """Rows per physical wave buffer (= wave_size x data replicas)."""
        return self._sched.wave_slots

    # -- model registry ------------------------------------------------------

    def register(self, cfg: CNNConfig, params,
                 calib_batches: Optional[Sequence[jax.Array]] = None,
                 calib_id: Optional[str] = None,
                 calibrator: str = "absmax",
                 granularity: str = "per_tensor") -> str:
        """Register a model under cfg.name.  `params` is the FLOAT tree;
        weights are engine-quantized here, and `calib_batches` (when given
        and the engine is quantized) select the static-int8 program under
        the chosen `calibrator` ("absmax" or a percentile like "p99.9")
        and `granularity` ("per_tensor", or "per_channel" to keep channel
        scale vectors on the DWC-consumed edges) -- both are part of the
        calibration-id, so no two settings share a cache entry.  The
        program itself compiles lazily on first request."""
        batches = list(calib_batches) if calib_batches is not None else None
        if self.eng.quant == "none":
            batches = None            # float fabric: dynamic program only
        if batches is not None and calib_id is None:
            calib_id = calibration_digest(batches, params, calibrator,
                                          granularity)
        self._models[cfg.name] = _Model(
            cfg=cfg, params=params,
            qparams=eng_lib.quantize_params(params, self.eng),
            calib_batches=batches, calib_id=calib_id, calibrator=calibrator,
            granularity=granularity)
        return cfg.name

    def models(self) -> List[str]:
        return sorted(self._models)

    # -- program cache -------------------------------------------------------

    def _key(self, m: _Model):
        return self._program_key(m.cfg, m.calib_id)

    def _compile(self, m: _Model) -> Program:
        if m.calib_batches is None:
            return compiler.compile_cnn(m.cfg, scheduled=self.scheduled,
                                        policy=self.schedule_policy)
        return compiler.compile_calibrated(
            m.cfg, m.params, m.calib_batches, scheduled=self.scheduled,
            policy=self.schedule_policy, method=m.calibrator,
            granularity=m.granularity)

    def program_for(self, name: str) -> Program:
        """The model's compiled program: cache hit, or compile-and-insert."""
        m = self._models[name]
        return self._cached_program(self._key(m), lambda: self._compile(m))

    def _executor_for(self, name: str):
        """A jitted batched execute for the model's program.  The wave shape
        is fixed ([wave_size, H, W, C]), so each cached program traces once;
        eviction drops the trace alongside the program."""
        m = self._models[name]
        program = self.program_for(name)
        run = self._jitted_for(self._key(m), program, self._jit_program)
        return run, self._exec_params(m, program)

    def _jit_program(self, prog: Program):
        """jit of one program execution.  On a mesh every data replica runs
        the whole program on its own rows (shard_map over the batch axis,
        weights replicated): Mosaic kernels cannot be partitioned by XLA,
        and no op of a CNN program mixes batch rows."""
        def run(p, im):
            return compiler.execute(prog, p, im, self.eng)
        if self.mexec is None:
            return jax.jit(run)
        mesh = self.mexec.mesh
        rows = self.mexec.batch_pspec(self.wave_rows)
        return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P(), rows),
                                     out_specs=rows, check_vma=False))

    def _exec_params(self, m: _Model, program: Program):
        """The model's execution param tree with weight layouts folded at
        compile time (im2col reshape, DWC lane padding) -- computed once per
        (model, program) binding."""
        if m.folded is None or m.folded[0] is not program:
            qp = compiler.fold_weight_layouts(program.graph, m.qparams)
            if self.mexec is not None:
                # data-parallel waves: weights replicate across the mesh
                # once per (model, program) binding, not per dispatch
                qp = self.mexec.replicate(qp)
            m.folded = (program, qp)
        return m.folded[1]

    # -- request batching ----------------------------------------------------

    def submit(self, name: str, image: np.ndarray) -> int:
        """Queue one [H, W, C] image request; returns its ticket: the key
        of its logits in a pump() result dict, and the SUBMISSION-ORDER
        rank within a flush() result list (flush returns only requests
        still queued when it runs, ordered by ticket -- use infer() or
        pump() when you need ticket-keyed results)."""
        if name not in self._models:
            raise KeyError(f"model {name!r} not registered "
                           f"(have {self.models()})")
        image = np.asarray(image)
        cfg = self._models[name].cfg
        want = (cfg.input_hw, cfg.input_hw, cfg.input_ch)
        if image.shape != want:
            # reject at submission: a bad request must not reach dispatch,
            # where the queue is already drained and a shape error would
            # drop every other pending request with it
            raise ValueError(f"submit() takes one {want} image per "
                             f"{name!r} request, got shape {image.shape}")
        # slot groups are keyed by shape: same-shape models share waves;
        # the model name is the pool-locality key on multi-device meshes
        ticket = self._sched.submit(want, (name, image), affinity=name)
        self.latency.submitted(ticket)
        return ticket

    def pending(self) -> int:
        return self._sched.pending()

    def pump(self) -> Dict[int, np.ndarray]:
        """Dispatch every FULL wave and return its results; partial waves
        stay queued for later arrivals to refill (continuous batching)."""
        return self._dispatch(force=False)

    def flush(self) -> List[np.ndarray]:
        """Run every queued request and return logits in submission order.

        Full waves dispatch as-is; each shape group's final partial wave is
        drained with zero-padded (masked-away) slots -- the pad-and-mask
        cost continuous pump() avoids."""
        results = self._dispatch(force=True)
        return [results[t] for t in sorted(results)]

    def _dispatch(self, force: bool) -> Dict[int, np.ndarray]:
        """Async dispatch with response-edge sync: every wave-model
        execution is launched first (results stay device arrays in
        flight, so host-side assembly of the next wave buffer overlaps
        device compute), then ONE np.asarray per execution materializes
        the logits at the response edge."""
        in_flight: List[Tuple[object, List[Tuple[int, int]]]] = []
        for group in self._sched.groups():
            while True:
                wave = self._sched.take_wave(group, force=force)
                if wave is None:
                    break
                self._run_wave(wave, group, in_flight)
        self._sched.next_epoch()
        results: Dict[int, np.ndarray] = {}
        for dev_logits, slots in in_flight:      # response edge: host sync
            with self.tracer.span("wave.readback"):
                logits = np.asarray(dev_logits)
            for slot, ticket in slots:
                results[ticket] = logits[slot]   # mask foreign/pad slots
                self.latency.completed(ticket)
        return results

    def _run_wave(self, wave, shape, in_flight) -> None:
        """Launch one wave buffer.  Slots may belong to different models
        (same shape): the buffer runs once per distinct model and each
        ticket reads its own slot's row.  Appends (device logits, slots)
        per execution without blocking; _dispatch materializes.  Records
        the spans wave.fill, wave.to_device and, per execution,
        wave.launch on self.tracer."""
        span = self.tracer.span
        with span("wave.fill"):
            buf = np.zeros((self.wave_rows,) + shape, np.float32)
            slots_of: Dict[str, List[Tuple[int, int]]] = {}
            for slot, (ticket, (name, img)) in enumerate(wave):
                buf[slot] = img
                slots_of.setdefault(name, []).append((slot, ticket))
        with span("wave.to_device"):
            jbuf = jnp.asarray(buf)
            if self.mexec is not None:
                jbuf = self.mexec.place_wave(jbuf)   # rows shard over replicas
        for name, slots in slots_of.items():
            with span("wave.launch"):
                run, qparams = self._executor_for(name)
                dev_logits = run(qparams, jbuf)
            in_flight.append((dev_logits, slots))
            self.wave_stats.program_execs += 1
            self.execs_by_model[name] = self.execs_by_model.get(name, 0) + 1
        self.wave_stats.requests += len(wave)
        self.wave_stats.waves += 1
        self.wave_stats.padded += self.wave_rows - len(wave)

    def infer(self, name: str, images) -> np.ndarray:
        """Convenience: submit a [N, H, W, C] batch as N requests and flush.
        Returns logits [N, num_classes]."""
        images = np.asarray(images)
        tickets = [self.submit(name, img) for img in images]
        results = self._dispatch(force=True)
        return np.stack([results[t] for t in tickets])

    # -- stats ---------------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        out = {"models": len(self._models)}
        out.update(self.cache_stats())
        # launch accounting of the bound (epilogue-fused) programs: peek so
        # monitoring never perturbs cache recency or compiles anything
        fused: Dict[str, Dict[str, int]] = {}
        for name, m in self._models.items():
            prog = self.cache.peek(self._key(m))
            if prog is not None:
                fs = compiler.fusion_stats(prog.graph)
                fused[name] = {"launches": fs["launches"],
                               "fused_ops": fs["fused_ops"]}
        out["fused_programs"] = fused
        out.update({
            "waves": self.wave_stats.waves,
            "requests": self.wave_stats.requests,
            "padded_slots": self.wave_stats.padded,
            "wave_fill_rate": self.wave_stats.occupancy,
            "program_execs": self.wave_stats.program_execs,
            "execs_by_model": dict(self.execs_by_model),
            "refilled_waves": self._sched.stats.refilled_waves,
            "queued": self._sched.pending(),
            "latency_ms": self.latency.percentiles(),
        })
        if self.mexec is not None:
            out["mesh"] = self.mexec.describe()
            out["wave_rows"] = self.wave_rows
            out["pool_locality_rate"] = self._sched.stats.locality_rate
            out["pool_locality_hits"] = self._sched.stats.locality_hits
        return out
