"""Shared program-serving base: compile -> ProgramCache -> jit -> schedule,
plus the continuous-batching SlotScheduler both engines feed the fabric
through.

Both serving engines ride this pipeline (the tentpole of the unified serve
path): `CNNServeEngine` serves registered CNN fleets as wave-batched
programs, and the LM `ServeEngine` serves transformer prefill + decode
programs from the same kind of keyed cache.  The base owns what they share:

  * the keyed LRU ProgramCache (own or injected/shared across engines),
    keyed by (model config, EngineConfig, calibration-id, variant);
  * the schedule variant (ASAP / ALAP leveling, or sequential);
  * the per-program jitted-executable store, pruned against the cache so a
    shared cache's evictions drop stale traces here too;
  * the SlotScheduler -- one slot-based request queue abstraction: the CNN
    engine keys slot groups by input shape (so models with identical
    shapes share wave buffers) and refills partial waves across arrival
    epochs; the LM engine draws prompt requests from it to refill finished
    decode slots between bursts;
  * cache statistics for the serving benchmarks.
"""
from __future__ import annotations

import hashlib
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler.executor import (Program, execute_interleaved,
                                     schedule_variant)
from repro.compiler.schedule import merge_schedules
from repro.core.config import EngineConfig
from repro.core.program_cache import ProgramCache, ProgramKey
from repro.serve.mesh_exec import MeshExecutor
from repro.serve.tracing import DISABLED


def calibration_digest(batches: Sequence, params=None,
                       method: str = "absmax",
                       granularity: str = "per_tensor",
                       weight_mode: str = "") -> str:
    """Stable id of the calibration inputs.  The recorded scales depend on
    the batches AND the float params (calibrate() runs the model) AND the
    calibrator method AND the scale granularity, so all four are digested:
    re-registering a model with new weights, new batches, a different
    calibrator (absmax vs percentile) or a different granularity
    (per-tensor vs per-channel) must miss the cache, not reuse stale
    activation scales.  `weight_mode` (engine.weight_mode: "" for int8
    weights, "w4g64" for int4 group-quantized) is appended so w4 and w8
    programs of the same model never share a cache line: the activation
    scales coincide, but the packed parameter trees the jitted executables
    close over do not."""
    h = hashlib.sha1()
    for b in batches:
        a = np.asarray(b)
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            h.update(np.asarray(leaf).tobytes())
    digest = h.hexdigest()[:12]
    if method != "absmax":
        digest = f"{digest}:{method}"
    if granularity != "per_tensor":
        digest = f"{digest}:pc"
    if weight_mode:
        digest = f"{digest}:{weight_mode}"
    return digest


# ---------------------------------------------------------------------------
# SlotScheduler: the shared continuous-batching request queue
# ---------------------------------------------------------------------------

@dataclass
class SlotStats:
    """Slot accounting across every dispatch the scheduler served."""
    submitted: int = 0
    dispatched: int = 0                  # requests handed out
    waves: int = 0                       # full-or-forced groups handed out
    padded_slots: int = 0                # empty slots in forced groups
    refilled_waves: int = 0              # groups spanning >1 arrival epoch
    locality_hits: int = 0               # requests placed in their model's
                                         # sticky device pool
    locality_misses: int = 0             # spilled into a foreign pool

    @property
    def fill_rate(self) -> float:
        slots = self.dispatched + self.padded_slots
        return self.dispatched / slots if slots else 0.0

    @property
    def locality_rate(self) -> float:
        placed = self.locality_hits + self.locality_misses
        return self.locality_hits / placed if placed else 0.0


@dataclass
class _Entry:
    ticket: int
    epoch: int
    payload: object
    affinity: Hashable = None            # pool-locality key (model name)


class SlotScheduler:
    """One slot-based request queue for every serving engine.

    Requests enter FIFO under a hashable group key (the CNN engine groups
    by input shape so same-shape models share wave buffers; the LM engine
    uses a single group whose takes refill finished decode slots).  A
    group's requests leave in waves of `slots`; a partial group is NOT
    dispatched until either later arrivals top it up (continuous batching)
    or the caller forces a drain (`take_wave(force=True)` pads, and the
    padding is what the fill-rate metric charges).  `epoch` advances on
    every dispatch round (`next_epoch`), so a dispatched wave whose entries
    span epochs is counted as a refilled wave -- slots that would have been
    pad under flush-per-arrival batching.

    With `pools` > 1 (one pool per mesh replica) a wave spans
    `pools * slots` rows and refill is LOCALITY-AWARE: each affinity key
    (the CNN engine passes the model name) gets a sticky home pool
    (round-robin on first sight), and `take_wave` packs that key's
    requests into its home pool's slot block first, spilling round-robin
    only when the block is full -- so a replica keeps seeing the model
    whose program rows it already executed (locality_hits / misses in
    stats).
    """

    def __init__(self, slots: int, pools: int = 1):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if pools < 1:
            raise ValueError("pools must be >= 1")
        self.slots = slots
        self.pools = pools
        self.stats = SlotStats()
        self.epoch = 0
        self._queues: "OrderedDict[Hashable, List[_Entry]]" = OrderedDict()
        self._home_pool: Dict[Tuple[Hashable, Hashable], int] = {}
        self._pool_rr: Dict[Hashable, int] = {}
        self._next_ticket = 0

    @property
    def wave_slots(self) -> int:
        """Rows per physical wave: one `slots`-sized pool per device."""
        return self.slots * self.pools

    def submit(self, group: Hashable, payload, affinity: Hashable = None
               ) -> int:
        ticket = self._next_ticket
        self._next_ticket += 1
        self._queues.setdefault(group, []).append(
            _Entry(ticket, self.epoch, payload, affinity))
        self.stats.submitted += 1
        return ticket

    def home_pool(self, group: Hashable, affinity: Hashable) -> int:
        """The affinity key's sticky device pool within the group
        (assigned round-robin on first sight, stable afterwards)."""
        key = (group, affinity)
        pool = self._home_pool.get(key)
        if pool is None:
            rr = self._pool_rr.get(group, 0)
            pool = self._home_pool[key] = rr % self.pools
            self._pool_rr[group] = rr + 1
        return pool

    def _pack_pools(self, group: Hashable, entries: List[_Entry]
                    ) -> List[_Entry]:
        """Order a wave's entries so each affinity key's requests fill its
        home pool's slot block first (wave row i belongs to device pool
        i // slots).

        A single-pool scheduler places every request in its (only) home
        pool, so those placements count as locality hits -- otherwise
        locality_rate reads 0.0 on a 1-device mesh and jumps to ~1.0 at 2
        devices, breaking the monotone locality trend the fleet benchmark
        plots."""
        if self.pools <= 1:
            self.stats.locality_hits += len(entries)
            return entries
        by_aff: "OrderedDict[Hashable, List[_Entry]]" = OrderedDict()
        for e in entries:
            by_aff.setdefault(e.affinity, []).append(e)
        placed: List[Optional[_Entry]] = [None] * self.wave_slots
        homes: Dict[int, int] = {}      # final row -> home pool
        for aff, es in by_aff.items():
            home = self.home_pool(group, aff)
            i = 0
            for k in range(self.pools):
                base = ((home + k) % self.pools) * self.slots
                for row in range(base, base + self.slots):
                    if i >= len(es):
                        break
                    if placed[row] is None:
                        placed[row] = es[i]
                        homes[row] = home
                        i += 1
        # a partial (forced) wave compacts; full waves keep their rows
        out, hit_rows = [], []
        for row, e in enumerate(placed):
            if e is not None:
                hit_rows.append((len(out), homes[row]))
                out.append(e)
        for row, home in hit_rows:
            if row // self.slots == home:
                self.stats.locality_hits += 1
            else:
                self.stats.locality_misses += 1
        return out

    def next_epoch(self) -> None:
        """Mark a dispatch round boundary (a pump/flush or decode-burst
        edge); entries surviving it count as refill candidates."""
        self.epoch += 1

    def groups(self) -> List[Hashable]:
        return [g for g, q in self._queues.items() if q]

    def pending(self, group: Optional[Hashable] = None) -> int:
        if group is not None:
            return len(self._queues.get(group, []))
        return sum(len(q) for q in self._queues.values())

    def peek(self, group: Hashable) -> List[object]:
        """The group's queued payloads, FIFO order, without dispatching
        (the LM engine sizes its fixed prefill width from these)."""
        return [e.payload for e in self._queues.get(group, [])]

    def take(self, group: Hashable, limit: Optional[int] = None
             ) -> List[Tuple[int, object]]:
        """FIFO-pop up to `limit` (default: the slot count) requests -- the
        LM engine's slot-refill entry point."""
        q = self._queues.get(group, [])
        n = min(len(q), self.slots if limit is None else limit)
        taken, self._queues[group] = q[:n], q[n:]
        self.stats.dispatched += len(taken)
        if taken and len({e.epoch for e in taken}) > 1:
            self.stats.refilled_waves += 1
        return [(e.ticket, e.payload) for e in taken]

    def take_wave(self, group: Hashable, force: bool = False
                  ) -> Optional[List[Tuple[int, object]]]:
        """Pop one wave of exactly `wave_slots` (= pools * slots) requests,
        or None when the group is partial.  force=True drains a final
        partial wave (its empty slots are charged to padded_slots).  Multi-
        pool waves come back locality-packed (see _pack_pools)."""
        cap = self.wave_slots
        q = self._queues.get(group, [])
        if not q or (len(q) < cap and not force):
            return None
        taken, self._queues[group] = q[:cap], q[cap:]
        self.stats.dispatched += len(taken)
        self.stats.waves += 1
        self.stats.padded_slots += cap - len(taken)
        if len({e.epoch for e in taken}) > 1:
            self.stats.refilled_waves += 1
        taken = self._pack_pools(group, taken)
        return [(e.ticket, e.payload) for e in taken]


class LatencyTracker:
    """Per-request wall-clock latency, submit -> response materialization.

    Both engines clock every ticket at submit() and again at the response
    edge where its result becomes a host array, so the distribution
    measures what a caller actually waits -- queueing + batching + device
    time + the response-edge sync, not just kernel time.  percentiles()
    feeds the `latency_ms` block of BENCH_serve.json.  Only the newest
    WINDOW samples are kept, so a long-running server's memory stays
    bounded."""

    WINDOW = 100_000

    def __init__(self):
        self._open: Dict[int, float] = {}
        self.samples_ms: deque = deque(maxlen=self.WINDOW)

    def submitted(self, ticket: int) -> None:
        self._open[ticket] = time.perf_counter()

    def completed(self, ticket: int) -> None:
        t0 = self._open.pop(ticket, None)
        if t0 is not None:
            self.samples_ms.append((time.perf_counter() - t0) * 1e3)

    def percentiles(self) -> Dict[str, float]:
        if not self.samples_ms:
            return {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
        a = np.asarray(self.samples_ms)
        return {"n": int(a.size),
                "p50_ms": float(np.percentile(a, 50)),
                "p99_ms": float(np.percentile(a, 99)),
                "mean_ms": float(a.mean())}


class ProgramServeBase:
    """Compile-once, cache-keyed, schedule-carrying program serving."""

    def __init__(self, eng: EngineConfig, cache_capacity: int = 8,
                 scheduled: bool = True, cache: Optional[ProgramCache] = None,
                 schedule_policy: str = "asap", mesh=None):
        self.eng = eng
        self.scheduled = scheduled
        self.schedule_policy = schedule_policy
        self.cache = (ProgramCache(cache_capacity, on_evict=self._on_evict)
                      if cache is None else cache)
        self._jitted: Dict[object, object] = {}
        # mesh= routes all dispatch through the sharded executor; None
        # keeps the single-implicit-device behavior bit-for-bit
        self.mexec: Optional[MeshExecutor] = (
            mesh if isinstance(mesh, MeshExecutor) or mesh is None
            else MeshExecutor(mesh))
        self.latency = LatencyTracker()
        # host spans of the serving path (serve/tracing.py); swap in an
        # enabled SpanRecorder between pumps to record them
        self.tracer = DISABLED

    # -- program cache -------------------------------------------------------

    def _variant(self, tag: str = "") -> str:
        v = schedule_variant(self.scheduled, self.schedule_policy)
        return f"{v}:{tag}" if tag else v

    def _program_key(self, model_cfg, calib_id: Optional[str],
                     tag: str = "") -> ProgramKey:
        topo = self.mexec.topology if self.mexec is not None else None
        return ProgramKey(model_cfg, self.eng, calib_id, self._variant(tag),
                          mesh=topo)

    def _cached_program(self, key: ProgramKey,
                        compile_fn: Callable[[], Program]) -> Program:
        """Cache hit, or compile-and-insert (counts hits/misses)."""
        return self.cache.get_or_compile(key, compile_fn)

    def _on_evict(self, key, program) -> None:
        self._jitted.pop(key, None)   # drop the evicted program's trace too

    # -- jitted executables --------------------------------------------------

    def _jitted_for(self, key, program: Program,
                    build: Callable[[Program], Callable]):
        """The program's jitted executable, traced once per cached program.

        A shared/injected cache evicts without calling this engine's
        _on_evict; prune traces for programs it no longer holds on every
        call (not just local misses) so the jit store stays bounded by the
        cache even when this engine's own working set is stable."""
        self._jitted = {k: f for k, f in self._jitted.items()
                        if k in self.cache}
        fn = self._jitted.get(key)
        if fn is None or fn[0] is not program:
            fn = (program, build(program))
            self._jitted[key] = fn
        return fn[1]

    # -- stats ---------------------------------------------------------------

    def cache_stats(self) -> Dict[str, object]:
        c = self.cache.stats
        return {
            "cache_hits": c.hits,
            "cache_misses": c.misses,
            "cache_evictions": c.evictions,
            "cache_hit_rate": c.hit_rate,
            "programs_cached": len(self.cache),
        }


# ---------------------------------------------------------------------------
# FabricPump: cross-engine multi-tenant tick stream (f-CNNx co-mapping)
# ---------------------------------------------------------------------------

class FabricPump:
    """Drive a CNNServeEngine wave lane and a (dense) LM ServeEngine decode
    lane on ONE fabric tick stream.

    Each fabric tick advances both tenants: one CNN wave buffer and one LM
    decode step.  With `interleave=True` the tick is a SINGLE fused jitted
    call of executor.execute_interleaved -- the two programs' levels are
    zipped by schedule.merge_schedules (`merge_policy`), so the conv-heavy
    CNN levels fill the units the MISC-heavy LM decode levels leave idle
    and the host pays one dispatch instead of two.  With
    `interleave=False` the same tick issues the two programs as separate
    jitted calls -- identical work, serialized dispatch: the baseline leg
    of benchmarks/serve_mixed.py.  Outputs are bit-identical between the
    two modes and to isolated per-engine execution (the lanes share the
    dispatch stream, never dataflow).

    Served path: the LM lane replicates ServeEngine.run's dense
    continuous-batching loop (masked prefill + slot refill + decode
    bursts).  Paged-KV and speculative engines are rejected -- their steps
    are fused host-side loops of their own; only the plain DecodeStep
    program zips levels with a CNN wave.  Prefill ticks run un-fused (a
    prefill is a full forward program, not a per-tick recurrence)."""

    def __init__(self, cnn_engine, lm_engine, merge_policy: str = "cost",
                 interleave: bool = True):
        self.cnn = cnn_engine
        self.lm = lm_engine
        self.merge_policy = merge_policy
        self.interleave = interleave
        self.latency = LatencyTracker()
        # per CNN model: (cnn program, lm program, jitted step, merged) --
        # each registered model fuses its own program pair with the LM
        # decode step, so a multi-model run round-robins fused ticks
        # without re-tracing
        self._fused: Dict[str, tuple] = {}
        self.ticks = 0
        self.fused_ticks = 0
        self.solo_cnn_ticks = 0
        self.solo_lm_ticks = 0

    # -- merged schedule / fused step ----------------------------------------

    def merged_schedule(self, name: str, policy: Optional[str] = None):
        """The MergedSchedule aligning the named CNN program's levels with
        the LM DecodeStep program's (cost-priced; stats carry the modeled
        makespan and combined occupancy the mixed benchmark reports)."""
        from repro.compiler import cost as cost_lib
        prog_a = self.cnn.program_for(name)
        prog_b = self.lm.decode_program()
        times_a = cost_lib.default_node_times(prog_a.graph, prog_a.cfg,
                                              prog_a.kind)
        times_b = cost_lib.default_node_times(prog_b.graph, prog_b.cfg,
                                              prog_b.kind)
        return merge_schedules(prog_a.graph, prog_a.schedule,
                               prog_b.graph, prog_b.schedule,
                               times_a, times_b,
                               policy=policy or self.merge_policy)

    def _fused_step(self, name: str):
        """One jitted (CNN wave + LM decode step) executable, traced once
        per program pair (the _spec_jit pairing pattern), LM cache
        donated like the engine's own decode step."""
        prog_a = self.cnn.program_for(name)
        prog_b = self.lm.decode_program()
        ent = self._fused.get(name)
        if (ent is None or ent[0] is not prog_a or ent[1] is not prog_b):
            merged = self.merged_schedule(name)
            eng_a, eng_b = self.cnn.eng, self.lm.eng

            def step(qparams, buf, lparams, cache, cur):
                return execute_interleaved(prog_a, qparams, buf,
                                           prog_b, lparams, cache, cur,
                                           eng_a, eng_b, merged=merged)

            ent = (prog_a, prog_b,
                   jax.jit(step, donate_argnums=(3,)), merged)
            self._fused[name] = ent
        return ent[2]

    # -- the pump ------------------------------------------------------------

    def run(self, submissions, images: Optional[Sequence[np.ndarray]] = None,
            prompts: Optional[Sequence] = None, max_new_tokens: int = 8
            ) -> Tuple[List[np.ndarray], Dict[int, np.ndarray]]:
        """Serve CNN image traces and an LM prompt trace to completion on
        one tick stream.  Returns (cnn logits in submission order,
        {lm ticket: greedy token ids}).

        `submissions` is either a single model name (the legacy form:
        `run(name, images, prompts)`) or a {model name: [images...]} dict
        spanning several registered CNNs (`run({...}, prompts)`).  The
        dict form packs waves per input shape -- same-shape models share
        wave buffers, engine-style -- and drains the shape groups
        round-robin, so every tenant's waves interleave with the LM lane
        instead of one model monopolizing the early fused ticks.  Each
        model's program pair fuses with the LM decode step under its own
        merged schedule, cached across runs."""
        cnn, lm = self.cnn, self.lm
        if isinstance(submissions, str):
            subs = {submissions: list(images) if images is not None else []}
        else:
            subs = {name: list(imgs) for name, imgs in submissions.items()}
            if prompts is None:
                # dict form shifts the positionals: run(subs, prompts, ...)
                prompts = images
        prompts = list(prompts) if prompts is not None else []
        if getattr(lm, "paged", False):
            raise ValueError("FabricPump serves the dense KV path; paged "
                             "engines fuse their own prefill+merge steps")
        if getattr(lm, "draft_len", 0):
            raise ValueError("FabricPump serves plain decode; speculative "
                             "bursts are their own fused verify step")
        if not (lm.compiled and lm.compiled_decode):
            raise ValueError("FabricPump needs compiled LM programs "
                             "(lowering blockers: "
                             f"{lm.lowering_blockers()})")
        if cnn.mexec is not None or lm.mexec is not None:
            raise ValueError("FabricPump is single-device; drop mesh=")

        # -- submit both tenants' traces -------------------------------------
        cnn_tickets = [cnn.submit(name, img)
                       for name, imgs in subs.items() for img in imgs]
        lm_tickets = []
        for p in prompts:
            t = lm.submit(p, max_new_tokens)
            if not t and t != 0:
                raise ValueError(f"LM request rejected: {t}")
            lm_tickets.append(t)

        # -- CNN lane: pre-pack the wave buffers (zero-padded tail) ----------
        # Waves are keyed by INPUT SHAPE (the scheduler's grouping: models
        # with one shape share buffers) and drained ROUND-ROBIN across the
        # shape groups, so a multi-model trace alternates tenants on the
        # fused tick stream rather than finishing one model first.
        shapes: List[Tuple[int, int, int]] = []
        for name in subs:
            cfg = cnn._models[name].cfg
            shape = (cfg.input_hw, cfg.input_hw, cfg.input_ch)
            if shape not in shapes:
                shapes.append(shape)
        waves: List[Tuple[jax.Array,
                          Dict[str, List[Tuple[int, int]]]]] = []
        live = list(shapes)
        while live:
            for shape in list(live):     # one wave per live group per pass
                wave = cnn._sched.take_wave(shape, force=True)
                if wave is None:
                    live.remove(shape)
                    continue
                buf = np.zeros((cnn.wave_rows,) + shape, np.float32)
                slots_of: Dict[str, List[Tuple[int, int]]] = {}
                for slot, (ticket, (name, img)) in enumerate(wave):
                    buf[slot] = img
                    slots_of.setdefault(name, []).append((slot, ticket))
                waves.append((jnp.asarray(buf), slots_of))
                cnn.wave_stats.requests += len(wave)
                cnn.wave_stats.waves += 1
                cnn.wave_stats.padded += cnn.wave_rows - len(wave)
        cnn._sched.next_epoch()
        executors = {name: cnn._executor_for(name) for name in subs}

        def launch_model(name, buf, slots, in_flight):
            run_fn, qp = executors[name]
            in_flight.append((run_fn(qp, buf), slots))
            cnn.wave_stats.program_execs += 1
            cnn.execs_by_model[name] = cnn.execs_by_model.get(name, 0) + 1

        in_flight: List[Tuple[object, List[Tuple[int, int]]]] = []
        wave_i = 0

        # -- LM lane state (ServeEngine.run's dense loop) --------------------
        results: Dict[int, np.ndarray] = {}
        sched, B = lm._sched, lm.batch
        plen = lm.prefill_len
        if plen is None and sched.pending("lm"):
            plen = max(len(p) for p, _ in sched.peek("lm"))
        prefill_exec = lm._prefill_exec()
        decode_exec = lm._decode_exec()
        cache = lm._empty_cache()
        cache["pos"] = jnp.zeros((B,), jnp.int32)
        cur = jnp.zeros((B, 1), jnp.int32)
        tickets: List[Optional[int]] = [None] * B
        remaining = np.zeros(B, np.int64)
        start = np.zeros(B, np.int64)
        step = 0
        blocks: List[List] = []           # [start step, [B, w] device toks]
        block_np: Dict[int, np.ndarray] = {}

        def tokens_for(slot: int, lo: int, hi: int) -> np.ndarray:
            parts = []
            for s0, blk in blocks:
                w = blk.shape[1]
                if s0 + w <= lo or s0 >= hi:
                    continue
                arr = block_np.get(id(blk))
                if arr is None:
                    arr = block_np[id(blk)] = np.asarray(blk)
                parts.append(arr[slot, max(lo - s0, 0):min(hi - s0, w)])
            return (np.concatenate(parts).astype(np.int32) if parts
                    else np.zeros(0, np.int32))

        def decode_tick(cur, cache):
            """One fabric tick: one LM decode step, co-scheduled with the
            next CNN wave when one is pending.  A multi-model wave fuses
            ONE model's execution with the decode step (the fused call zips
            exactly one program pair); the wave's same-shape foreign models
            launch solo on the same tick, engine-style."""
            nonlocal wave_i
            self.ticks += 1
            if wave_i < len(waves):
                buf, slots_of = waves[wave_i]
                wave_i += 1
                names = list(slots_of)
                fused_with = None
                logits_b = None
                if self.interleave:
                    fused_with = names[0]
                    run_fn, qp = executors[fused_with]
                    logits_a, logits_b, cache = self._fused_step(fused_with)(
                        qp, buf, lm.params, cache, cur)
                    in_flight.append((logits_a, slots_of[fused_with]))
                    cnn.wave_stats.program_execs += 1
                    cnn.execs_by_model[fused_with] = (
                        cnn.execs_by_model.get(fused_with, 0) + 1)
                    self.fused_ticks += 1
                for name in names:
                    if name != fused_with:
                        launch_model(name, buf, slots_of[name], in_flight)
                if logits_b is not None:
                    return logits_b, cache
            else:
                self.solo_lm_ticks += 1
            logits_b, cache = decode_exec(lm.params, cache, cur)
            return logits_b, cache

        # -- continuous batching over fabric ticks ---------------------------
        while True:
            free = [i for i in range(B) if remaining[i] == 0]
            if free and sched.pending("lm"):
                taken = sched.take("lm", limit=len(free))
                if taken:
                    toks = np.zeros((B, plen), np.int32)
                    mask = np.zeros(B, bool)
                    for slot, (ticket, (prompt, mnt)) in zip(free, taken):
                        if len(prompt) > plen:
                            raise ValueError(
                                f"prompt of length {len(prompt)} exceeds "
                                f"the run's fixed prefill width {plen}")
                        toks[slot, plen - len(prompt):] = prompt
                        mask[slot] = True
                        if tickets[slot] is not None:
                            lm.serve_stats.slot_refills += 1
                        tickets[slot] = ticket
                        remaining[slot] = mnt
                        start[slot] = step
                    jmask = jnp.asarray(mask)
                    logits, fresh = prefill_exec(
                        lm.params, lm._empty_cache(),
                        {"tokens": jnp.asarray(toks)})
                    cache = lm.jmerge(cache, fresh, jmask)
                    first = jnp.argmax(logits[:, -1, :], axis=-1)
                    cur = jnp.where(jmask[:, None], first[:, None], cur
                                    ).astype(jnp.int32)
                    lm.serve_stats.prefill_calls += 1
                    lm.serve_stats.requests += len(taken)
                    sched.next_epoch()
                    self.ticks += 1

            act = [i for i in range(B) if remaining[i] > 0]
            if not act:
                if sched.pending("lm"):
                    continue
                break
            burst = int(min(lm.decode_burst,
                            min(remaining[i] for i in act)))
            cols = []
            for _ in range(burst):
                cols.append(cur)
                logits, cache = decode_tick(cur, cache)
                cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None
                                                            ].astype(jnp.int32)
                lm.serve_stats.decode_steps += 1
                lm.serve_stats.active_slot_steps += len(act)
            blocks.append([step, cols[0] if burst == 1
                           else jnp.concatenate(cols, axis=1)])
            step += burst
            finished = False
            for i in act:
                remaining[i] -= burst
                if remaining[i] == 0:     # response edge for this ticket
                    results[tickets[i]] = tokens_for(i, int(start[i]), step)
                    lm.latency.completed(tickets[i])
                    self.latency.samples_ms.append(
                        lm.latency.samples_ms[-1])
                    finished = True
            if finished:
                live = [int(start[i]) for i in range(B) if remaining[i] > 0]
                lo = min(live) if live else step
                keep = [b for b in blocks if b[0] + b[1].shape[1] > lo]
                kept_ids = {id(b[1]) for b in keep}
                for b in blocks:
                    if id(b[1]) not in kept_ids:
                        block_np.pop(id(b[1]), None)
                blocks = keep

        # -- drain leftover CNN waves (LM lane dry) --------------------------
        while wave_i < len(waves):
            buf, slots_of = waves[wave_i]
            wave_i += 1
            for name, slots in slots_of.items():
                launch_model(name, buf, slots, in_flight)
            self.ticks += 1
            self.solo_cnn_ticks += 1

        # -- CNN response edge: one host sync per wave execution -------------
        cnn_results: Dict[int, np.ndarray] = {}
        for dev_logits, slots in in_flight:
            logits = np.asarray(dev_logits)
            for slot, ticket in slots:
                cnn_results[ticket] = logits[slot]
                cnn.latency.completed(ticket)
                self.latency.samples_ms.append(
                    cnn.latency.samples_ms[-1])
        return ([cnn_results[t] for t in cnn_tickets],
                {t: results[t] for t in lm_tickets})

    def stats(self) -> Dict[str, object]:
        out = {
            "ticks": self.ticks,
            "fused_ticks": self.fused_ticks,
            "solo_cnn_ticks": self.solo_cnn_ticks,
            "solo_lm_ticks": self.solo_lm_ticks,
            "interleave": self.interleave,
            "merge_policy": self.merge_policy,
            "latency_ms": self.latency.percentiles(),
        }
        if self._fused:
            # legacy single-model key: the first fused pair's merged stats;
            # the per-model dict carries every tenant's schedule evidence
            first = next(iter(self._fused.values()))
            out["merged"] = dict(first[3].stats)
            out["merged_by_model"] = {name: dict(ent[3].stats)
                                      for name, ent in self._fused.items()}
        return out
