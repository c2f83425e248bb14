"""Engine-program executor: runs a compiled op graph on any backend.

Programs from both frontends execute here: CNN graphs (build_graph) and LM
prefill graphs (lower_transformer), including mixed fleets sharing one
engine -- the op evaluators dispatch on node kind, not on model family.

Two execution modes, selected by whether the program carries a QuantPlan:

  * dynamic (plan=None) -- reproduces the historical eager paths exactly
    (`cnn_forward`, `T.forward`): every op dispatches through kernels/ops.py
    with the engine config's quant mode, GEMM activations are re-quantized
    dynamically per call.  This is the float/training path and the
    "dynamic-f32 pipeline" baseline of the benchmarks.

  * static (plan from passes.fold_requant) -- the paper's dataflow: for a
    CNN the input image is quantized once with its calibrated scale and
    every engine consumes and emits int8 via its fused requant epilogue; for
    an LM program every Conv PE GEMM consumes int8 at a static calibrated
    scale (the producing MISC op's requant epilogue), while the float-domain
    MISC work (attention math, residual stream, gate product) stays f32.

LM prefill programs additionally support `collect`: each AttnOp deposits its
(roped-k, v) pair keyed by layer index, which the serving layer writes into
the decode KV cache -- so one compiled program yields both the prefill
logits and the cache fill, like `T.prefill`.

Either mode consumes the program's Schedule (compiler/schedule.py) when one
is attached: ops are dispatched level-by-level, and every op of a level is
evaluated against the previous levels' values only -- concurrent-PE
semantics, where a same-level data dependence would fail loudly instead of
silently serializing.  A program without a schedule falls back to the raw
topological node order (bit-identical results either way; the parity suite
pins that).

Backend selection (ref / pallas / XVDPU-analog baseline) stays inside
kernels/ops.py: the same compiled program runs on any EngineConfig.

Compiled dynamic programs are memoized in a bounded ProgramCache
(core/program_cache.py) rather than a raw functools.lru_cache: the same
store type the serving layer keys calibrated programs with, LRU-bounded
instead of unbounded, and its hit/miss counters feed the serving
benchmarks.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict, Hashable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compiler import passes as passes_lib
from repro.compiler.graph import (AddOp, AttnOp, ConcatOp, ConvOp, DwcOp,
                                  EmbedOp, Graph, HeadOp, InputOp,
                                  LinearGroupOp, LinearOp, MulOp, NormOp,
                                  OpNode, PoolOp, ViewOp, build_graph,
                                  get_param, lower_transformer)
from repro.compiler.passes import QuantPlan, fold_requant
from repro.compiler.schedule import (MergedSchedule, Schedule,
                                     engine_unit, level_schedule,
                                     merge_schedules)
from repro.core.config import ArchConfig, CNNConfig, EngineConfig
from repro.core.quant import (Q4Tensor, QTensor, quantize_act_dynamic,
                              quantize_static)
from repro.kernels import ops, ref
from repro.models import layers as L
from repro.models import transformer as T
from repro.core.program_cache import ProgramCache, ProgramKey


@dataclass(frozen=True)
class Program:
    """A compiled engine program: op graph + optional static-int8 plan and
    concurrent-dispatch schedule.  `cfg` is the frontend config the graph
    was lowered from (CNNConfig or ArchConfig).

    kind="forward": stateless (image or token batch) -> logits; run it with
    `execute`.  kind="decode": a DecodeStep program -- the cache-state
    recurrence with signature (params, cache, tokens) -> (logits, cache);
    run it with `execute_decode`.  kind="chunk": a chunked partial-prefill
    program over a paged cache (prefix sharing) -- run it with
    `prefill_from(program, params, cache, tokens, eng, start=...)`."""
    graph: Graph
    cfg: Hashable
    plan: Optional[QuantPlan] = None
    schedule: Optional[Schedule] = None
    kind: str = "forward"

    @property
    def static(self) -> bool:
        return self.plan is not None

    def f32_roundtrips(self) -> int:
        """f32 edges between engines (0 for a correct static program)."""
        if self.plan is None:
            return passes_lib.dynamic_roundtrip_count(self.graph)
        return len(passes_lib.f32_roundtrip_edges(self.graph, self.plan))


# The process-wide store for compiled dynamic programs (the eager
# cnn_forward path compiles each config once).  Bounded: a long-running
# trainer or server sweeping many configs no longer grows it without limit.
_DYNAMIC_CACHE_CAPACITY = 64
_dynamic_cache = ProgramCache(capacity=_DYNAMIC_CACHE_CAPACITY)


def program_cache() -> ProgramCache:
    """The executor's dynamic-program store (shared with the serving layer's
    introspection; serving keeps its own cache for calibrated programs)."""
    return _dynamic_cache


def schedule_variant(scheduled: bool, policy: str) -> str:
    """The ProgramKey variant string for a scheduling choice."""
    if not scheduled:
        return "sequential"
    return "scheduled" if policy == "asap" else f"scheduled-{policy}"


def compile_cnn(cfg: CNNConfig,
                scales: Optional[Dict[int, float]] = None,
                scheduled: bool = True, policy: str = "asap",
                granularity: str = "per_tensor",
                fuse: bool = True) -> Program:
    """Lower a CNNConfig to an engine program.

    Without `scales` the program executes dynamically (eager-equivalent);
    that program is cached per config (CNNConfig is frozen/hashable) in the
    bounded program_cache(), so the eager cnn_forward wrapper builds each
    graph once.  With calibrated per-edge scales the requant-folding pass
    produces the static int8 plan (granularity="per_channel" keeps channel
    vectors on the DWC-consumed edges).  `scheduled=False` omits the
    concurrency schedule (sequential raw-order dispatch; the parity tests'
    baseline); `policy` selects ASAP / ALAP / slack leveling
    (schedule.level_schedule).

    `fuse` (default ON) runs passes.fuse_epilogues: Conv/DWC -> {residual
    add, pool tail} chains collapse into single fused launches, with the
    calibration scales remapped onto the fused graph (calibration itself
    always observes the UNFUSED graph, whose edges are what the scales
    describe).  fuse=False keeps the one-op-per-launch graph -- the
    fused-vs-unfused parity baseline.
    """

    def lower():
        g = build_graph(cfg)
        if fuse:
            g, _ = passes_lib.fuse_epilogues(g)
        return g

    if scales is None:
        variant = schedule_variant(scheduled, policy) + (
            "" if fuse else ":nofuse")
        key = ProgramKey(cfg, None, None, variant)
        return _dynamic_cache.get_or_compile(
            key, lambda: _finish_program(lower(), cfg, None,
                                         scheduled, policy))
    g = build_graph(cfg)
    if fuse:
        g, scales = passes_lib.fuse_epilogues(g, scales)
    return _finish_program(g, cfg, scales, scheduled, policy,
                           granularity=granularity)


def compile_lm(arch: ArchConfig,
               scales: Optional[Dict[int, float]] = None,
               scheduled: bool = True, policy: str = "asap",
               prefill: bool = False, mode: Optional[str] = None,
               granularity: str = "per_tensor",
               fuse: bool = True, page_size: int = 0) -> Program:
    """Lower a transformer ArchConfig to an engine program.

    `mode` selects the program: "full" computes full-sequence logits like
    `T.forward`; "prefill" emits only the last position's logits (the
    serving variant whose AttnOps feed the KV-cache fill via `collect`);
    "decode" is the DecodeStep program (run with `execute_decode`).  The
    legacy `prefill=True` flag is shorthand for mode="prefill".  Dynamic
    programs are memoized per (arch, variant) in the bounded
    program_cache(); calibrated ones are keyed by the serving layer.

    `fuse` (default ON, mirroring compile_cnn) runs the LM graph rewrites:
    passes.fuse_projections collapses each Q/K/V triple and gate/up pair
    into ONE multi-output Conv PE launch, then passes.fuse_epilogues folds
    the residual adds after the O/down projections into their GEMMs.
    Calibration always observes the UNFUSED graph; its per-edge scales are
    remapped through both rewrites (deterministic, so the full and decode
    twins stay node-aligned).  fuse=False keeps the one-op-per-launch
    graph -- the fused-vs-unfused parity baseline.

    `page_size` > 0 (decode / chunk modes) compiles the block-paged
    variant: global-layer AttnOps index cache["tables"] instead of a dense
    [B, max_seq] cache.  The page size rides the program variant (":pN"),
    so paged and dense programs hold distinct ProgramCache lines.

    mode="chunk" is the prefix-sharing partial-prefill program (run with
    `prefill_from`): a [B, T] prompt TAIL attends the paged cache at a
    query offset and stores only the slot's owned tail pages.  It
    requires page_size > 0 and an all-global arch.
    """
    mode = mode or ("prefill" if prefill else "full")
    if mode not in ("full", "prefill", "decode", "chunk"):
        raise ValueError(f"unknown LM program mode {mode!r}")
    if page_size and mode not in ("decode", "chunk"):
        raise ValueError("page_size applies to decode/chunk programs only")
    if mode == "chunk" and page_size <= 0:
        raise ValueError("chunk programs need page_size > 0")
    variant = (schedule_variant(scheduled, policy) + f":{mode}"
               + (f":p{page_size}" if page_size else "")
               + ("" if fuse else ":nofuse"))
    kind = mode if mode in ("decode", "chunk") else "forward"

    def lower(sc=None):
        if mode in ("decode", "chunk"):
            g = lower_transformer(arch, mode=mode, page_size=page_size)
        else:
            g = lower_transformer(arch, last_only=(mode == "prefill"))
        if fuse:
            g, sc = passes_lib.fuse_projections(g, sc)
            g, sc = passes_lib.fuse_epilogues(g, sc)
        return g, sc

    if scales is None:
        key = ProgramKey(arch, None, None, variant)
        return _dynamic_cache.get_or_compile(
            key, lambda: _finish_program(lower()[0], arch, None,
                                         scheduled, policy, kind))
    g, scales = lower(scales)
    return _finish_program(g, arch, scales, scheduled, policy, kind,
                           granularity=granularity)


def _finish_program(g: Graph, cfg, scales, scheduled: bool,
                    policy: str = "asap", kind: str = "forward",
                    granularity: str = "per_tensor") -> Program:
    plan = (fold_requant(g, scales, granularity=granularity)
            if scales is not None else None)
    sched = None
    if scheduled:
        times = None
        if policy in ("cost", "slack"):
            # Time-aware policies price each node with the analytic tile
            # model (compiler/cost.py); count-based behavior is preserved
            # when callers invoke level_schedule directly without times.
            from repro.compiler import cost as cost_lib
            times = cost_lib.default_node_times(g, cfg, kind)
        sched = level_schedule(g, policy, node_times=times)
    return Program(g, cfg, plan, sched, kind)


def execute(program: Program, params, inputs: jax.Array,
            eng: EngineConfig,
            observer: Optional[Callable[[OpNode, jax.Array], None]] = None,
            collect: Optional[dict] = None) -> jax.Array:
    """Run a stateless (kind="forward") program.  `inputs` is whatever the
    graph's InputOp consumes: [N, H, W, C] float images (CNN) or [B, L]
    int32 token ids (LM).  Returns logits.  `collect`, when given, is
    filled with each AttnOp's (k, v) pair keyed by layer index (the
    serving KV-cache fill)."""
    if program.kind == "decode":
        raise ValueError("decode programs carry cache state; run them "
                         "through execute_decode(program, params, cache, "
                         "tokens, eng)")
    if program.kind == "chunk":
        raise ValueError("chunk programs carry cache state; run them "
                         "through prefill_from(program, params, cache, "
                         "tokens, eng, start=...)")
    if program.static:
        return _execute_static(program, params, inputs, eng, collect)
    return _execute_dynamic(program, params, inputs, eng, observer, collect)


class _DecodeCtx:
    """Cache state threaded through a DecodeStep program's AttnOp updates.

    `tables` is the block table [B, max_pages] of a paged cache (None for
    dense).  `collect`, when set to a dict, flips the AttnOps into VERIFY
    mode: fresh per-token (k, v) land there instead of the cache, which
    stays untouched until `commit_decode_kv` applies the accepted prefix.
    """

    def __init__(self, cache: dict):
        self.cache = cache
        self.pos = cache["pos"]          # scalar, or [B] per-slot positions
        self.tables = cache.get("tables")
        self.collect: Optional[Dict[int, tuple]] = None
        self.new_layers: Dict[int, dict] = {}

    def entry(self, layer: int) -> dict:
        return self.cache["layers"][layer]

    def finish(self) -> dict:
        layers = [self.new_layers.get(i, e)
                  for i, e in enumerate(self.cache["layers"])]
        out = {"layers": layers, "pos": self.pos + 1}
        if self.tables is not None:
            out["tables"] = self.tables
        return out


def execute_decode(program: Program, params, cache: dict,
                   tokens: jax.Array, eng: EngineConfig
                   ) -> Tuple[jax.Array, dict]:
    """Run a DecodeStep program: one token per slot against the KV cache.

    tokens: [B, 1] int32; cache: the serving cache (T.cache_schema or
    T.paged_cache_schema layout, "pos" scalar or [B] per-slot).  Returns
    (logits [B, 1, V], new cache) -- the compiled counterpart of
    `T.decode`, jit/donation friendly."""
    if program.kind != "decode":
        raise ValueError(f"execute_decode needs a decode program, got "
                         f"kind={program.kind!r}")
    ctx = _DecodeCtx(cache)
    if program.static:
        logits = _execute_static(program, params, tokens, eng, None,
                                 decode=ctx)
    else:
        logits = _execute_dynamic(program, params, tokens, eng, None, None,
                                  decode=ctx)
    return logits, ctx.finish()


def execute_verify(program: Program, params, cache: dict,
                   tokens: jax.Array, eng: EngineConfig
                   ) -> Tuple[jax.Array, Dict[int, tuple]]:
    """Teacher-force W tokens per slot through a DecodeStep program WITHOUT
    committing cache state -- the speculative-decode verification step.

    tokens: [B, W] int32 (position i sits at cache position pos+i).  Each
    AttnOp scatters its fresh per-token (k, v) into a read-once VIEW of the
    cache, so token i attends to committed history plus drafts 0..i exactly
    as sequential decode would -- logits are bit-identical to W sequential
    `execute_decode` steps.  Returns (logits [B, W, V], kvs): per-layer
    post-RoPE fresh (k, v) [B, W, Hkv, D] for `commit_decode_kv`.
    """
    if program.kind != "decode":
        raise ValueError(f"execute_verify needs a decode program, got "
                         f"kind={program.kind!r}")
    ctx = _DecodeCtx(cache)
    ctx.collect = {}
    if program.static:
        logits = _execute_static(program, params, tokens, eng, None,
                                 decode=ctx)
    else:
        logits = _execute_dynamic(program, params, tokens, eng, None, None,
                                  decode=ctx)
    return logits, ctx.collect


def commit_decode_kv(program: Program, cache: dict,
                     kvs: Dict[int, tuple], accept: jax.Array,
                     eng: EngineConfig) -> dict:
    """Commit the accepted prefix of verified draft (k, v) into the cache.

    kvs: `execute_verify`'s per-layer fresh (k, v) [B, W, Hkv, D];
    accept: [B] int32, tokens to commit per slot (0 <= accept <= W; 0 for
    idle slots).  Draft position i commits iff i < accept[b]; rejected
    writes are redirected past the buffer end and dropped, so a rolled-back
    slot's cache is untouched.  pos advances by accept.  Returns the new
    cache dict (same schema, donation friendly)."""
    accept = jnp.asarray(accept, jnp.int32)
    b = accept.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (b,))
    tables = cache.get("tables")
    layers = list(cache["layers"])
    for n in program.graph.nodes:
        if not (isinstance(n, AttnOp) and n.mode == "update"):
            continue
        k, v = kvs[n.layer]
        entry = layers[n.layer]
        for i in range(k.shape[1]):
            mask = i < accept
            ki, vi = k[:, i:i + 1], v[:, i:i + 1]
            if n.page_size:
                entry = T._paged_kv_store(entry, ki, vi, tables, pos + i,
                                          eng, n.page_size, mask=mask)
            elif n.layer_kind == "local":
                w = entry["k"].shape[1]
                entry = T._masked_kv_store(entry, ki, vi, (pos + i) % w,
                                           mask, eng)
            else:
                entry = T._masked_kv_store(entry, ki, vi, pos + i, mask, eng)
        layers[n.layer] = entry
    out = {"layers": layers, "pos": cache["pos"] + accept}
    if tables is not None:
        out["tables"] = tables
    return out


class _ChunkCtx:
    """Paged-cache state threaded through a chunk (partial-prefill)
    program's AttnOps.

    `start` is the STATIC absolute position of the tail's first token
    (uniform across rows -- sharing pins the padded prompt width, so
    every admitted row's tail occupies positions [start, start+T)).
    `row_starts` [B] is each row's first NON-SHARED position: stores
    below it are dropped (those pages belong to the prefix index and
    possibly other tables -- the copy-on-write boundary), but the row
    still RECOMPUTES [start, row_starts) so one fused wave can mix
    match lengths; recomputed values are bit-identical to the shared
    pages' content, so skipping their store changes nothing.
    `mask` [B] gates rows being (re)filled, like _run_paged_prefill."""

    def __init__(self, cache: dict, start: int, row_starts, mask):
        self.cache = cache
        self.tables = cache["tables"]
        self.start = start
        self.row_starts = row_starts
        self.mask = mask
        self.new_layers: Dict[int, dict] = {}

    def entry(self, layer: int) -> dict:
        return self.cache["layers"][layer]

    def finish(self, width: int) -> dict:
        layers = [self.new_layers.get(i, e)
                  for i, e in enumerate(self.cache["layers"])]
        pos = jnp.where(self.mask, self.start + width, self.cache["pos"])
        return {"layers": layers, "pos": pos, "tables": self.tables}


def prefill_from(program: Program, params, cache: dict, tokens: jax.Array,
                 eng: EngineConfig, *, start: int, row_starts, mask
                 ) -> Tuple[jax.Array, dict]:
    """Run a chunk program: prefill the TAIL of a prompt whose first
    `start` positions already sit in the paged cache (shared prefix
    pages matched by the engine's prefix index).

    tokens: [B, T] int32, the tail span (absolute positions
    [start, start+T)); cache: T.paged_cache_schema layout with "tables"
    bound.  row_starts [B] is each row's first position NOT covered by
    its matched prefix (start <= row_starts[b] <= start+T); mask [B]
    gates the rows being filled.  Returns (last-position logits
    [B, 1, V], new cache) -- masked rows' cache entries and positions
    are untouched, matching `_run_paged_prefill` semantics.

    start == 0 with row_starts == 0 reproduces a whole-prompt paged
    prefill through the same program, which is what makes any
    page-aligned split point bit-identical: the attended k/v ALWAYS
    round-trips the cache dtype, whether it came from a shared page or
    the fresh tail."""
    if program.kind != "chunk":
        raise ValueError(f"prefill_from needs a chunk program, got "
                         f"kind={program.kind!r}")
    b = tokens.shape[0]
    row_starts = jnp.broadcast_to(
        jnp.asarray(row_starts, jnp.int32), (b,))
    mask = jnp.broadcast_to(jnp.asarray(mask, bool), (b,))
    ctx = _ChunkCtx(cache, jnp.asarray(start, jnp.int32), row_starts, mask)
    if program.static:
        logits = _execute_static(program, params, tokens, eng, None,
                                 chunk=ctx)
    else:
        logits = _execute_dynamic(program, params, tokens, eng, None, None,
                                  chunk=ctx)
    return logits, ctx.finish(tokens.shape[1])


# ---------------------------------------------------------------------------
# Scheduled dispatch (shared by both modes)
# ---------------------------------------------------------------------------

def _refcounts(g: Graph) -> Dict[int, int]:
    counts: Dict[int, int] = {}
    for n in g.nodes:
        for i in n.inputs:
            counts[i] = counts.get(i, 0) + 1
    return counts


def _release(vals: Dict, counts: Dict[int, int], n: OpNode, g: Graph) -> None:
    """Drop activations after their last consumer so eager (un-jitted)
    execution keeps O(live edges) -- not O(all nodes) -- tensors alive,
    matching the replaced hand-written forward's rebinding behavior."""
    for i in n.inputs:
        counts[i] -= 1
        if counts[i] == 0 and i != g.output:
            del vals[i]


def _dispatch_waves(program: Program) -> Iterable[Tuple[OpNode, ...]]:
    """The execution order: schedule levels when present, else one op per
    wave in raw topological order."""
    g = program.graph
    if program.schedule is None:
        for n in g.nodes:
            yield (n,)
    else:
        for level in program.schedule.levels:
            yield tuple(g.nodes[i] for i in level)


def _eval_scoped(eval_node, n: OpNode, vals: Dict[int, object]):
    """Evaluate one node under the named scope of its engine (conv_pe,
    dwc_pe, low_channel, misc or mem): every HLO op it lowers to, glue
    included, carries the engine in its op_name metadata.  Metadata only;
    the computation is unchanged."""
    with jax.named_scope(engine_unit(n)):
        return eval_node(n, vals)


def _run_scheduled(program: Program, eval_node, observer=None):
    """Evaluate the program wave-by-wave.  Each wave's ops read only values
    produced by earlier waves (`vals` is merged after the whole wave), so a
    schedule bug that co-levels dependent ops raises KeyError instead of
    silently reading a half-updated environment."""
    g = program.graph
    counts = _refcounts(g)
    vals: Dict[int, object] = {}
    for wave in _dispatch_waves(program):
        produced = [(n, _eval_scoped(eval_node, n, vals)) for n in wave]
        for n, v in produced:
            vals[n.id] = v
        for n, v in produced:
            if observer is not None:
                observer(n, v)
            _release(vals, counts, n, g)
    return vals[g.output]


# ---------------------------------------------------------------------------
# LM op evaluators (shared by both modes; the float-domain MISC work)
# ---------------------------------------------------------------------------

# Module-level bounded cos/sin table store: repeated eager executes (serve
# waves draining through un-jitted paths, calibration sweeps, tests) stop
# rebuilding the same RoPE tables on every call.  Bounded LRU so a server
# sweeping many (B, L) shapes cannot grow it without limit.
_ROPE_TABLE_CAPACITY = 32
_rope_tables: "OrderedDict[Tuple, Tuple[jax.Array, jax.Array]]" = OrderedDict()


def _rope_table(b: int, l: int, hd: int, theta: float):
    """The (cos, sin) table for (B, L, head_dim, theta).

    Concrete tables are cached module-wide (every AttnOp of every program
    with the same geometry reuses one table).  Traced values -- execute()
    running under jit -- are NEVER stored: a cached tracer would poison
    later calls, and jitted programs constant-fold the tables into their
    trace anyway, so the cache only needs to serve eager execution.
    """
    key = (b, l, hd, theta)
    hit = _rope_tables.get(key)
    if hit is not None:
        _rope_tables.move_to_end(key)
        return hit
    pos = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    val = L.rope_angles(pos, hd, theta)
    if not isinstance(val[0], jax.core.Tracer):
        _rope_tables[key] = val
        while len(_rope_tables) > _ROPE_TABLE_CAPACITY:
            _rope_tables.popitem(last=False)
    return val


def rope_table_stats() -> Dict[str, int]:
    """Introspection for tests/benchmarks."""
    return {"entries": len(_rope_tables),
            "capacity": _ROPE_TABLE_CAPACITY}


def _rope_decode_memo(pos):
    """Decode-step RoPE: angles at the cache position(s), one table per
    (B, W, head_dim, theta) per execute_decode() call.  `pos` is a scalar
    or [B] per-slot position vector (both traced under jit); draft token i
    of a W-wide verify burst sits at position pos + i (W == 1 reproduces
    the single-token table bitwise: + arange(1) is the integer identity)."""
    cache: Dict[Tuple, Tuple[jax.Array, jax.Array]] = {}

    def rope(b: int, w: int, hd: int, theta: float):
        key = (b, w, hd, theta)
        if key not in cache:
            base = (pos[:, None] if jnp.asarray(pos).ndim == 1
                    else jnp.broadcast_to(pos[None, None], (b, 1)))
            positions = base + jnp.arange(w, dtype=jnp.int32)[None, :]
            cache[key] = L.rope_angles(positions, hd, theta)
        return cache[key]

    return rope


def _embed_eval(n: EmbedOp, tokens: jax.Array, params) -> jax.Array:
    emb = get_param(params, n.w)
    if isinstance(emb, QTensor):
        rows = jnp.take(emb.q, tokens, axis=0).astype(jnp.float32)
        x = rows * jnp.take(emb.scale, tokens, axis=0)
    else:
        x = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
    if n.emb_scale:
        x = x * jnp.asarray(n.emb_scale, jnp.float32)
    return x


def _cache_roundtrip(val: jax.Array, eng: EngineConfig) -> jax.Array:
    """Cast a fresh k/v slice [B, 1, Hkv, D] exactly as a cache store+read
    roundtrip would: int8 caches quantize per-token and dequantize back to
    bf16; bf16 caches just downcast.  The speculative verify path scatters
    these into the read-once cache view, so each draft token sees the SAME
    bits sequential store-then-read decode would produce."""
    if eng.kv_cache_dtype == "int8":
        q = quantize_act_dynamic(val, per_token=True)
        return (q.q.astype(jnp.float32) * q.scale).astype(jnp.bfloat16)
    return val.astype(jnp.bfloat16)


def _attn_update_eval(n: AttnOp, q: jax.Array, k: jax.Array, v: jax.Array,
                      rope_d, ctx: "_DecodeCtx", eng: EngineConfig
                      ) -> jax.Array:
    """AttnOp in `update` mode: write this token's (k, v) into the cache at
    the slot position, then attend against the cache -- the op-level twin
    of the attention body of `T.decode` (bit-identical cache layout).

    Three variants share this evaluator:
      * dense commit (page_size == 0, width 1): the historical path, byte
        for byte unchanged.
      * paged commit (n.page_size > 0): the store goes through the block
        table into the shared pool; the read gathers the slot-ordered
        dense view, so attention math is identical to the dense cache.
      * verify (ctx.collect set, width W >= 1): NOTHING commits.  Fresh
        (k, v) scatter into a read-once view and draft token i attends to
        committed history + drafts 0..i -- teacher-forced sequential
        decode, restartable because the real cache never moved.
    """
    b, width = q.shape[0], q.shape[1]
    g = n.n_heads // n.n_kv_heads
    q = q.reshape(b, width, n.n_kv_heads, g, n.head_dim)
    k = k.reshape(b, width, n.n_kv_heads, n.head_dim)
    v = v.reshape(b, width, n.n_kv_heads, n.head_dim)
    cos, sin = rope_d(b, width, n.head_dim, n.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    entry = ctx.entry(n.layer)
    paged = bool(n.page_size)
    ring = n.layer_kind == "local"
    if ctx.collect is not None:
        ctx.collect[n.layer] = (k, v)
        if paged:
            kc, vc = T._paged_kv_read(entry, ctx.tables, eng)
        else:
            kc, vc = T._kv_read(entry, eng)
        s = kc.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(ctx.pos, jnp.int32), (b,))
        rows = jnp.arange(b)
        outs = []
        for i in range(width):
            slot = (pos + i) % s if ring else pos + i
            ki = _cache_roundtrip(k[:, i:i + 1], eng)[:, 0]
            vi = _cache_roundtrip(v[:, i:i + 1], eng)[:, 0]
            kc = kc.at[rows, slot].set(ki.astype(kc.dtype), mode="drop")
            vc = vc.at[rows, slot].set(vi.astype(vc.dtype), mode="drop")
            outs.append(L.decode_attention(
                q[:, i:i + 1], kc, vc, pos + i + 1, window=n.window,
                logit_softcap=n.softcap, ring=ring))
        out = jnp.concatenate(outs, axis=1)
        return out.reshape(b, width, n.n_heads * n.head_dim
                           ).astype(jnp.float32)
    if paged:
        entry = T._paged_kv_store(entry, k, v, ctx.tables, ctx.pos, eng,
                                  n.page_size)
        ctx.new_layers[n.layer] = entry
        kc, vc = T._paged_kv_read(entry, ctx.tables, eng)
    else:
        if ring:
            w = entry["k"].shape[1]
            entry = T._kv_store(entry, k, v, ctx.pos % w, eng)
        else:
            entry = T._kv_store(entry, k, v, ctx.pos, eng)
        ctx.new_layers[n.layer] = entry
        kc, vc = T._kv_read(entry, eng)
    out = L.decode_attention(q, kc, vc, ctx.pos + 1, window=n.window,
                             logit_softcap=n.softcap, ring=ring)
    return out.reshape(b, 1, n.n_heads * n.head_dim).astype(jnp.float32)


def _attn_chunk_eval(n: AttnOp, q: jax.Array, k: jax.Array, v: jax.Array,
                     rope_c, ctx: "_ChunkCtx", eng: EngineConfig
                     ) -> jax.Array:
    """AttnOp in `chunk` mode: the prefix-sharing partial prefill.

    The tail's fresh (k, v) is RoPE'd at its absolute positions
    (start + j), scattered through the block table into the slot's OWNED
    tail pages only (positions < row_starts[b] drop -- those pages are
    shared, read-only), then the tail queries attend the gathered cache
    view at q_offset=start.  Reading back AFTER the store means every
    attended key -- shared prefix and fresh tail alike -- has
    round-tripped the cache dtype, so logits are invariant to WHERE the
    page-aligned split fell (the bit-identity contract the golden test
    pins).  Rows whose match extends past `start` recompute those
    positions; the recomputed bits equal the shared pages' content, and
    their store is masked off, so nothing shared is ever written."""
    b, t = q.shape[0], q.shape[1]
    g = n.n_heads // n.n_kv_heads
    q = q.reshape(b, t, n.n_kv_heads, g, n.head_dim)
    k = k.reshape(b, t, n.n_kv_heads, n.head_dim)
    v = v.reshape(b, t, n.n_kv_heads, n.head_dim)
    cos, sin = rope_c(b, t, n.head_dim, n.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    entry = ctx.entry(n.layer)
    entry = T._paged_tail_store(entry, k, v, ctx.tables, ctx.mask, eng,
                                n.page_size, ctx.start, ctx.row_starts)
    ctx.new_layers[n.layer] = entry
    kc, vc = T._paged_kv_read(entry, ctx.tables, eng)
    out = L.flash_attention(q, kc, vc, causal=True, window=n.window,
                            logit_softcap=n.softcap, q_offset=ctx.start)
    return out.reshape(b, t, n.n_heads * n.head_dim).astype(jnp.float32)


def _attn_eval(n: AttnOp, q: jax.Array, k: jax.Array, v: jax.Array,
               rope, collect: Optional[dict]) -> jax.Array:
    b, l = q.shape[0], q.shape[1]
    g = n.n_heads // n.n_kv_heads
    q = q.reshape(b, l, n.n_kv_heads, g, n.head_dim)
    k = k.reshape(b, l, n.n_kv_heads, n.head_dim)
    v = v.reshape(b, l, n.n_kv_heads, n.head_dim)
    cos, sin = rope(b, l, n.head_dim, n.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    out = L.flash_attention(q, k, v, causal=True, window=n.window,
                            logit_softcap=n.softcap)
    if collect is not None:
        collect[n.layer] = (k, v)          # post-RoPE k, like T.prefill
    return out.reshape(b, l, n.n_heads * n.head_dim)


def _head_eval(n: HeadOp, x: jax.Array, params) -> jax.Array:
    w = get_param(params, n.w)
    xf = x.astype(jnp.float32)
    if n.last_only:
        xf = xf[:, -1:]
    sig = "bld,vd->blv" if n.tied else "bld,dv->blv"
    if isinstance(w, QTensor):
        logits = jnp.einsum(sig, xf, w.q.astype(jnp.float32))
        logits = logits * w.scale.reshape(1, 1, -1)
    else:
        logits = jnp.einsum(sig, xf, w.astype(jnp.float32))
    if n.softcap > 0:
        logits = jnp.tanh(logits / n.softcap) * n.softcap
    return logits


# ---------------------------------------------------------------------------
# Dynamic mode (eager-equivalent; also the calibration vehicle)
# ---------------------------------------------------------------------------

def _dynamic_eval(program: Program, params, images, eng: EngineConfig,
                  collect: Optional[dict] = None,
                  decode: Optional[_DecodeCtx] = None,
                  chunk: Optional["_ChunkCtx"] = None):
    """The dynamic-mode eval_node closure for one program invocation.

    Factored out of _execute_dynamic so execute_interleaved can drive two
    programs' evaluators on one merged tick stream."""
    rope = _rope_table
    rope_d = _rope_decode_memo(decode.pos) if decode is not None else None
    rope_c = (_rope_decode_memo(jnp.asarray(chunk.start, jnp.int32))
              if chunk is not None else None)

    def eval_node(n: OpNode, vals: Dict[int, jax.Array]) -> jax.Array:
        if isinstance(n, InputOp):
            return images
        if isinstance(n, ConvOp):
            w, b = get_param(params, n.w), get_param(params, n.b)
            ep = n.epilogue
            res = (vals[n.inputs[-1]] if ep is not None and ep.add
                   else None)
            if n.first_layer:
                v = ops.first_layer_conv(vals[n.inputs[0]], w, b, n.stride,
                                         n.padding, n.act, eng,
                                         epilogue=ep, residual=res)
                return v.astype(jnp.float32)
            return ops.conv2d_pe(vals[n.inputs[0]], w, b, n.stride,
                                 n.padding, n.act, eng,
                                 epilogue=ep, residual=res)
        if isinstance(n, DwcOp):
            w, b = get_param(params, n.w), get_param(params, n.b)
            ep = n.epilogue
            res = (vals[n.inputs[-1]] if ep is not None and ep.add
                   else None)
            return ops.dwc2d(vals[n.inputs[0]], w, b, n.stride, n.padding,
                             n.act, eng, epilogue=ep, residual=res)
        if isinstance(n, AddOp):
            return ops.misc_add(vals[n.inputs[0]], vals[n.inputs[1]],
                                n.act, eng)
        if isinstance(n, PoolOp):
            x = vals[n.inputs[0]]
            if n.pool == "global":
                return ref.global_avgpool(x)
            if n.pool == "avg":
                return ops.avgpool2d(x, n.kernel, n.stride, eng)
            return ref.maxpool2d(x, n.kernel, n.stride)
        if isinstance(n, ConcatOp):
            return jnp.concatenate([vals[i] for i in n.inputs], axis=-1)
        if isinstance(n, LinearOp):
            w, b = get_param(params, n.w), get_param(params, n.b)
            ep = n.epilogue
            if ep is not None and ep.add:
                return ops.linear_ep(vals[n.inputs[0]], w, b, n.act, ep,
                                     vals[n.inputs[-1]], eng,
                                     out_dtype=jnp.float32)
            return ops.linear(vals[n.inputs[0]], w, b, n.act, eng,
                              out_dtype=jnp.float32)
        if isinstance(n, LinearGroupOp):
            ws = [get_param(params, w) for w in n.ws]
            bs = [get_param(params, b) for b in n.bs]
            return ops.linear_group(vals[n.inputs[0]], ws, bs, n.acts, eng,
                                    out_dtype=jnp.float32)
        if isinstance(n, ViewOp):
            return vals[n.inputs[0]][n.index]
        if isinstance(n, EmbedOp):
            return _embed_eval(n, vals[n.inputs[0]], params)
        if isinstance(n, NormOp):
            return L.rms_norm(vals[n.inputs[0]], get_param(params, n.w),
                              n.eps)
        if isinstance(n, MulOp):
            return (vals[n.inputs[0]] * vals[n.inputs[1]]
                    ).astype(jnp.float32)
        if isinstance(n, AttnOp):
            if n.mode == "update":
                return _attn_update_eval(n, vals[n.inputs[0]],
                                         vals[n.inputs[1]], vals[n.inputs[2]],
                                         rope_d, decode, eng)
            if n.mode == "chunk":
                return _attn_chunk_eval(n, vals[n.inputs[0]],
                                        vals[n.inputs[1]], vals[n.inputs[2]],
                                        rope_c, chunk, eng)
            return _attn_eval(n, vals[n.inputs[0]], vals[n.inputs[1]],
                              vals[n.inputs[2]], rope, collect)
        if isinstance(n, HeadOp):
            return _head_eval(n, vals[n.inputs[0]], params)
        raise TypeError(f"unknown op {type(n).__name__}")

    return eval_node


def _execute_dynamic(program: Program, params, images, eng: EngineConfig,
                     observer=None, collect: Optional[dict] = None,
                     decode: Optional[_DecodeCtx] = None,
                     chunk: Optional["_ChunkCtx"] = None) -> jax.Array:
    eval_node = _dynamic_eval(program, params, images, eng, collect, decode,
                              chunk)
    return _run_scheduled(program, eval_node, observer)


# ---------------------------------------------------------------------------
# Static mode (calibrated end-to-end int8 dataflow)
# ---------------------------------------------------------------------------

def _require_qtensor(w, n: OpNode, path=None):
    if not isinstance(w, (QTensor, Q4Tensor)):
        raise ValueError(
            f"static program: {type(n).__name__} #{n.id} expects quantized "
            f"(QTensor / Q4Tensor) weights at "
            f"{path if path is not None else getattr(n, 'w', None)}; "
            "quantize params with core.engine.quantize_params first")
    return w


def _static_eval(program: Program, params, images,
                 eng: EngineConfig, collect: Optional[dict] = None,
                 decode: Optional[_DecodeCtx] = None,
                 chunk: Optional["_ChunkCtx"] = None):
    """The static-mode eval_node closure for one program invocation (the
    counterpart of _dynamic_eval; shared by _execute_static and
    execute_interleaved)."""
    plan = program.plan
    scale_of = plan.out_scale
    rope = _rope_table
    rope_d = _rope_decode_memo(decode.pos) if decode is not None else None
    rope_c = (_rope_decode_memo(jnp.asarray(chunk.start, jnp.int32))
              if chunk is not None else None)

    def out_scale_for(n: OpNode):
        return scale_of[n.id] if plan.emit_int8[n.id] else None

    def _as_scale(nid: int, os):
        """The node's out-scale as an array: the compile-time constant the
        plan precomputed (passes.fold_requant), falling back to a fresh
        conversion only for plans built before scale_arr existed."""
        arr = plan.scale_arr.get(nid)
        return arr if arr is not None else jnp.asarray(os, jnp.float32)

    def _q_or_raw(r, n: OpNode, os):
        """A float-domain MISC op's requant epilogue: int8 when the plan
        carries the edge int8 (all consumers are GEMM engines), f32 else."""
        if os is None:
            return r
        return QTensor(quantize_static(r, _as_scale(n.id, os)), os)

    def _raw(v):
        return v.dequant() if isinstance(v, QTensor) else v

    def _scaled(v):
        return (v.q, float(v.scale)) if isinstance(v, QTensor) else (v, 1.0)

    def eval_node(n: OpNode, vals: Dict[int, QTensor]):
        os = out_scale_for(n)
        if isinstance(n, InputOp):
            if os is None:
                return images              # token ids pass through raw
            # One static quantization at the boundary; int8 from here on.
            return QTensor(quantize_static(images, _as_scale(n.id, os)), os)
        if isinstance(n, ConvOp):
            w = _require_qtensor(get_param(params, n.w), n)
            b = get_param(params, n.b)
            ep = n.epilogue
            res, res_s = None, 1.0
            if ep is not None and ep.add:
                res, res_s = _scaled(vals[n.inputs[-1]])
            fn = ops.first_layer_conv if n.first_layer else ops.conv2d_pe
            r = fn(vals[n.inputs[0]], w, b, n.stride, n.padding, n.act, eng,
                   out_scale=os, epilogue=ep, residual=res, res_scale=res_s)
            return QTensor(r, os)
        if isinstance(n, DwcOp):
            w = _require_qtensor(get_param(params, n.w), n)
            b = get_param(params, n.b)
            ep = n.epilogue
            res, res_s = None, 1.0
            if ep is not None and ep.add:
                res, res_s = _scaled(vals[n.inputs[-1]])
            r = ops.dwc2d(vals[n.inputs[0]], w, b, n.stride, n.padding,
                          n.act, eng, out_scale=os, epilogue=ep,
                          residual=res, res_scale=res_s)
            return QTensor(r, os)
        if isinstance(n, AddOp):
            # Mixed domains compose: a CNN residual add sees two int8 edges,
            # an LM residual add sees the f32 stream + the block's int8
            # GEMM output (dequantized by its static scale in this pass).
            a, sa = _scaled(vals[n.inputs[0]])
            b, sb = _scaled(vals[n.inputs[1]])
            r = ops.misc_add(a, b, n.act, eng, sa=sa, sb=sb, out_scale=os)
            return QTensor(r, os) if os is not None else r
        if isinstance(n, PoolOp):
            x = vals[n.inputs[0]]
            if n.pool == "max":
                # Order-preserving on int8: values and scale pass through.
                return QTensor(ref.maxpool2d(x.q, n.kernel, n.stride), os)
            if n.pool == "global":
                # Sum in int32 (like every engine accumulator), then one
                # fused scale+requant epilogue -- no f32 fmap materialized.
                acc = jnp.sum(x.q.astype(jnp.int32), axis=(1, 2))
                px = x.q.shape[1] * x.q.shape[2]
                r = acc.astype(jnp.float32) * (float(x.scale) / px)
                return (QTensor(quantize_static(r, _as_scale(n.id, os)), os)
                        if os is not None else r)
            acc = jax.lax.reduce_window(
                x.q.astype(jnp.int32), 0, jax.lax.add,
                (1, n.kernel, n.kernel, 1), (1, n.stride, n.stride, 1),
                "VALID")
            r = acc.astype(jnp.float32) * (float(x.scale) / n.kernel ** 2)
            return QTensor(quantize_static(r, _as_scale(n.id, os)), os)
        if isinstance(n, ConcatOp):
            parts = []
            for i in n.inputs:
                xi = vals[i]
                if xi.scale == os:            # requant folded into producer
                    parts.append(xi.q)
                else:                         # MISC-side int8->int8 rescale
                    parts.append(_rescale_int8(xi.q, float(xi.scale), os))
            return QTensor(jnp.concatenate(parts, axis=-1), os)
        if isinstance(n, LinearOp):
            w = _require_qtensor(get_param(params, n.w), n)
            b = get_param(params, n.b)
            x = vals[n.inputs[0]]
            ep = n.epilogue
            if ep is not None and ep.add:
                res, res_s = _scaled(vals[n.inputs[-1]])
                r = ops.linear_ep(x, w, b, n.act, ep, res, eng,
                                  res_scale=res_s, out_scale=os,
                                  out_dtype=jnp.float32)
            else:
                r = ops.linear(x, w, b, n.act, eng, out_dtype=jnp.float32,
                               out_scale=os)
            return QTensor(r, os) if os is not None else r
        if isinstance(n, LinearGroupOp):
            ws = [_require_qtensor(get_param(params, p), n, p)
                  for p in n.ws]
            bs = [get_param(params, b) for b in n.bs]
            # One launch, tuple value; member edges stay f32 (their
            # consumers -- attention, the gate product -- are float-domain
            # MISC ops, so the views never requantize).
            return ops.linear_group(vals[n.inputs[0]], ws, bs, n.acts, eng,
                                    out_dtype=jnp.float32)
        if isinstance(n, ViewOp):
            return vals[n.inputs[0]][n.index]
        if isinstance(n, EmbedOp):
            return _q_or_raw(_embed_eval(n, _raw(vals[n.inputs[0]]),
                                         params), n, os)
        if isinstance(n, NormOp):
            # f32 norm math on the MISC core; the requant epilogue is what
            # hands the consumer GEMMs their static-int8 activations.
            r = L.rms_norm(_raw(vals[n.inputs[0]]), get_param(params, n.w),
                           n.eps)
            return _q_or_raw(r, n, os)
        if isinstance(n, MulOp):
            r = (_raw(vals[n.inputs[0]]) * _raw(vals[n.inputs[1]])
                 ).astype(jnp.float32)
            return _q_or_raw(r, n, os)
        if isinstance(n, AttnOp):
            if n.mode == "update":
                r = _attn_update_eval(n, _raw(vals[n.inputs[0]]),
                                      _raw(vals[n.inputs[1]]),
                                      _raw(vals[n.inputs[2]]),
                                      rope_d, decode, eng)
            elif n.mode == "chunk":
                r = _attn_chunk_eval(n, _raw(vals[n.inputs[0]]),
                                     _raw(vals[n.inputs[1]]),
                                     _raw(vals[n.inputs[2]]),
                                     rope_c, chunk, eng)
            else:
                r = _attn_eval(n, _raw(vals[n.inputs[0]]),
                               _raw(vals[n.inputs[1]]),
                               _raw(vals[n.inputs[2]]), rope, collect)
            return _q_or_raw(r, n, os)
        if isinstance(n, HeadOp):
            return _head_eval(n, _raw(vals[n.inputs[0]]), params)
        raise TypeError(f"unknown op {type(n).__name__}")

    return eval_node


def _execute_static(program: Program, params, images,
                    eng: EngineConfig, collect: Optional[dict] = None,
                    decode: Optional[_DecodeCtx] = None,
                    chunk: Optional["_ChunkCtx"] = None) -> jax.Array:
    eval_node = _static_eval(program, params, images, eng, collect, decode,
                             chunk)
    out = _run_scheduled(program, eval_node)
    return out.dequant() if isinstance(out, QTensor) else out


def _rescale_int8(q: jax.Array, s_in: float, s_out: float) -> jax.Array:
    """int8 -> int8 rescale without materializing an f32 tensor between
    engines (a MISC-core epilogue in hardware)."""
    r = jnp.clip(jnp.round(q.astype(jnp.float32) * (s_in / s_out)),
                 -127, 127)
    return r.astype(jnp.int8)


# ---------------------------------------------------------------------------
# Fabric-interleaved execution (multi-tenant co-mapping, f-CNNx style)
# ---------------------------------------------------------------------------

def _eval_for(program: Program, params, inputs, eng: EngineConfig,
              collect: Optional[dict] = None,
              decode: Optional[_DecodeCtx] = None):
    if program.static:
        return _static_eval(program, params, inputs, eng, collect, decode)
    return _dynamic_eval(program, params, inputs, eng, collect, decode)


class _Lane:
    """One program's value environment advancing level-by-level under an
    external tick driver (execute_interleaved).  Same wave semantics as
    _run_scheduled: a level's ops read only earlier levels' values, merged
    after the whole wave, with last-consumer release."""

    def __init__(self, program: Program, eval_node):
        self.g = program.graph
        self.eval_node = eval_node
        self.counts = _refcounts(self.g)
        self.vals: Dict[int, object] = {}
        self.waves = tuple(_dispatch_waves(program))

    def step(self, k: int) -> None:
        produced = [(n, _eval_scoped(self.eval_node, n, self.vals))
                    for n in self.waves[k]]
        for n, v in produced:
            self.vals[n.id] = v
        for n, _ in produced:
            _release(self.vals, self.counts, n, self.g)

    def result(self):
        out = self.vals[self.g.output]
        return out.dequant() if isinstance(out, QTensor) else out


def execute_interleaved(program_a: Program, params_a, inputs_a,
                        program_b: Program, params_b, cache_b,
                        tokens_b, eng_a: EngineConfig,
                        eng_b: Optional[EngineConfig] = None,
                        merged: Optional[MergedSchedule] = None,
                        collect_a: Optional[dict] = None):
    """Run a forward program (lane A: CNN wave or LM prefill) and a
    DecodeStep program (lane B) on ONE fabric tick stream.

    Each merged tick evaluates at most one level of each lane, aligned by
    merge_schedules: a conv-heavy CNN level rides alongside a MISC-heavy
    LM decode level, so the units one tenant leaves idle are filled by the
    other (the f-CNNx co-mapping).  The lanes keep separate value
    environments and share no dataflow, so outputs are bit-identical to
    isolated execution -- what is shared is the dispatch stream (and,
    under jit, the fused per-tick computation).

    Returns (logits_a, logits_b, new_cache_b)."""
    if program_a.kind != "forward":
        raise ValueError(f"lane A must be a forward program, got "
                         f"kind={program_a.kind!r}")
    if program_b.kind != "decode":
        raise ValueError(f"lane B must be a decode program, got "
                         f"kind={program_b.kind!r}")
    if program_a.schedule is None or program_b.schedule is None:
        raise ValueError("execute_interleaved needs scheduled programs "
                         "(compile with scheduled=True)")
    eng_b = eng_b if eng_b is not None else eng_a
    ctx = _DecodeCtx(cache_b)
    lane_a = _Lane(program_a, _eval_for(program_a, params_a, inputs_a,
                                        eng_a, collect=collect_a))
    lane_b = _Lane(program_b, _eval_for(program_b, params_b, tokens_b,
                                        eng_b, decode=ctx))
    if merged is None:
        merged = merge_schedules(program_a.graph, program_a.schedule,
                                 program_b.graph, program_b.schedule,
                                 policy="asap")
    for ia, ib in merged.ticks:
        if ia is not None:
            lane_a.step(ia)
        if ib is not None:
            lane_b.step(ib)
    return lane_a.result(), lane_b.result(), ctx.finish()
