"""LM serving engine: compiled prefill + decode programs behind a
continuous-batching slot scheduler.

This is the small-scale executable counterpart of launch/build.build_serve
(which produces the production-mesh programs).  ServeEngine runs real tokens
on the local device(s) through the unified serve path (serve/base.py):

  * prefill AND decode both lower through the model-agnostic engine IR
    (compiler.lower_transformer) into programs cached in the keyed
    ProgramCache -- the same compile -> cache -> schedule pipeline
    CNNServeEngine uses -- keyed by (ArchConfig, EngineConfig,
    calibration-id) with distinct prefill/decode variants.  With
    calibration token batches and a w8a8 engine BOTH programs are
    static-int8 from ONE calibration run (compiler.calibrate_lm): every
    projection GEMM -- including every decode-step GEMM, the steady-state
    serving path -- consumes activations pre-quantized at compile-time
    scales instead of re-quantizing per token.
  * the compiled prefill program fills the decode KV cache (each AttnOp
    deposits its roped-k/v pair), and the compiled decode program IS the
    cache recurrence (AttnOp `update` mode): the decode burst executes it
    jit-once with the cache donated, exactly like the eager path it
    replaces.
  * requests queue in the shared SlotScheduler (serve/base.py): `submit()`
    enqueues (prompt, max_new_tokens); `run()` serves the whole queue with
    B fixed decode slots, refilling finished slots from the queue between
    decode bursts (continuous batching).  Prompts left-pad to one fixed
    prefill width, so a request's tokens depend only on its own padded
    slot row: with `prefill_len` pinned at construction, arrival order and
    batch composition cannot change its output (the order-invariance
    property test pins that; see run() on the unset-width default).

With `mesh=` the engine serves tensor-parallel (serve/mesh_exec.py):
projection weights shard over the mesh's "model" axis at whole-head
granularity, the KV cache replicates, and every decode-burst GEMM runs
sharded -- bit-identical to single-device execution (the sharded-parity
property test pins it).  Decode dispatch is async: bursts keep emitted
token columns on device and the host syncs only at response edges (a
request completing), never per step.

With `kv_layout="paged"` the engine's global-attention KV state lives in a
shared block pool (T.paged_cache_schema) behind ONE block table: a request
holds exactly ceil((prompt + max_new_tokens) / page_size) blocks from a
BlockAllocator (serve/kv_alloc.py), and admission gates on FREE BLOCKS, not
worst-case slot envelopes -- so sustainable concurrency at fixed memory
follows the measured request footprint (the paper's bandwidth thesis
applied to cache capacity).  Paged decode is bit-identical to dense: the
gather is a pure copy and masked positions exp-underflow to exactly zero.

With `prefix_sharing=True` (paged only, pinned `prefill_len`) requests that
share a page-aligned prompt PREFIX share the physical KV blocks holding it:
a hash-chain prefix index over page-sized token chunks (keyed by the
engine's (calibration-id, page_size)) maps each admitted request's padded
row to the longest already-cached prefix, the allocator refcounts those
blocks instead of allocating new ones (`BlockAllocator.share`), and
prefill runs a CHUNK program over only the unshared tail
(`compiler.prefill_from`): shared pages are read-only (stores below a
row's matched length drop -- copy-on-write at the page boundary), decode
writes always land in freshly owned pages, and release decrements
refcounts, freeing a block only when its last owner leaves.  The chunk
program ALWAYS round-trips attended k/v through the cache dtype (it
stores the fresh tail, then attends the gathered view), so a request's
token ids are a pure function of its padded row -- invariant to where
the page-aligned split falls and to index warmth.  When the compute
dtype equals the cache dtype (quant="none", bf16 cache: store-cast is
the identity) that makes shared serving bit-identical to non-shared
serving; with f32 attention inputs (static int8 programs) or an int8 KV
cache, non-shared PREFILL attends pre-roundtrip values the shared prefix
cannot reproduce, so sharing stays deterministic and split-invariant but
may round differently than the isolated engine.  Archs with local (ring)
attention layers fall back to whole-prompt prefill (the dense ring has no
page boundary to share at); `stats()["prefix_sharing"]` records the
blocker.

With `draft_len=k` decode runs SPECULATIVE bursts: each step teacher-forces
the current token plus k self-speculative n-gram drafts (no second model)
through ONE [B, 1+k]-wide DecodeStep execution (`execute_verify`), accepts
the longest greedy-consistent prefix, and commits only accepted positions
(`commit_decode_kv`) -- rejected drafts never touch the cache, so emitted
ids match one-token greedy decode token-for-token while each burst can
commit multiple tokens.

SSM / MoE mixers and the audio encoder-decoder stay eager: `stats()`
reports the exact `lowering_blockers` instead of silently falling back.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compiler
from repro.compiler import executor as ex
from repro.core import engine as eng_lib
from repro.core.config import ArchConfig, EngineConfig
from repro.models import params as prm
from repro.models import transformer as T
from repro.models import whisper as W
from repro.models.params import is_spec
from repro.serve.base import (ProgramServeBase, SlotScheduler,
                              calibration_digest)
from repro.serve.kv_alloc import BlockAllocator
from repro.serve.program_cache import ProgramCache

_LM = "lm"                            # the scheduler's single slot group


class PrefixIndex:
    """Hash-chain index over page-aligned token chunks -> physical blocks.

    Each node keys one page-sized chunk of a padded prompt by the CHAIN of
    chunks before it (the node key is the tuple of chunk byte-strings from
    the root), so `match()` walks the longest indexed prefix in O(pages)
    dict lookups -- a radix tree flattened into a dict.  The index holds
    its OWN refcount on every registered block (`alloc.share`), so a block
    stays warm for future matches after its last request leaves; under
    allocation pressure `evict_for()` drops leaf nodes nobody but the
    index references (refcount == 1), children before parents.

    `key` records the (calibration-id, page_size) the index is valid for:
    cached KV bits are a function of both, so an engine never matches
    pages produced under a different quantization or page geometry.
    """

    def __init__(self, page_size: int, alloc: BlockAllocator,
                 key=None):
        self.page = int(page_size)
        self.alloc = alloc
        self.key = key
        # chain-key tuple -> {"block": id, "children": set of chain keys}
        self._nodes: Dict[tuple, dict] = {}
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._nodes)

    def _chunk(self, row: np.ndarray, i: int) -> bytes:
        return np.ascontiguousarray(
            row[i * self.page:(i + 1) * self.page], np.int32).tobytes()

    def match(self, row: np.ndarray, max_pages: int) -> List[int]:
        """Block ids of the longest indexed page-aligned prefix of `row`
        (the PADDED prompt -- pad tokens are ordinary context, so cached
        bits are a function of the padded row).  Pure: no refcounts move;
        callers `alloc.share()` the result when they bind it."""
        key, blocks = (), []
        for i in range(min(max_pages, len(row) // self.page)):
            nkey = key + (self._chunk(row, i),)
            node = self._nodes.get(nkey)
            if node is None:
                break
            blocks.append(node["block"])
            key = nkey
        return blocks

    def register(self, row: np.ndarray, blocks: List[int],
                 pages: int) -> int:
        """Index the first `pages` chunks of `row` against `blocks`.  Pages
        already indexed keep their existing node (the caller matched them,
        so blocks[i] IS that node's block); new nodes take an index-owned
        refcount.  Returns how many new nodes were added."""
        key, added = (), 0
        for i in range(min(pages, len(row) // self.page, len(blocks))):
            nkey = key + (self._chunk(row, i),)
            if nkey not in self._nodes:
                self.alloc.share([blocks[i]])        # the index's own ref
                self._nodes[nkey] = {"block": blocks[i], "children": set()}
                if key in self._nodes:
                    self._nodes[key]["children"].add(nkey)
                added += 1
            key = nkey
        return added

    def held_only(self) -> int:
        """Blocks the index alone still references (refcount == 1) --
        reclaimable by eviction, and excluded from 'active' occupancy."""
        return sum(1 for n in self._nodes.values()
                   if self.alloc.refcount(n["block"]) == 1)

    def evict_for(self, need: int, protected=frozenset()) -> int:
        """Free index-only leaf nodes (children first) until `need` blocks
        are free or nothing evictable remains.  `protected` blocks (a
        candidate request's matched chain) are never victims.  Returns the
        number of nodes evicted."""
        evicted = 0
        while self.alloc.free_blocks < need:
            victim = next(
                (k for k, n in self._nodes.items()
                 if not n["children"]
                 and n["block"] not in protected
                 and self.alloc.refcount(n["block"]) == 1), None)
            if victim is None:
                break
            node = self._nodes.pop(victim)
            parent = victim[:-1]
            if parent in self._nodes:
                self._nodes[parent]["children"].discard(victim)
            self.alloc.free([node["block"]])
            self.evictions += 1
            evicted += 1
        return evicted

    def reset(self) -> None:
        """Drop every node and its ref -- for when the pool backing the
        indexed bits is discarded (the chains would otherwise resolve to
        blocks whose contents no longer exist)."""
        for node in self._nodes.values():
            self.alloc.free([node["block"]])
        self._nodes.clear()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # [L] int32
    max_new_tokens: int = 16
    out_tokens: Optional[list] = None


@dataclasses.dataclass(frozen=True)
class SubmitRejection:
    """Structured submit() rejection (queue-level backpressure, NOT an
    exception): the request cannot be served by this engine configuration.
    Falsy, so `if ticket:` keeps working for accepted submissions."""
    reason: str                     # "over_length" | "over_capacity"
    detail: str
    prompt_len: int
    max_new_tokens: int

    def __bool__(self) -> bool:
        return False


@dataclasses.dataclass
class LMServeStats:
    """Continuous-batching counters across run() calls."""
    requests: int = 0
    prefill_calls: int = 0            # batched prefill executions
    decode_steps: int = 0             # decode program/burst steps
    active_slot_steps: int = 0        # slot-steps that served a request
    slot_refills: int = 0             # slots reused after a finished request
    rejected_requests: int = 0        # structured submit() rejections
    spec_steps: int = 0               # speculative verify bursts
    spec_slot_steps: int = 0          # slot-bursts (active slots x bursts)
    drafted_tokens: int = 0           # draft tokens eligible for acceptance
    accepted_drafts: int = 0          # drafts that matched greedy decode
    committed_tokens: int = 0         # tokens emitted by spec bursts
    prefill_tokens_computed: int = 0  # prompt tokens actually run through
                                      # a prefill program (tail-only under
                                      # prefix sharing)
    prefix_hits: int = 0              # requests that matched >= 1 page
    prefix_misses: int = 0            # requests that matched nothing
    prefix_shared_blocks: int = 0     # blocks joined via refcount bumps
    batch: int = 0

    @property
    def slot_occupancy(self) -> float:
        total = self.decode_steps * max(self.batch, 1)
        return self.active_slot_steps / total if total else 0.0

    @property
    def refill_rate(self) -> float:
        """Fraction of requests admitted by refilling a finished slot
        mid-run rather than by the initial batch fill."""
        return self.slot_refills / self.requests if self.requests else 0.0

    @property
    def accepted_draft_rate(self) -> float:
        """Fraction of eligible draft tokens that matched greedy decode."""
        return (self.accepted_drafts / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    @property
    def tokens_per_burst(self) -> float:
        """Mean tokens committed per slot per verify burst, in [1, 1+k]
        (per SLOT-burst, not per batch step: dividing by bursts alone
        would credit batch width as speculation win)."""
        return (self.committed_tokens / self.spec_slot_steps
                if self.spec_slot_steps else 0.0)


class ServeEngine(ProgramServeBase):
    def __init__(self, arch: ArchConfig, params, eng: EngineConfig,
                 batch_size: int = 4, max_seq: int = 256,
                 calib_batches: Optional[Sequence] = None,
                 calibrator: str = "absmax",
                 granularity: str = "per_tensor",
                 cache: Optional[ProgramCache] = None,
                 cache_capacity: int = 4, scheduled: bool = True,
                 schedule_policy: str = "asap",
                 compile_prefill: bool = True,
                 compile_decode: bool = True,
                 decode_burst: int = 4,
                 prefill_len: Optional[int] = None,
                 mesh=None,
                 kv_layout: str = "dense",
                 page_size: int = 8,
                 kv_blocks: Optional[int] = None,
                 draft_len: int = 0,
                 prefix_sharing: bool = False):
        super().__init__(eng, cache_capacity=cache_capacity,
                         scheduled=scheduled, cache=cache,
                         schedule_policy=schedule_policy, mesh=mesh)
        self.arch = arch
        self.batch, self.max_seq = batch_size, max_seq
        self.decode_burst = max(1, decode_burst)
        self.prefill_len = prefill_len
        self._float_params = params
        self.params = eng_lib.quantize_params(params, eng)
        self.is_audio = arch.family == "audio"
        # mesh= places the param tree tensor-parallel over the "model"
        # axis (whole-head granularity; see serve/mesh_exec.py) -- decode
        # bursts then run their projection GEMMs sharded, bit-identical
        # to single-device
        self.tp_placement = None
        if self.mexec is not None and eng.backend == "pallas":
            # XLA's partitioner cannot split a Mosaic kernel, and the TP
            # plan shards the projection GEMMs' operands
            raise ValueError(
                "LM serving on a mesh (tensor-parallel decode) runs "
                "backend='ref': XLA cannot partition compiled Pallas kernels")
        if self.mexec is not None:
            if self.is_audio:
                self.params = self.mexec.replicate(self.params)
            else:
                self.params, self.tp_placement = \
                    self.mexec.place_lm_params(arch, self.params)
        mod = W if self.is_audio else T
        self.mod = mod
        # Prefill/decode compile through the engine IR when the arch
        # lowers; SSM / MoE / audio archs fall back to the eager path and
        # stats() carries the blockers.
        lowerable = not self.is_audio and compiler.can_lower(arch)
        self.compiled = compile_prefill and lowerable
        self.compiled_decode = compile_decode and lowerable
        # -- block-paged KV cache + speculative decode configuration ------
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got "
                             f"{kv_layout!r}")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        self.draft_len = int(draft_len)
        self.page_size = int(page_size)
        if self.draft_len < 0:
            raise ValueError(f"draft_len must be >= 0, got {draft_len}")
        if self.paged or self.draft_len:
            if not (self.compiled and self.compiled_decode):
                blockers = self.lowering_blockers() or ["compile_* disabled"]
                raise ValueError(
                    "paged KV / speculative decode need the compiled "
                    f"prefill+decode programs ({'; '.join(blockers)})")
            if self.mexec is not None:
                raise ValueError("paged KV / speculative decode are "
                                 "single-device paths (mesh=None)")
        self.alloc: Optional[BlockAllocator] = None
        if self.paged:
            if self.page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            # round max_seq UP to a page multiple: the gathered view is
            # then shape-identical to the dense cache (bit-identity)
            self.max_seq = T.num_pages(self.max_seq,
                                       self.page_size) * self.page_size
            self.kv_pages = T.num_pages(self.max_seq, self.page_size)
            total = (int(kv_blocks) if kv_blocks is not None
                     else batch_size * self.kv_pages)
            self.alloc = BlockAllocator(total)
            # host mirror of cache["tables"]; the POSITIVE sentinel `total`
            # (one past the pool) makes unallocated-page writes drop --
            # negative sentinels would WRAP in a JAX scatter
            self._host_tables = np.full((batch_size, self.kv_pages), total,
                                        np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in
                                                  range(batch_size)]
        # -- prefix sharing (refcounted copy-on-write blocks) --------------
        self.prefix_sharing = bool(prefix_sharing)
        self.prefix_sharing_blockers: List[str] = []
        self.prefix_index: Optional[PrefixIndex] = None
        if self.prefix_sharing:
            if not self.paged:
                raise ValueError("prefix_sharing requires kv_layout='paged' "
                                 "(it shares physical KV pages)")
            if prefill_len is None:
                raise ValueError(
                    "prefix_sharing requires a pinned prefill_len: cached "
                    "KV bits are a function of the PADDED row, so the pad "
                    "width must not depend on queue composition")
            if any(arch.layer_kind(i) == "local"
                   for i in range(arch.n_layers)):
                # documented fallback (not an error): the dense ring KV of
                # local layers has no page boundary to share at, so these
                # archs serve with private whole-prompt prefill
                self.prefix_sharing_blockers.append(
                    "local attention layers (dense ring KV has no page "
                    "boundary)")
                self.prefix_sharing = False
        self._paged_jit = None        # (program, jitted paged prefill+merge)
        self._chunk_jit = None        # (program, jitted chunk prefill)
        self._pool_cache = None       # paged pool persisted across runs
        self._spec_jit = None         # (program, jitted verify+commit step)
        # calibration only feeds the compiled static programs; skip the
        # (whole-param-tree) digest when both paths stay eager.  w4a8
        # shares w8a8's activation calibration (same float graph, same
        # scales) but the digest carries weight_mode so w4 and w8 programs
        # key distinct ProgramCache lines.
        batches = (list(calib_batches)
                   if calib_batches is not None
                   and eng.quant in ("w8a8", "w4a8")
                   and (self.compiled or self.compiled_decode) else None)
        self.calib_batches = batches
        self.calib_id = (calibration_digest(
                             batches, params, calibrator, granularity,
                             weight_mode=eng_lib.weight_mode(eng))
                         if batches is not None else None)
        self.calibrator = calibrator
        self.granularity = granularity
        self._scales = None           # one calibration run, both programs
        if self.prefix_sharing:
            # the index is only valid for KV bits produced under THIS
            # quantization + page geometry, so it carries both as its key
            self.prefix_index = PrefixIndex(self.page_size, self.alloc,
                                            key=(self.calib_id,
                                                 self.page_size))
        self._sched = SlotScheduler(batch_size)
        self.serve_stats = LMServeStats(batch=batch_size)

        def _prefill(params, cache, batch):
            return mod.prefill(params, cache, batch, arch, eng)

        def _decode(params, cache, tokens):
            return mod.decode(params, cache, tokens, arch, eng)

        self.jprefill = jax.jit(_prefill, donate_argnums=(1,))
        self.jdecode = jax.jit(_decode, donate_argnums=(1,))

        def _merge(old, new, mask):
            """Scatter refilled slots' prefill state into the live cache:
            per-slot row select on every [B, ...] leaf, per-slot pos."""
            def sel(o, n):
                m = mask.reshape((mask.shape[0],) + (1,) * (o.ndim - 1))
                return jnp.where(m, n.astype(o.dtype), o)
            layers = jax.tree_util.tree_map(sel, old["layers"],
                                            new["layers"])
            pos = jnp.where(mask, jnp.asarray(new["pos"], jnp.int32),
                            jnp.asarray(old["pos"], jnp.int32))
            return {"layers": layers, "pos": pos}

        self.jmerge = jax.jit(_merge, donate_argnums=(0, 1))

    # -- compiled programs (the unified serve path) --------------------------

    def lowering_blockers(self) -> List[str]:
        """Why this arch's programs fell back to eager ([] = compiled)."""
        if self.is_audio:
            return ["encoder-decoder (audio)"]
        return compiler.lowering_blockers(self.arch)

    def _lm_scales(self):
        """The shared calibration run: one execution of the calibration
        batches quantizes prefill AND decode (graph node ids line up)."""
        if self._scales is None:
            self._scales = compiler.calibrate_lm(
                self.arch, self._float_params, self.calib_batches,
                method=self.calibrator, granularity=self.granularity)
        return self._scales

    def _prefill_key(self):
        return self._program_key(self.arch, self.calib_id, tag="prefill")

    def _decode_key(self):
        # page size and draft length ride the key: paged/dense x draft
        # variants hold DISTINCT ProgramCache lines (and jitted traces --
        # a [B, 1+k] verify trace is not a [B, 1] decode trace)
        tag = ("decode"
               + (f":p{self.page_size}" if self.paged else "")
               + (f":k{self.draft_len}" if self.draft_len else ""))
        return self._program_key(self.arch, self.calib_id, tag=tag)

    def _chunk_key(self):
        # the chunk (prefix-sharing partial-prefill) program variant; page
        # size rides the tag like the decode key's
        return self._program_key(self.arch, self.calib_id,
                                 tag=f"chunk:p{self.page_size}")

    def _compile_mode(self, mode: str) -> ex.Program:
        page = (self.page_size
                if (self.paged and mode in ("decode", "chunk")) else 0)
        if self.calib_batches is None:
            return compiler.compile_lm(self.arch, scheduled=self.scheduled,
                                       policy=self.schedule_policy,
                                       mode=mode, page_size=page)
        return compiler.compile_lm(self.arch, scales=self._lm_scales(),
                                   scheduled=self.scheduled,
                                   policy=self.schedule_policy, mode=mode,
                                   granularity=self.granularity,
                                   page_size=page)

    def prefill_program(self) -> ex.Program:
        """The compiled prefill program: ProgramCache hit, or compile."""
        return self._cached_program(self._prefill_key(),
                                    lambda: self._compile_mode("prefill"))

    def decode_program(self) -> ex.Program:
        """The compiled DecodeStep program: ProgramCache hit, or compile."""
        return self._cached_program(self._decode_key(),
                                    lambda: self._compile_mode("decode"))

    def chunk_program(self) -> ex.Program:
        """The compiled chunk (prefill-tail) program: ProgramCache hit, or
        compile.  Used for EVERY prefill when prefix sharing is on --
        start=0 on an index miss -- so logits are invariant to where the
        page-aligned split falls (see compiler.prefill_from)."""
        return self._cached_program(self._chunk_key(),
                                    lambda: self._compile_mode("chunk"))

    def _run_program_prefill(self, program: ex.Program, params, cache,
                             batch):
        """Execute the prefill program and write the collected per-layer
        (k, v) pairs into the decode cache -- the compiled counterpart of
        `T.prefill` (bit-identical cache layout)."""
        tokens = batch["tokens"]
        kvs: Dict[int, tuple] = {}
        logits = ex.execute(program, params, tokens, self.eng, collect=kvs)
        new_layers = []
        for i in range(self.arch.n_layers):
            entry = cache["layers"][i]
            k, v = kvs[i]
            if self.arch.layer_kind(i) == "local":
                w = entry["k"].shape[1]
                entry = T._kv_store(entry, k[:, -w:], v[:, -w:], 0, self.eng)
            else:
                entry = T._kv_store(entry, k, v, 0, self.eng)
            new_layers.append(entry)
        return logits, {"layers": new_layers,
                        "pos": jnp.asarray(tokens.shape[1], jnp.int32)}

    def _prefill_exec(self):
        """The jitted prefill executable: the eager path, or the cached
        program's (traced once per cached program; stats accrue per call)."""
        if not self.compiled:
            return self.jprefill
        program = self.prefill_program()
        return self._jitted_for(
            self._prefill_key(), program,
            lambda prog: jax.jit(
                functools.partial(self._run_program_prefill, prog),
                donate_argnums=(1,)))

    def _decode_exec(self):
        """The jitted decode-step executable: the compiled DecodeStep
        program from the ProgramCache (jit-once, cache donated), or the
        eager `T.decode` for fallback archs."""
        if not self.compiled_decode:
            return self.jdecode
        program = self.decode_program()
        return self._jitted_for(
            self._decode_key(), program,
            lambda prog: jax.jit(
                lambda params, cache, tokens: ex.execute_decode(
                    prog, params, cache, tokens, self.eng),
                donate_argnums=(1,)))

    def _run_paged_prefill(self, program: ex.Program, params, cache, batch,
                           mask):
        """Execute the prefill program and scatter the refilled slots'
        collected (k, v) spans through the block table into the live paged
        cache -- prefill + merge fused in one jitted step (`mask` [B] gates
        rows; foreign rows' writes drop via the table sentinel)."""
        tokens = batch["tokens"]
        kvs: Dict[int, tuple] = {}
        logits = ex.execute(program, params, tokens, self.eng, collect=kvs)
        sel_mask = mask

        def sel(o, n):
            m = sel_mask.reshape((sel_mask.shape[0],) + (1,) * (o.ndim - 1))
            return jnp.where(m, n.astype(o.dtype), o)

        layers = []
        for i in range(self.arch.n_layers):
            entry = cache["layers"][i]
            k, v = kvs[i]
            if self.arch.layer_kind(i) == "local":
                w = entry["k"].shape[1]
                fresh = jax.tree_util.tree_map(jnp.zeros_like, entry)
                fresh = T._kv_store(fresh, k[:, -w:], v[:, -w:], 0, self.eng)
                entry = jax.tree_util.tree_map(sel, entry, fresh)
            else:
                entry = T._paged_prefill_store(entry, k, v, cache["tables"],
                                               mask, self.eng,
                                               self.page_size)
            layers.append(entry)
        pos = jnp.where(mask, jnp.asarray(tokens.shape[1], jnp.int32),
                        jnp.asarray(cache["pos"], jnp.int32))
        return logits, {"layers": layers, "tables": cache["tables"],
                        "pos": pos}

    def _paged_prefill_exec(self):
        """Jitted paged prefill+merge (traced once per cached program)."""
        program = self.prefill_program()
        if self._paged_jit is None or self._paged_jit[0] is not program:
            fn = jax.jit(functools.partial(self._run_paged_prefill, program),
                         donate_argnums=(1,))
            self._paged_jit = (program, fn)
        return self._paged_jit[1]

    def _chunk_prefill_exec(self):
        """Jitted chunk prefill (one trace per tail width; `start` and the
        per-row match lengths are traced operands, so every width-T wave
        shares one executable regardless of which pages matched)."""
        program = self.chunk_program()
        if self._chunk_jit is None or self._chunk_jit[0] is not program:
            def run(params, cache, tokens, start, row_starts, mask):
                return ex.prefill_from(program, params, cache, tokens,
                                       self.eng, start=start,
                                       row_starts=row_starts, mask=mask)
            self._chunk_jit = (program, jax.jit(run, donate_argnums=(1,)))
        return self._chunk_jit[1]

    def _shared_prefill(self, cache, toks: np.ndarray, mask: np.ndarray,
                        matched: np.ndarray):
        """One admission wave's chunked prefill: run the chunk program on
        the tail past the wave's SHORTEST match.  Rows whose own match
        extends further recompute those positions (bit-identical to the
        shared pages' content; their stores drop below `matched[row]`), so
        one fused wave serves mixed match lengths.  Accounts the tokens
        actually computed."""
        plen = toks.shape[1]
        admitted = matched[mask]
        start = int(admitted.min()) if admitted.size else 0
        tail = toks[:, start:]
        logits, cache = self._chunk_prefill_exec()(
            self.params, cache, jnp.asarray(tail),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(matched, jnp.int32), jnp.asarray(mask))
        self.serve_stats.prefill_tokens_computed += \
            int(mask.sum()) * (plen - start)
        return logits, cache

    def _spec_exec(self):
        """The jitted speculative step: ONE [B, 1+k]-wide verify execution,
        greedy acceptance, masked commit -- a single device round-trip per
        burst, cache donated like the plain decode step."""
        program = self.decode_program()
        if self._spec_jit is None or self._spec_jit[0] is not program:
            def step(params, cache, tokens, cap):
                # tokens [B, W]: column 0 is each slot's current token, the
                # rest are n-gram drafts; cap [B] bounds acceptance (0 for
                # idle slots, so their rows can never commit)
                logits, kvs = ex.execute_verify(program, params, cache,
                                                tokens, self.eng)
                g = jnp.argmax(logits, -1).astype(jnp.int32)   # [B, W]
                match = (tokens[:, 1:] == g[:, :-1]).astype(jnp.int32)
                accept = jnp.minimum(
                    1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1), cap)
                cache = ex.commit_decode_kv(program, cache, kvs, accept,
                                            self.eng)
                idx = jnp.maximum(accept - 1, 0)
                nxt = jnp.take_along_axis(g, idx[:, None], axis=1)[:, 0]
                return accept, nxt, cache
            self._spec_jit = (program, jax.jit(step, donate_argnums=(1,)))
        return self._spec_jit[1]

    # -- request queue / continuous batching ---------------------------------

    def _empty_cache(self):
        if self.is_audio:
            cs = W.whisper_cache_schema(self.arch, self.batch, self.max_seq,
                                        self.eng)
        elif self.paged:
            cs = T.paged_cache_schema(self.arch, self.batch, self.max_seq,
                                      self.eng, self.page_size,
                                      num_blocks=self.alloc.num_blocks)
        else:
            cs = T.cache_schema(self.arch, self.batch, self.max_seq, self.eng)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), cs, is_leaf=is_spec)
        if self.paged:
            cache["tables"] = jnp.asarray(self._host_tables)
        if self.mexec is not None:
            cache = self.mexec.replicate(cache)   # KV cache stays replicated
        return cache

    def _run_cache(self, B: int):
        """The cache a run() starts from.  Plain serving builds a fresh
        zeroed pool per run; prefix sharing must NOT -- the index maps
        token prefixes to block ids whose *contents* live in the pool, so
        the pool persists across runs (stashed at run exit, reclaimed
        here).  If the pool is gone (first run, or a prior run aborted
        mid-donation) any surviving index nodes point at bits that no
        longer exist, so the index resets rather than serve zeros."""
        if self.prefix_sharing:
            if self._pool_cache is not None:
                cache = self._pool_cache
                self._pool_cache = None   # donated into this run's execs
            else:
                if len(self.prefix_index):
                    self.prefix_index.reset()
                cache = self._empty_cache()
        else:
            cache = self._empty_cache()
        cache["pos"] = jnp.zeros((B,), jnp.int32)   # per-slot positions
        return cache

    def submit(self, prompt, max_new_tokens: int = 16):
        """Queue one prompt; returns its ticket (the key of its decoded
        token ids in run()'s results), or a falsy `SubmitRejection` when
        the request cannot be served (over max_seq, or over the paged
        pool's total capacity) -- queue-level backpressure instead of an
        exception, so callers can shed load without try/except."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} (a "
                "0-token request would never own its slot and be dropped)")
        if len(prompt) + max_new_tokens > self.max_seq:
            self.serve_stats.rejected_requests += 1
            return SubmitRejection(
                reason="over_length",
                detail=(f"prompt ({len(prompt)}) + max_new_tokens "
                        f"({max_new_tokens}) exceeds "
                        f"max_seq={self.max_seq}"),
                prompt_len=len(prompt), max_new_tokens=int(max_new_tokens))
        if self.paged:
            need = T.num_pages(len(prompt) + max_new_tokens, self.page_size)
            if need > self.alloc.num_blocks:
                self.serve_stats.rejected_requests += 1
                return SubmitRejection(
                    reason="over_capacity",
                    detail=(f"request needs {need} KV blocks but the pool "
                            f"holds {self.alloc.num_blocks} total"),
                    prompt_len=len(prompt),
                    max_new_tokens=int(max_new_tokens))
        ticket = self._sched.submit(_LM, (prompt, int(max_new_tokens)))
        self.latency.submitted(ticket)
        return ticket

    def pending(self) -> int:
        return self._sched.pending(_LM)

    def _blocks_needed(self, plen: int, mnt: int) -> int:
        """Blocks covering positions [0, padded-prompt + new tokens); the
        dense cache silently drops writes past max_seq, so cap there (the
        paged sentinel reproduces the same drop)."""
        return T.num_pages(min(plen + mnt, self.max_seq), self.page_size)

    def _padded_row(self, prompt: np.ndarray, plen: int) -> np.ndarray:
        """The left-padded token row prefix matching operates on (pad
        tokens are ordinary context, so cached KV bits are a function of
        the PADDED row, not the raw prompt)."""
        row = np.zeros(plen, np.int32)
        row[plen - len(prompt):] = prompt
        return row

    def _max_match_pages(self, plen: int) -> int:
        """Matching leaves the tail at least ONE token: prefill must run a
        non-empty span to emit the last position's logits."""
        return (plen - 1) // self.page_size

    def _fresh_needed(self, prompt: np.ndarray, plen: int, mnt: int) -> int:
        """Blocks this request must ALLOCATE (not share), given the current
        index state: total need minus its matched-prefix pages.  Shared
        pages are accounted once -- joining them costs no free blocks."""
        need = self._blocks_needed(plen, mnt)
        if not self.prefix_sharing or len(prompt) > plen:
            return need           # over-long prompts fail loudly in run()
        row = self._padded_row(np.asarray(prompt, np.int32), plen)
        m = len(self.prefix_index.match(row, self._max_match_pages(plen)))
        return need - m

    def _admit(self, nfree: int, plen: int):
        """FIFO admission: dense takes up to `nfree` queued requests; paged
        additionally gates each on free blocks, head-of-line (no
        reordering -- arrival order is the serving contract), allocating
        the request's blocks and writing its host table row.  Under prefix
        sharing the gate counts only the FRESH blocks a request needs --
        matched pages are shared, not allocated, so a wave of same-prefix
        requests admits where private allocation would backpressure."""
        if not self.paged:
            return self._sched.take(_LM, limit=nfree)
        taken, reserved = [], 0
        while len(taken) < nfree and self._sched.pending(_LM):
            prompt, mnt = self._sched.peek(_LM)[0]
            # gate on free minus what THIS wave already reserved: the
            # actual allocs happen later in _bind_blocks, so probing each
            # request against the raw free count would over-admit.  (The
            # binding's own match can only be LONGER than this probe's --
            # same-wave registrations add nodes, evictions never run
            # mid-wave -- so the reservation is an upper bound.)
            need = self._fresh_needed(prompt, plen, mnt)
            if not self.alloc.can_allocate(reserved + need):
                break                 # backpressure: wait for frees
            reserved += need
            taken.extend(self._sched.take(_LM, limit=1))
        return taken

    def _bind_blocks(self, slot: int, plen: int, mnt: int,
                     row: Optional[np.ndarray] = None) -> int:
        """Bind an admitted request's blocks into its slot's table row
        (host mirror; pushed to device at the admission edge, the only
        point where freed blocks may be reassigned).

        With prefix sharing (`row` = the padded prompt), the longest
        indexed prefix is JOINED -- refcounts bump instead of allocating
        -- and only the remaining pages come from the free list; the
        prompt's full pages are then registered so later arrivals can
        match them (including same-wave ones: the wave's prefill writes
        every admitted row's owned pages before any of them decodes).
        Returns the matched prefix length in tokens (0 without sharing)."""
        need = self._blocks_needed(plen, mnt)
        matched: List[int] = []
        if self.prefix_sharing and row is not None:
            matched = self.prefix_index.match(row,
                                              self._max_match_pages(plen))
            if matched:
                self.alloc.share(matched)
                self.serve_stats.prefix_hits += 1
                self.serve_stats.prefix_shared_blocks += len(matched)
            else:
                self.serve_stats.prefix_misses += 1
        blocks = matched + self.alloc.alloc(need - len(matched))
        self._slot_blocks[slot] = blocks
        trow = np.full(self.kv_pages, self.alloc.num_blocks, np.int32)
        trow[:need] = blocks
        self._host_tables[slot] = trow
        if self.prefix_sharing and row is not None:
            # register only pages FULLY covered by the prompt: a partial
            # last page is decode-writable, so it stays request-private
            self.prefix_index.register(row, blocks,
                                       plen // self.page_size)
        return len(matched) * self.page_size

    def _ensure_admissible(self, plen: int) -> None:
        """Called when the queue is non-empty but no slot is active and
        admission produced nothing.  Without sharing that means the pool
        itself is too small (nothing in flight will ever free blocks), so
        raise.  With sharing the prefix index may be what is holding
        blocks: this is the quiescent point -- no slot owns a table row --
        so leaf index nodes can be evicted without invalidating any bound
        table, and admission retries after eviction."""
        if not self.paged:
            return
        prompt, mnt = self._sched.peek(_LM)[0]
        need = self._blocks_needed(plen, mnt)
        if self.prefix_sharing:
            fresh = need
            protected: set = set()
            if len(prompt) <= plen:
                row = self._padded_row(np.asarray(prompt, np.int32), plen)
                mblocks = self.prefix_index.match(
                    row, self._max_match_pages(plen))
                fresh = need - len(mblocks)
                protected = set(mblocks)
            self.prefix_index.evict_for(fresh, protected=protected)
            if self.alloc.free_blocks >= fresh:
                return                # admission will succeed next pass
            raise RuntimeError(
                f"queued request needs {fresh} fresh KV blocks beyond its "
                f"shared prefix but only {self.alloc.free_blocks} of "
                f"{self.alloc.num_blocks} are free after evicting unshared "
                "prefixes; raise kv_blocks or shrink the request")
        if self.alloc.in_use == 0:
            raise RuntimeError(
                f"queued request needs {need} KV blocks "
                f"but the pool holds {self.alloc.num_blocks} "
                "total; raise kv_blocks or shrink the request")

    def _release_blocks(self, slot: int) -> None:
        """Response edge: return the slot's blocks and clear its row to the
        drop sentinel (the dead slot's in-flight writes then land nowhere,
        so a freed block reassigned at the NEXT admission edge -- after the
        cleared row is pushed -- can never be corrupted)."""
        self.alloc.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._host_tables[slot] = self.alloc.num_blocks

    def run(self) -> Dict[int, np.ndarray]:
        """Serve the queue to completion with continuous batching: prefill
        fills free slots, decode bursts advance every slot one token per
        step, and finished slots refill from the queue between bursts.
        Returns {ticket: greedy token ids}.

        Every prompt left-pads to ONE prefill width (`prefill_len`, or the
        longest queued prompt when unset); pad tokens are ordinary context
        (no pad masking, like the legacy wave path), so a request's output
        is a function of its padded row alone.  With `prefill_len` set the
        row -- and therefore the output -- is independent of arrival order
        and batch composition (the order-invariance property test); with
        it unset, prompts shorter than the queue's max see a
        queue-dependent pad width, exactly as the per-wave padding before
        them did.

        Dispatch is ASYNC with response-edge sync: decode bursts keep the
        emitted token columns as device arrays in flight (one [B, burst]
        block per burst, no per-step host readback), and the host
        materializes a block only at a response edge -- when some slot's
        request completes at the end of a burst.  Blocks every live slot
        has consumed are dropped, so in-flight device memory stays bounded
        by the longest active request.

        With `draft_len` set, the burst loop is the speculative variant
        (`_run_speculative`): host-synced per burst (the n-gram drafter
        needs emitted ids), one verify+commit device step per burst."""
        if self.draft_len:
            return self._run_speculative()
        results: Dict[int, np.ndarray] = {}
        sched, B = self._sched, self.batch
        if not sched.pending(_LM):
            return results
        plen = self.prefill_len
        if plen is None:
            plen = max(len(p) for p, _ in sched.peek(_LM))
        prefill_exec = (self._paged_prefill_exec() if self.paged
                        else self._prefill_exec())
        decode_exec = self._decode_exec()

        cache = self._run_cache(B)
        cur = jnp.zeros((B, 1), jnp.int32)
        tickets: List[Optional[int]] = [None] * B
        remaining = np.zeros(B, np.int64)
        start = np.zeros(B, np.int64)     # slot's first global step
        step = 0                          # global decode-step counter
        blocks: List[List] = []           # [start step, [B, w] device toks]
        block_np: Dict[int, np.ndarray] = {}   # id(block) -> host tokens

        def tokens_for(slot: int, lo: int, hi: int) -> np.ndarray:
            """Materialize steps [lo, hi) of one slot from the in-flight
            blocks -- the response edge's only host sync."""
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

        while True:
            free = [i for i in range(B) if remaining[i] == 0]
            if free and sched.pending(_LM):
                taken = self._admit(len(free), plen)
                if taken:
                    toks = np.zeros((B, plen), np.int32)
                    mask = np.zeros(B, bool)
                    matched = np.full(B, plen, np.int32)
                    for slot, (ticket, (prompt, mnt)) in zip(free, taken):
                        if len(prompt) > plen:
                            raise ValueError(
                                f"prompt of length {len(prompt)} exceeds the "
                                f"run's fixed prefill width {plen} (set "
                                f"prefill_len at construction)")
                        toks[slot, plen - len(prompt):] = prompt
                        mask[slot] = True
                        if tickets[slot] is not None:
                            self.serve_stats.slot_refills += 1
                        tickets[slot] = ticket
                        remaining[slot] = mnt
                        start[slot] = step
                        if self.paged:
                            matched[slot] = self._bind_blocks(
                                slot, plen, mnt,
                                row=(toks[slot] if self.prefix_sharing
                                     else None))
                    jmask = jnp.asarray(mask)
                    # batched prefill of the refill slots only; foreign rows
                    # compute garbage that the masked merge throws away
                    if self.paged:
                        # admission edge: push the host table (new rows AND
                        # rows cleared at response edges) before any writes
                        cache["tables"] = jnp.asarray(self._host_tables)
                        if self.prefix_sharing:
                            logits, cache = self._shared_prefill(
                                cache, toks, mask, matched)
                        else:
                            logits, cache = prefill_exec(
                                self.params, cache,
                                {"tokens": jnp.asarray(toks)}, jmask)
                            self.serve_stats.prefill_tokens_computed += (
                                len(taken) * plen)
                    else:
                        logits, fresh = prefill_exec(
                            self.params, self._empty_cache(),
                            {"tokens": jnp.asarray(toks)})
                        cache = self.jmerge(cache, fresh, jmask)
                        self.serve_stats.prefill_tokens_computed += (
                            len(taken) * plen)
                    first = jnp.argmax(logits[:, -1, :], axis=-1)
                    cur = jnp.where(jmask[:, None], first[:, None], cur
                                    ).astype(jnp.int32)
                    self.serve_stats.prefill_calls += 1
                    self.serve_stats.requests += len(taken)
                    sched.next_epoch()

            act = [i for i in range(B) if remaining[i] > 0]
            if not act:
                if sched.pending(_LM):
                    self._ensure_admissible(plen)
                    continue
                break
            burst = int(min(self.decode_burst,
                            min(remaining[i] for i in act)))
            cols = []
            for _ in range(burst):
                cols.append(cur)          # emitted token, still on device
                logits, cache = decode_exec(self.params, cache, cur)
                cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None
                                                            ].astype(jnp.int32)
                self.serve_stats.decode_steps += 1
                self.serve_stats.active_slot_steps += len(act)
            blocks.append([step, cols[0] if burst == 1
                           else jnp.concatenate(cols, axis=1)])
            step += burst
            finished = False
            for i in act:
                remaining[i] -= burst
                if remaining[i] == 0:     # response edge for this ticket
                    results[tickets[i]] = tokens_for(i, int(start[i]), step)
                    self.latency.completed(tickets[i])
                    if self.paged:
                        self._release_blocks(i)
                    finished = True
            if finished:
                # drop blocks every live slot is past (bounded in-flight)
                live = [int(start[i]) for i in range(B) if remaining[i] > 0]
                lo = min(live) if live else step
                keep = [b for b in blocks if b[0] + b[1].shape[1] > lo]
                kept_ids = {id(b[1]) for b in keep}
                for b in blocks:
                    if id(b[1]) not in kept_ids:
                        block_np.pop(id(b[1]), None)
                blocks = keep
        if self.prefix_sharing:
            self._pool_cache = cache   # warm prefix bits survive the run
        return results

    @staticmethod
    def _ngram_draft(hist: List[int], k: int, max_n: int = 3) -> List[int]:
        """Self-speculative n-gram proposal: k draft tokens continuing
        `hist` (prompt + emitted ids, most recent last).  Matches the
        longest suffix n-gram (n <= max_n) against earlier history and
        copies what followed its most recent occurrence; with no match it
        repeats the last token.  Pure host-side -- no second model, no
        device work; a wrong draft only costs its share of the burst."""
        seq = list(hist)
        for _ in range(k):
            nxt = None
            for n in range(min(max_n, len(seq) - 1), 0, -1):
                suf = seq[-n:]
                for j in range(len(seq) - n - 1, -1, -1):
                    if seq[j:j + n] == suf:
                        nxt = seq[j + n]
                        break
                if nxt is not None:
                    break
            seq.append(seq[-1] if nxt is None else nxt)
        return seq[-k:]

    def _run_speculative(self) -> Dict[int, np.ndarray]:
        """Speculative continuous batching: each burst teacher-forces the
        current token plus `draft_len` n-gram drafts through ONE [B, 1+k]
        verify step, commits the longest greedy-consistent prefix, and
        rolls the rest back for free (rejected drafts never touched the
        cache).  Emitted ids are token-for-token identical to the greedy
        one-token loop; each burst commits 1..1+k tokens.  Host-synced per
        burst: the drafter consumes emitted ids (that sync replaces run()'s
        async block machinery)."""
        results: Dict[int, np.ndarray] = {}
        sched, B, W = self._sched, self.batch, 1 + self.draft_len
        if not sched.pending(_LM):
            return results
        plen = self.prefill_len
        if plen is None:
            plen = max(len(p) for p, _ in sched.peek(_LM))
        prefill_exec = (self._paged_prefill_exec() if self.paged
                        else self._prefill_exec())
        spec_exec = self._spec_exec()

        cache = self._run_cache(B)
        cur = np.zeros(B, np.int32)
        tickets: List[Optional[int]] = [None] * B
        remaining = np.zeros(B, np.int64)
        hist: List[List[int]] = [[] for _ in range(B)]  # prompt + emitted
        out: List[List[int]] = [[] for _ in range(B)]

        while True:
            free = [i for i in range(B) if remaining[i] == 0]
            if free and sched.pending(_LM):
                taken = self._admit(len(free), plen)
                if taken:
                    toks = np.zeros((B, plen), np.int32)
                    mask = np.zeros(B, bool)
                    matched = np.full(B, plen, np.int32)
                    for slot, (ticket, (prompt, mnt)) in zip(free, taken):
                        if len(prompt) > plen:
                            raise ValueError(
                                f"prompt of length {len(prompt)} exceeds "
                                f"the run's fixed prefill width {plen} "
                                "(set prefill_len at construction)")
                        toks[slot, plen - len(prompt):] = prompt
                        mask[slot] = True
                        if tickets[slot] is not None:
                            self.serve_stats.slot_refills += 1
                        tickets[slot] = ticket
                        remaining[slot] = mnt
                        hist[slot] = [int(t) for t in prompt]
                        out[slot] = []
                        if self.paged:
                            matched[slot] = self._bind_blocks(
                                slot, plen, mnt,
                                row=(toks[slot] if self.prefix_sharing
                                     else None))
                    jmask = jnp.asarray(mask)
                    if self.paged:
                        cache["tables"] = jnp.asarray(self._host_tables)
                        if self.prefix_sharing:
                            logits, cache = self._shared_prefill(
                                cache, toks, mask, matched)
                        else:
                            logits, cache = prefill_exec(
                                self.params, cache,
                                {"tokens": jnp.asarray(toks)}, jmask)
                            self.serve_stats.prefill_tokens_computed += (
                                len(taken) * plen)
                    else:
                        logits, fresh = prefill_exec(
                            self.params, self._empty_cache(),
                            {"tokens": jnp.asarray(toks)})
                        cache = self.jmerge(cache, fresh, jmask)
                        self.serve_stats.prefill_tokens_computed += (
                            len(taken) * plen)
                    first = np.asarray(jnp.argmax(logits[:, -1, :], -1))
                    for slot in free[:len(taken)]:
                        cur[slot] = first[slot]
                    self.serve_stats.prefill_calls += 1
                    self.serve_stats.requests += len(taken)
                    sched.next_epoch()

            act = [i for i in range(B) if remaining[i] > 0]
            if not act:
                if sched.pending(_LM):
                    self._ensure_admissible(plen)
                    continue
                break

            tok = np.zeros((B, W), np.int32)
            cap = np.zeros(B, np.int32)
            for i in act:
                tok[i, 0] = cur[i]
                if W > 1:
                    tok[i, 1:] = self._ngram_draft(hist[i] + [int(cur[i])],
                                                   W - 1)
                cap[i] = min(int(remaining[i]), W)
            accept, nxt, cache = spec_exec(self.params, cache,
                                           jnp.asarray(tok),
                                           jnp.asarray(cap))
            accept, nxt = np.asarray(accept), np.asarray(nxt)
            self.serve_stats.decode_steps += 1
            self.serve_stats.spec_steps += 1
            self.serve_stats.spec_slot_steps += len(act)
            self.serve_stats.active_slot_steps += len(act)
            for i in act:
                a = int(accept[i])
                emitted = tok[i, :a].tolist()
                out[i].extend(emitted)
                hist[i].extend(emitted)
                cur[i] = nxt[i]
                remaining[i] -= a
                self.serve_stats.committed_tokens += a
                self.serve_stats.drafted_tokens += int(cap[i]) - 1
                self.serve_stats.accepted_drafts += a - 1
                if remaining[i] == 0:     # response edge
                    results[tickets[i]] = np.asarray(out[i], np.int32)
                    self.latency.completed(tickets[i])
                    if self.paged:
                        self._release_blocks(i)
        if self.prefix_sharing:
            self._pool_cache = cache   # warm prefix bits survive the run
        return results

    # -- generation ----------------------------------------------------------

    def generate(self, prompts: Sequence[np.ndarray], max_new_tokens: int = 16,
                 enc_embeds: Optional[np.ndarray] = None) -> List[np.ndarray]:
        """Greedy generation for a batch of equal-priority requests, in
        submission order -- submit() + run() over the continuous scheduler.
        Audio (encoder-decoder) archs serve on the legacy wave path."""
        if self.is_audio or enc_embeds is not None:
            return self._generate_waves(prompts, max_new_tokens, enc_embeds)
        tickets = [self.submit(p, max_new_tokens) for p in prompts]
        rejected = [t for t in tickets if isinstance(t, SubmitRejection)]
        if rejected:
            raise ValueError(f"{len(rejected)} of {len(prompts)} prompts "
                             f"rejected: {rejected[0].detail}")
        results = self.run()
        return [results[t] for t in tickets]

    def _generate_waves(self, prompts, max_new_tokens, enc_embeds):
        """Fixed waves of `batch` requests (the audio fallback path)."""
        out: List[np.ndarray] = []
        for start in range(0, len(prompts), self.batch):
            wave = list(prompts[start:start + self.batch])
            n = len(wave)
            plen = max(len(p) for p in wave)
            toks = np.zeros((self.batch, plen), np.int32)
            for i, p in enumerate(wave):
                toks[i, plen - len(p):] = p      # left-pad into the batch
            cache = self._empty_cache()
            batch = {"tokens": jnp.asarray(toks)}
            if self.is_audio:
                ee = (enc_embeds if enc_embeds is not None else
                      np.zeros((self.batch, self.arch.encoder_seq,
                                self.arch.d_model), np.float32))
                batch["enc_embeds"] = jnp.asarray(ee[:self.batch])
            logits, cache = self.jprefill(self.params, cache, batch)
            seqs = [[] for _ in range(n)]
            cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            for step in range(max_new_tokens):
                for i in range(n):
                    seqs[i].append(int(cur[i, 0]))
                logits, cache = self.jdecode(self.params, cache, cur)
                cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            out.extend(np.asarray(s, np.int32) for s in seqs)
        return out

    # -- stats ---------------------------------------------------------------

    def _kv_memory(self) -> Dict[str, float]:
        """Measured KV-cache footprint: total bytes of global-layer KV
        state, and bytes a single request actually occupies (dense: the
        worst-case max_seq envelope every slot reserves; paged: mean blocks
        held per admitted request)."""
        itm = 1 if self.eng.kv_cache_dtype == "int8" else 2
        nkv, hd = self.arch.n_kv_heads, self.arch.head_dim
        per_pos = 2 * nkv * hd * itm      # k + v
        if self.eng.kv_cache_dtype == "int8":
            per_pos += 2 * nkv * 4        # k_scale + v_scale
        n_glb = sum(1 for i in range(self.arch.n_layers)
                    if self.arch.layer_kind(i) not in
                    ("local", "mamba", "recurrent"))
        if self.paged:
            block_bytes = self.page_size * per_pos * n_glb
            st = self.alloc.stats
            per_slot = (block_bytes * st.blocks_served / st.allocs
                        if st.allocs else float(block_bytes * self.kv_pages))
            return {"kv_bytes": float(block_bytes * self.alloc.num_blocks),
                    "kv_bytes_per_slot": per_slot,
                    "kv_block_bytes": float(block_bytes)}
        per_slot = float(self.max_seq * per_pos * n_glb)
        return {"kv_bytes": per_slot * self.batch,
                "kv_bytes_per_slot": per_slot}

    def stats(self) -> Dict[str, object]:
        out = {"arch": self.arch.name,
               "compiled_prefill": self.compiled,
               "compiled_decode": self.compiled_decode,
               "schedule_policy": self.schedule_policy,
               "kv_layout": self.kv_layout,
               "draft_len": self.draft_len,
               # the eager-fallback gate, made loud: WHY an arch fell back
               "lowering_blockers": self.lowering_blockers()}
        out.update(self.cache_stats())
        s = self.serve_stats
        out.update({
            "requests": s.requests,
            "prefill_calls": s.prefill_calls,
            "decode_steps": s.decode_steps,
            "slot_refills": s.slot_refills,
            "slot_refill_rate": s.refill_rate,
            "slot_occupancy": s.slot_occupancy,
            "rejected_requests": s.rejected_requests,
            "prefill_tokens_computed": s.prefill_tokens_computed,
            "latency_ms": self.latency.percentiles(),
        })
        out.update(self._kv_memory())
        if self.paged:
            out["page_size"] = self.page_size
            out["kv_blocks"] = self.alloc.describe()
        if self.prefix_sharing or self.prefix_sharing_blockers:
            ps = {"enabled": self.prefix_sharing,
                  "blockers": list(self.prefix_sharing_blockers)}
            if self.prefix_index is not None:
                ps.update({
                    "hits": s.prefix_hits,
                    "misses": s.prefix_misses,
                    "shared_blocks": s.prefix_shared_blocks,
                    "evictions": self.prefix_index.evictions,
                    "index_nodes": len(self.prefix_index),
                    "held_only": self.prefix_index.held_only(),
                })
            out["prefix_sharing"] = ps
        if self.draft_len:
            out.update({
                "spec_steps": s.spec_steps,
                "accepted_draft_rate": s.accepted_draft_rate,
                "tokens_per_burst": s.tokens_per_burst,
            })
        if self.mexec is not None:
            out["mesh"] = self.mexec.describe()
            if self.tp_placement is not None:
                out["tp_placement"] = self.tp_placement
        for tag, key in (("prefill", self._prefill_key()),
                         ("decode", self._decode_key())):
            program = self.cache.peek(key)
            if program is not None and program.schedule is not None:
                out[f"{tag}_levels"] = program.schedule.n_levels
                occ = compiler.engine_occupancy(program.graph,
                                                program.schedule)
                out[f"{tag}_occupancy"] = occ["occupancy"]
        return out


def throughput_probe(engine: ServeEngine, steps: int = 8) -> dict:
    """Tokens/s of the decode loop (CPU wall-clock; relative numbers only)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, engine.arch.vocab_size, size=8)
               for _ in range(engine.batch)]
    engine.generate(prompts, max_new_tokens=1)     # compile outside the clock
    t0 = time.perf_counter()
    engine.generate(prompts, max_new_tokens=steps)
    dt = time.perf_counter() - t0
    return {"tokens_per_s": engine.batch * steps / dt, "wall_s": dt}
