"""Sharding machinery: logical-axis resolution, ZeRO-1 specs, and a
subprocess mini dry-run on a fake multi-device mesh."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.models import params as prm
from repro.models import transformer as T


class FakeMesh:
    """Shape-only stand-in (tests run on 1 device)."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
POD = FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestResolve:
    def test_divisible(self):
        assert prm.resolve_pspec(MESH, (4096, 14336), ("fsdp", "tp")) == \
            P("data", "model")

    def test_non_divisible_drops(self):
        # 8 kv heads * 128 = 1024 divisible; 49155 vocab is not.
        assert prm.resolve_pspec(MESH, (49155, 1024), ("tp", None)) == P()

    def test_dp_composes_pods(self):
        assert prm.resolve_pspec(POD, (256, 4096), ("dp", None)) == \
            P(("pod", "data"))

    def test_axis_used_once(self):
        spec = prm.resolve_pspec(MESH, (256, 256), ("tp", "tp"))
        assert spec == P("model")      # second use dropped

    def test_all_arch_param_specs_resolve(self):
        """Every param of every arch gets a valid spec on both meshes."""
        for name in configs.list_archs():
            arch = configs.get_arch(name)
            if arch.family == "audio":
                from repro.models import whisper as W
                schema = W.whisper_schema(arch)
            else:
                schema = T.lm_schema(arch)
            for mesh in (MESH, POD):
                tree = prm.pspec_tree(schema, mesh)
                leaves = jax.tree_util.tree_leaves(
                    tree, is_leaf=lambda x: isinstance(x, P))
                assert all(isinstance(l, P) for l in leaves), name

    def test_tp_coverage(self):
        """The big matrices must actually shard over the model axis."""
        arch = configs.get_arch("granite-8b")
        schema = T.lm_schema(arch)
        specs = prm.pspec_tree(schema, MESH)
        blk = specs["blocks"][0]
        assert "model" in tuple(blk["attn"]["wq"])
        assert "model" in tuple(blk["mlp"]["wu"])


class TestZero1:
    def test_adds_data_axis(self):
        from repro.train.optim import zero1_pspec

        class M:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        sp = zero1_pspec(P(None, "model"), (4096, 14336), M())
        assert sp == P("data", "model")

    def test_respects_existing_fsdp(self):
        from repro.train.optim import zero1_pspec

        class M:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        sp = zero1_pspec(P("data", "model"), (4096, 14336), M())
        assert sp == P("data", "model")

    def test_non_divisible_stays(self):
        from repro.train.optim import zero1_pspec

        class M:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        assert zero1_pspec(P(), (3,), M()) == P()


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.core.config import ShapeConfig
from repro.core import engine as eng_lib
from repro.launch import build as B
from repro.launch import mesh as mesh_lib
mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
arch = configs.reduced(configs.get_arch("gemma2-2b"))
arch = dataclasses.replace(arch, vocab_size=256)
import repro.core.config as cc
cc.SHAPES["mini"] = ShapeConfig("mini", 64, 8, "train")
prog = B.build(arch.name, "mini", mesh, arch=arch)
lowered = prog.fn.lower(*prog.args)
compiled = lowered.compile()
txt = compiled.as_text()
assert any(k in txt for k in ("all-reduce", "all-gather", "reduce-scatter")), \
    "expected collectives in the partitioned module"
cost = compiled.cost_analysis()
if isinstance(cost, (list, tuple)):        # older jax returns [dict]
    cost = cost[0] if cost else {}
print("MINI_DRYRUN_OK", cost.get("flops", 0.0) > 0)
"""


def test_mini_dryrun_multidevice():
    """End-to-end: production builder lowers+compiles on a fake 8-device
    mesh and the partitioned module contains collectives."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MINI_DRYRUN_OK True" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Serving-mesh placement (serve/mesh_exec.py): topology keys, slot pools,
# the whole-head TP rule, and sharded-vs-single-device parity
# ---------------------------------------------------------------------------


class TestMeshTopologyKeys:
    def test_program_key_mesh_disambiguates(self):
        from repro.core.program_cache import ProgramKey
        from repro.serve.mesh_exec import MeshTopology

        t2 = MeshTopology((("data", 2), ("model", 1)))
        t8 = MeshTopology((("data", 8), ("model", 1)))
        k2 = ProgramKey("m", "e", None, "scheduled", mesh=t2)
        k8 = ProgramKey("m", "e", None, "scheduled", mesh=t8)
        assert k2 != k8 and hash(k2) != hash(k8)
        assert {k2: 1, k8: 2}[k2] == 1

    def test_program_key_default_mesh_back_compat(self):
        """Old positional constructions (no mesh) still work and equal the
        explicit-None form -- single-device cache keys are unchanged."""
        from repro.core.program_cache import ProgramKey

        assert ProgramKey("m", "e", None, "v") == \
            ProgramKey("m", "e", None, "v", mesh=None)

    def test_topology_descriptor(self):
        from repro.serve.mesh_exec import MeshTopology

        t = MeshTopology((("data", 8), ("model", 1)))
        assert t.devices == 8 and t.size("data") == 8
        assert t.size("missing") == 1
        assert str(t) == "mesh[8x1;data,model]"


class TestSlotPools:
    def _sched(self, slots=2, pools=2):
        from repro.serve.base import SlotScheduler
        return SlotScheduler(slots, pools=pools)

    def test_wave_slots_scales_with_pools(self):
        assert self._sched(slots=2, pools=4).wave_slots == 8
        assert self._sched(slots=3, pools=1).wave_slots == 3

    def test_locality_packing_sticky_home_pools(self):
        """Two affinity keys on a 2-pool scheduler: each gets a sticky
        home pool and a full wave places every request at home."""
        s = self._sched(slots=2, pools=2)
        for i in range(2):
            s.submit("g", ("a", i), affinity="a")
        for i in range(2):
            s.submit("g", ("b", i), affinity="b")
        wave = s.take_wave("g")
        assert len(wave) == 4
        payloads = [p for _, p in wave]
        # pool 0 = rows [0,2) -> model "a" (first seen), pool 1 -> "b"
        assert {p[0] for p in payloads[:2]} == {"a"}
        assert {p[0] for p in payloads[2:]} == {"b"}
        assert s.stats.locality_hits == 4 and s.stats.locality_misses == 0
        assert s.stats.locality_rate == 1.0

    def test_locality_spill_counts_misses(self):
        """One affinity overflowing its home pool spills to the other pool
        and the spilled rows count as misses."""
        s = self._sched(slots=2, pools=2)
        for i in range(4):
            s.submit("g", ("a", i), affinity="a")
        wave = s.take_wave("g")
        assert len(wave) == 4
        assert s.stats.locality_hits == 2 and s.stats.locality_misses == 2

    def test_partial_wave_queues_until_forced(self):
        s = self._sched(slots=2, pools=2)
        s.submit("g", "x", affinity="a")
        assert s.take_wave("g") is None
        assert len(s.take_wave("g", force=True)) == 1


class TestWholeHeadTPRule:
    """tp_shardable / lm_tp_pspec on shape-only meshes: attention
    projections shard only at whole-head granularity; MLP + embeddings
    shard whenever divisible; norms/biases replicate."""

    def _arch(self, name):
        return configs.reduced(configs.get_arch(name))

    def test_kv_replicates_when_heads_dont_divide(self):
        # qwen2 reduced: 4 q heads, 1 kv head.  tp=4 divides q, not kv.
        from repro.serve import mesh_exec as mx

        arch = self._arch("qwen2-1.5b")
        mesh = FakeMesh({"data": 2, "model": 4})
        d, hd = arch.d_model, arch.head_dim
        assert mx.lm_tp_pspec("wq", (d, arch.n_heads * hd), arch, mesh) == \
            P(None, "model")
        assert mx.lm_tp_pspec("wk", (d, arch.n_kv_heads * hd), arch, mesh) \
            == P()
        assert mx.lm_tp_pspec("wo", (arch.n_heads * hd, d), arch, mesh) == \
            P("model")                 # trailing None trimmed

    def test_kv_shards_at_whole_head_granularity(self):
        # gemma2 reduced: 2 kv heads.  tp=2 splits BETWEEN them -- exact.
        from repro.serve import mesh_exec as mx

        arch = self._arch("gemma2-2b")
        mesh = FakeMesh({"data": 4, "model": 2})
        d, hd = arch.d_model, arch.head_dim
        assert mx.tp_shardable("wk", arch, 2)
        assert mx.lm_tp_pspec("wk", (d, arch.n_kv_heads * hd), arch, mesh) \
            == P(None, "model")
        assert not mx.tp_shardable("wk", arch, 4)   # intra-head: refuse

    def test_mlp_and_vocab_shard_norms_replicate(self):
        from repro.serve import mesh_exec as mx

        arch = self._arch("qwen2-1.5b")
        mesh = FakeMesh({"data": 2, "model": 4})
        assert mx.lm_tp_pspec("wu", (arch.d_model, arch.d_ff), arch, mesh) \
            == P(None, "model")
        assert mx.lm_tp_pspec("wd", (arch.d_ff, arch.d_model), arch, mesh) \
            == P("model")
        assert mx.lm_tp_pspec("embed", (arch.vocab_size, arch.d_model),
                              arch, mesh) == P("model")
        assert mx.lm_tp_pspec("norm", (arch.d_model,), arch, mesh) == P()

    def test_tp1_replicates_everything(self):
        from repro.serve import mesh_exec as mx

        arch = self._arch("qwen2-1.5b")
        for name in ("wq", "wk", "wu", "embed"):
            assert not mx.tp_shardable(name, arch, 1)


class TestCompiledPallasOnMesh:
    def test_lm_engine_refuses_compiled_pallas_on_a_mesh(self):
        """XLA cannot partition a Mosaic kernel, so an LM engine on a mesh
        with the Pallas backend is refused up front with the reason, on
        every platform, before any weight is placed."""
        from repro.core.config import EngineConfig
        from repro.serve.engine import ServeEngine
        from repro.serve.mesh_exec import make_serve_mesh

        arch = configs.reduced(configs.get_arch("qwen2-1.5b"))
        params = prm.init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="cannot partition"):
            ServeEngine(arch, params,
                        EngineConfig(quant="w8a8", backend="pallas"),
                        batch_size=2, max_seq=32,
                        mesh=make_serve_mesh(n_data=1))


class TestLatencyTracker:
    def test_percentiles_over_completions(self):
        from repro.serve.base import LatencyTracker

        lt = LatencyTracker()
        for t in range(4):
            lt.submitted(t)
        for t in range(3):
            lt.completed(t)
        p = lt.percentiles()
        assert p["n"] == 3 and p["p99_ms"] >= p["p50_ms"] >= 0.0

    def test_unknown_ticket_is_noop(self):
        from repro.serve.base import LatencyTracker

        lt = LatencyTracker()
        lt.completed(99)
        assert lt.percentiles()["n"] == 0

    def test_window_stays_bounded(self):
        from repro.serve.base import LatencyTracker

        lt = LatencyTracker()
        n = lt.WINDOW + 10
        for t in range(n):
            lt.submitted(t)
            lt.completed(t)
        assert len(lt.samples_ms) == lt.WINDOW
        assert lt.percentiles()["n"] == lt.WINDOW


CNN_WAVE_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import engine as eng_lib
from repro.models import cnn
from repro.models.params import init_params
from repro.serve.cnn_engine import CNNServeEngine
from repro.serve.mesh_exec import make_serve_mesh

mesh = make_serve_mesh(n_data=8)
rng = np.random.default_rng(0)
# zoo-wide: a mesh-attached engine (8 pools x 2 slots = 16-row sharded
# waves) must reproduce a plain 2-slot engine's bits on the same requests
for i, (name, base) in enumerate(sorted(CNN_ZOO.items())):
    cfg = dataclasses.replace(base, input_hw=32)
    params = init_params(cnn.cnn_schema(cfg), jax.random.PRNGKey(i))
    calib = jnp.asarray(rng.normal(
        size=(2, 32, 32, cfg.input_ch)).astype(np.float32))
    imgs = rng.normal(size=(16, 32, 32, cfg.input_ch)).astype(np.float32)
    plain = CNNServeEngine(eng_lib.paper_engine(), wave_size=2)
    plain.register(cfg, params, calib_batches=[calib])
    meshed = CNNServeEngine(eng_lib.paper_engine(), wave_size=2, mesh=mesh)
    meshed.register(cfg, params, calib_batches=[calib])
    assert meshed.wave_rows == 16 and plain.wave_rows == 2
    a = plain.infer(cfg.name, imgs)
    b = meshed.infer(cfg.name, imgs)
    assert np.array_equal(a, b), name
    st = meshed.stats()
    assert st["mesh"]["devices"] == 8, name
    print("WAVE_PARITY_OK", name)
"""


def test_cnn_sharded_wave_parity_zoo():
    """Property, zoo-wide on 8 forced host devices: a mesh-attached engine
    serving 16-row waves sharded over the data axis (2 rows per device)
    emits BIT-IDENTICAL logits to a single-device 2-slot engine on the
    same requests.  Matching per-device row counts is what makes the
    executables bit-compatible: the int8 GEMMs accumulate in int32
    (order-free) and XLA tiles the float epilogues identically when the
    local batch matches."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", CNN_WAVE_PARITY], env=env,
                         capture_output=True, text=True, timeout=600)
    from repro.configs.cnn_zoo import CNN_ZOO
    for name in CNN_ZOO:
        assert f"WAVE_PARITY_OK {name}" in out.stdout, \
            name + "\n" + out.stdout + out.stderr


LM_TP_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro import configs
from repro.core.config import EngineConfig
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import ServeEngine
from repro.serve.mesh_exec import make_serve_mesh

w8 = EngineConfig(quant="w8a8", backend="ref")
rng = np.random.default_rng(0)
# (arch, data x model): qwen2 tp=4 keeps its 1 kv head replicated;
# gemma2 tp=2 shards its 2 kv heads whole -- both must stay exact
for name, (nd, nm) in (("qwen2-1.5b", (2, 4)), ("gemma2-2b", (4, 2))):
    arch = configs.reduced(configs.get_arch(name))
    params = init_params(T.lm_schema(arch), jax.random.PRNGKey(0))
    calib = [jnp.array(rng.integers(0, arch.vocab_size,
                                    (2, 8)).astype(np.int32))]
    prompts = [rng.integers(0, arch.vocab_size, size=6).astype(np.int32)
               for _ in range(2)]
    def serve(mesh):
        se = ServeEngine(arch, params, w8, batch_size=2, max_seq=32,
                         calib_batches=calib, prefill_len=8, mesh=mesh)
        return se.generate(prompts, max_new_tokens=6), se.stats()
    base, _ = serve(None)
    tp, st = serve(make_serve_mesh(n_data=nd, n_model=nm))
    for a, b in zip(base, tp):
        assert np.array_equal(a, b), (name, a, b)
    assert st["tp_placement"]["tp_sharded"] > 0, name
    print("LM_TP_OK", name, st["tp_placement"]["tp_sharded"])
"""


def test_lm_tp_decode_parity():
    """Property, on 8 forced host devices: tensor-parallel decode bursts
    (whole-head sharding over the model axis) emit bit-identical token
    ids to single-device serving, for a kv-replicated arch (qwen2, tp=4)
    and a kv-sharded arch (gemma2, tp=2)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", LM_TP_PARITY], env=env,
                         capture_output=True, text=True, timeout=600)
    for name in ("qwen2-1.5b", "gemma2-2b"):
        assert f"LM_TP_OK {name}" in out.stdout, out.stdout + out.stderr
