"""Quick tests of the benchmark's own code: work counts, trace reduction,
BENCHMARK.json and discovery by name.  No model is built or compiled here;
bench/tests/check_control.py holds the slow checks of `correct`."""
from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import model, peaks, traffic
from bench import trace as tr
from bench.metrics import reader
from bench.run import cell_metrics, load_benchmark, logit_err

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).resolve().parent / "trace_r50_offline.json"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def by_name(cfg, name):
    return next(l for l in model.layers(cfg) if l.name == name)


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------

def test_conv_counts_by_hand():
    stem = by_name(model.load_config("resnet50-224-int8"), "stem")
    assert (stem.hw_in, stem.hw_out) == (224, 112)
    assert stem.macs == 112 * 112 * 7 * 7 * 3 * 64
    assert stem.weight_bytes == 7 * 7 * 3 * 64
    assert stem.map_bytes == 224 * 224 * 3 + 112 * 112 * 64


def test_depthwise_counts_by_hand():
    # first block of MobileNetV2's 24-channel row: 16 -> 96 channels, the
    # 3x3 depthwise at stride 2 takes 112x112 to 56x56
    dw = by_name(model.load_config("mobilenetv2-224-int8"), "s1.b0.dw")
    assert (dw.hw_in, dw.hw_out, dw.cout) == (112, 56, 96)
    assert dw.macs == 56 * 56 * 9 * 96
    assert dw.weight_bytes == 9 * 96
    assert dw.map_bytes == 112 * 112 * 96 + 56 * 56 * 96


def test_bottleneck_counts_by_hand():
    # ResNet-50's first bottleneck, at 55x55 after the VALID max pool:
    # 1x1 64->64, 3x3 64->64, 1x1 64->256, 1x1 projection 64->256, add
    cfg = model.load_config("resnet50-224-int8")
    block = [l for l in model.layers(cfg) if l.name.startswith("s1.b0.")]
    assert [l.name.split(".")[-1] for l in block] == [
        "conv1", "conv2", "conv3", "proj", "add"]
    px = 55 * 55
    assert sum(l.macs for l in block) == px * (64 * 64 + 9 * 64 * 64
                                               + 64 * 256 + 64 * 256)
    add = block[-1]
    assert add.macs == 0 and add.map_bytes == 3 * px * 256


@pytest.mark.parametrize("name,published_edit,extra_macs", [
    # as published the pool pads SAME and stage 1 runs at 56x56
    ("resnet50-224-int8", lambda c: c["maxpool"].update(padding="SAME"),
     None),
    # as published the t=1 block has no expansion conv (32 -> 32 at 112x112)
    ("mobilenetv2-224-int8", lambda c: c.update(expand_conv_at_t1=False),
     112 * 112 * 32 * 32),
])
def test_model_totals_match_the_papers(name, published_edit, extra_macs):
    cfg = model.load_config(name)
    published = json.loads(json.dumps(cfg))
    published_edit(published)
    w, pub = model.work(cfg), model.work(published)
    assert pub["macs"] == pytest.approx(cfg["published"]["macs"], rel=0.01)
    assert pub["params"] == pytest.approx(cfg["published"]["params"],
                                          rel=0.03)
    if extra_macs is not None:
        assert w["macs"] - pub["macs"] == extra_macs
    else:
        assert w["macs"] < pub["macs"]
    assert w["ops"] == 2 * w["macs"]


def test_roofline_time_is_the_larger_bound_per_layer():
    cfg = model.load_config("mobilenetv2-224-int8")
    p = peaks.peak("TPU v5 lite")
    wave = 64
    want = sum(max(2.0 * l.macs / p["int8_ops_per_s"],
                   (l.map_bytes + l.weight_bytes / wave) / p["hbm_bytes_per_s"])
               for l in model.layers(cfg))
    got = model.roofline_s_per_image(cfg, wave, p["int8_ops_per_s"],
                                     p["hbm_bytes_per_s"])
    assert got == pytest.approx(want)
    # no layer can be faster than its compute alone
    assert got >= model.work(cfg)["ops"] / p["int8_ops_per_s"]


def test_program_config_follows_the_file():
    cfg = model.load_config("resnet50-224-int8")
    pc = model.program_config(cfg)
    assert pc.input_hw == 224 and pc.stem_kernel == 7 and pc.stem_ch == 64
    assert [s.kind for s in pc.stages] == ["pool"] + ["bottleneck"] * 4
    assert [s.repeat for s in pc.stages[1:]] == [3, 4, 6, 3]
    bad = model.load_config("mobilenetv2-224-int8")
    bad["expand_conv_at_t1"] = False
    with pytest.raises(ValueError):
        model.program_config(bad)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

KERNEL = 'custom-call(), custom_call_target="tpu_custom_call"'


def hand_trace():
    # window [0, 100); ops: a kernel [10, 30), glue [20, 40) overlapping it,
    # glue [60, 70); spans: submit [0, 10), pump [10, 75), record [80, 90)
    return tr.Trace(
        window=(0, 100),
        ops={"/device:TPU:0": [("%k = s8[4] " + KERNEL, 10, 20),
                               ("%f = s8[4]{0} fusion(x)", 20, 20),
                               ("%c = f32[2]{0} copy(y)", 60, 10)]},
        spans=[("round", 0, 75), ("submit", 0, 10), ("pump", 10, 65),
               ("record", 80, 10)])


def test_reduction_by_hand():
    t = hand_trace()
    assert tr.busy(t)["/device:TPU:0"] == [(10, 40), (60, 70)]
    assert tr.busy_s(t) == pytest.approx(40e-9)
    assert tr.idle_share(t) == pytest.approx(0.6)
    # glue covers [20, 40) and [60, 70): 30 of 40 busy
    assert tr.glue_share(t) == pytest.approx(0.75)
    assert tr.op_count(t) == 3
    assert tr.gaps(t) == [(0, 10), (40, 60), (70, 100)]
    # the last gap [70, 100) lies in pump [70, 75), nothing [75, 80),
    # record [80, 90) and nothing [90, 100)
    assert tr.idle_gaps(t) == [
        ("pump", pytest.approx(20e-9)), ("submit", pytest.approx(10e-9)),
        ("record", pytest.approx(10e-9)), ("harness", pytest.approx(10e-9)),
        ("pump", pytest.approx(5e-9)), ("harness", pytest.approx(5e-9))]
    assert tr.idle_by_label(t) == {
        "submit": pytest.approx(10e-9), "pump": pytest.approx(25e-9),
        "harness": pytest.approx(15e-9), "record": pytest.approx(10e-9)}
    assert tr.idle_in_spans(t, "round") == [75 - 40]
    assert tr.short_name("%k = s8[4] " + KERNEL) == \
        "%k s8[4] custom-call tpu_custom_call"
    assert [n for n, _ in tr.top_ops(t)] == [
        "%k s8[4] custom-call tpu_custom_call", "%f s8[4] fusion",
        "%c f32[2] copy"]


def test_reduction_of_a_recorded_chip_trace():
    t = tr.Trace.load(RECORDED)
    lo, hi = t.window
    evs = t.ops["/device:TPU:0"]
    mask = np.zeros(hi - lo, bool)
    glue = np.zeros(hi - lo, bool)
    for name, s, d in evs:
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            mask[a:b] = True
            if not tr.is_kernel(name):
                glue[a:b] = True
    assert tr.busy_s(t) == pytest.approx(mask.sum() * 1e-9)
    assert tr.glue_share(t) == pytest.approx(glue.sum() / mask.sum())
    assert 0 < tr.idle_share(t) < 1
    assert sum(tr.idle_by_label(t).values()) == pytest.approx(
        (hi - lo - mask.sum()) * 1e-9)
    # the device idles from the end of one pump, through the next submit,
    # into the next pump (the early regime in which wave buffers faulted)
    assert set(tr.idle_by_label(t)) == {"pump", "submit", "harness"}
    assert len(tr.top_ops(t)) == 10 and len(tr.idle_gaps(t)) <= 10


# ---------------------------------------------------------------------------
# BENCHMARK.json and discovery
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def test_benchmark_json_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in bench["workloads"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in bench[group]}) == len(bench[group])
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_cell_reports_what_the_contract_asks(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = {m["name"] for m in cell_metrics(bench, w["name"], False)}
        assert "setup_s" in reported and len(reported) >= 2
        layer = cell_metrics(bench, w["name"], True)
        assert layer and all(m["moves"] in reported for m in layer)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_run_seconds_fits_the_check_with_24_cells(bench):
    rs = bench["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_discovery_by_name(bench):
    assert {c["name"] for c in bench["configs"]} == set(model.config_names())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).resolve() == \
            model.CONFIG_DIR / f"{c['name']}.json"
        assert model.load_config(c["name"])["source"] == c["source"]
    used = {w["traffic"] for w in bench["workloads"]}
    assert used == set(traffic.mix_names())
    for name in used:
        assert traffic.load_mix(name)["wave_size"] >= 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(reader(m["name"]))
    with pytest.raises(KeyError):
        model.load_config("no-such-model")
    with pytest.raises(KeyError):
        traffic.load_mix("no-such-mix")
    with pytest.raises(KeyError):
        reader("no_such_metric")


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_logit_err():
    ref = np.array([[1.0, -1.0], [-1.0, 1.0]])
    # the reference answers spread by 1 around their mean (0, 0)
    assert logit_err(ref, ref) == 0.0
    assert logit_err(ref[::-1], ref) == pytest.approx(2.0)
    assert logit_err(np.zeros_like(ref), ref) == pytest.approx(1.0)
    got = ref + np.array([[0.3, 0.4], [0.0, 0.0]])
    assert logit_err(got, ref) == pytest.approx(np.sqrt(0.125))
    got[1, 1] = np.nan
    assert logit_err(got, ref) == float("inf")
