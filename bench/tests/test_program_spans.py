"""The serving engine's own spans (wave.fill, wave.to_device, wave.launch,
wave.readback; repro/serve/tracing.py) and engine scopes, as the trace
reduction of bench/trace.py sees them: by hand, and on a recorded
single-stream chip trace.  No JAX here."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

RECORDED = Path(__file__).resolve().parent / "trace_mnv2_single.json"
WAVE = ("wave.fill", "wave.to_device", "wave.launch", "wave.readback")
ENGINES = {"conv_pe", "dwc_pe", "low_channel", "misc", "mem"}
KERNEL = 'custom-call(), custom_call_target="tpu_custom_call"'


def hand_trace():
    # one single-stream request in a window [0, 100): round [0, 80) holds
    # submit [0, 5) and pump [5, 80); the pump holds the program's spans
    # fill [5, 10), to_device [10, 20), launch [20, 30), readback [30, 78);
    # the device runs [25, 40) and [45, 70); record [82, 90)
    return tr.Trace(
        window=(0, 100),
        ops={"/device:TPU:0": [("%k = s8[4] " + KERNEL, 25, 15),
                               ("%f = s8[4]{0} fusion(x)", 45, 25)]},
        spans=[("round", 0, 80), ("submit", 0, 5), ("pump", 5, 75),
               ("wave.fill", 5, 5), ("wave.to_device", 10, 10),
               ("wave.launch", 20, 10), ("wave.readback", 30, 48),
               ("record", 82, 8)])


def test_wave_spans_split_the_host_time_by_hand():
    t = hand_trace()
    idle = {n: tr.idle_in_spans(t, n) for n in WAVE}
    assert idle == {"wave.fill": [5], "wave.to_device": [10],
                    "wave.launch": [5], "wave.readback": [13]}
    # the four spans lie in the round and do not overlap, so their idle
    # time never passes the round's (host_ms_per_request.single) nor the
    # window's (idle_share.offline)
    spans = sum(sum(v) for v in idle.values())
    assert spans == 33 <= sum(tr.idle_in_spans(t, "round")) == 40
    assert spans * 1e-9 <= tr.idle_share(t) * tr.window_s(t)
    # gaps are named by the innermost span: the program's, inside pump
    assert tr.idle_by_label(t) == {
        "submit": pytest.approx(5e-9), "wave.fill": pytest.approx(5e-9),
        "wave.to_device": pytest.approx(10e-9),
        "wave.launch": pytest.approx(5e-9),
        "wave.readback": pytest.approx(13e-9),
        "pump": pytest.approx(2e-9), "record": pytest.approx(8e-9),
        "harness": pytest.approx(12e-9)}


def test_reduction_of_a_recorded_single_stream_trace():
    t = tr.Trace.load(RECORDED)
    scopes = json.loads(RECORDED.read_text())["scopes"]["/device:TPU:0"]
    lo, hi = t.window
    evs = t.ops["/device:TPU:0"]
    mask = np.zeros(hi - lo, bool)
    scoped = np.zeros(hi - lo, bool)
    for (name, s, d), scope in zip(evs, scopes):
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            mask[a:b] = True
            scoped[a:b] |= bool(scope)
    assert tr.busy_s(t) == pytest.approx(mask.sum() * 1e-9)
    by_label = tr.idle_by_label(t)
    assert sum(by_label.values()) == pytest.approx(
        (hi - lo - mask.sum()) * 1e-9)
    assert set(by_label) <= {"submit", "pump", "record", "harness", *WAVE}
    # each request's pump records the four spans once, in order, disjoint
    spans = sorted(t.spans, key=lambda x: x[1])
    pumps = [x for x in spans if x[0] == "pump"]
    assert len(pumps) == 2
    for _, p0, pd in pumps:
        inside = [x for x in spans if x[0] in WAVE
                  and p0 <= x[1] and x[1] + x[2] <= p0 + pd]
        assert [x[0] for x in inside] == list(WAVE)
        for a, b in zip(inside, inside[1:]):
            assert a[1] + a[2] <= b[1]
    # the program's spans sit on the device's clock: the device works only
    # between the start of a launch and the end of the readback after it
    launch = [x[1] for x in spans if x[0] == "wave.launch"]
    back = [x[1] + x[2] for x in spans if x[0] == "wave.readback"]
    merged = tr.busy(t)["/device:TPU:0"]
    inside = sum(tr.covered(merged, a, b) for a, b in zip(launch, back))
    assert inside >= 0.99 * mask.sum()
    # the wave spans' idle time is part of the rounds' idle time
    wave_idle = sum(sum(tr.idle_in_spans(t, n)) for n in WAVE)
    assert 0 < wave_idle <= sum(tr.idle_in_spans(t, "round"))
    # every op's scope is an engine (or none, for ops XLA adds); a Pallas
    # kernel is named after the engine scope it runs under
    assert set(scopes) <= ENGINES | {""}
    for (name, _, _), scope in zip(evs, scopes):
        if tr.is_kernel(name):
            assert name.lstrip("%").startswith(scope + "."), name
    assert scoped.sum() >= 0.9 * mask.sum()
