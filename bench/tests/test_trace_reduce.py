"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e (``fixtures/trace_events.json``: 12
ms of a traced run of the SmolLM-360M ReAct cell, as ``load`` returns
it, cut with ``trace_reduce.trim``)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import trace_reduce as trr

FIX = Path(__file__).resolve().parent / "fixtures"
MS = 1_000_000


def test_reduce_by_hand():
    ops = [["fusion.1", 0, 2 * MS], ["copy.2", 1 * MS, 2 * MS],
           ["fusion.1", 5 * MS, 1 * MS], ["dot.3", 9 * MS, 1 * MS]]
    mods = [["jit_fused_step(7)", 0, 3 * MS],
            ["jit_mega_step(2)", 5 * MS, 1 * MS],
            ["jit_resume_step", 9 * MS, 1 * MS]]
    events = {"devices": {"/device:TPU:0": {trr.OPS_LINE: ops,
                                            trr.MODULES_LINE: mods}},
              "spans": [["engine.step", 2 * MS, 8 * MS],
                        ["engine.flush", 6 * MS, 2 * MS],
                        [trr.MARKER, 0, 10]]}
    out = trr.reduce(events, window_ns=(0, 12 * MS))
    assert out["busy_s"] == pytest.approx(5e-3)        # [0,3] [5,6] [9,10]
    assert out["executables"] == pytest.approx(
        {"fused_step": 3e-3, "mega_step": 1e-3, "resume_step": 1e-3})
    assert out["top_ops"][0] == ["fusion.1", pytest.approx(3e-3)]
    # idle: [3,5] in engine.step, [6,9] in engine.flush at its middle
    # (7.5 ms), [10,12] after every span
    assert out["idle_gaps"] == [["engine.flush", pytest.approx(3e-3)],
                                ["engine.step", pytest.approx(2e-3)],
                                ["no span", pytest.approx(2e-3)]]
    assert out["marker_s"] == 0.0
    # without a window the span of the device operations is used
    assert trr.reduce(events)["idle_gaps"][-1][1] == pytest.approx(2e-3)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trr.reduce({"devices": {"/device:TPU:0": {}}, "spans": []})


@pytest.mark.parametrize("name,want", [
    ("jit_fused_step(12)", "fused_step"), ("jit_mega_step", "mega_step"),
    ("jit_prefill_step.3", "prefill_step"), ("fusion", "fusion")])
def test_executable_names(name, want):
    assert trr.executable_name(name) == want



def test_recorded_v5e_trace():
    events = json.loads((FIX / "trace_events.json").read_text())
    ops = events["devices"]["/device:TPU:0"][trr.OPS_LINE]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    out = trr.reduce(events)
    assert out["devices"] == 1
    assert 0.9 * (hi - lo) / 1e9 < out["busy_s"] <= (hi - lo) / 1e9
    assert set(out["executables"]) == {"fused_step"}
    assert out["top_ops"][0][0].startswith("%while")
    assert all(len(name) <= trr.NAME_CHARS for name, _ in out["top_ops"])
    assert sum(d for _, d in out["top_ops"]) <= sum(
        d for _, _, d in ops) / 1e9 + 1e-12
    assert {name for name, _ in out["idle_gaps"]} <= {
        "engine.step", "engine.execute", "engine.flush", "no span"}
