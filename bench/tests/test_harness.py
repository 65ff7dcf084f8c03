"""The harness finds every part of a cell by name, refuses unknown names,
makes the same schedule from the same seed, and refuses to run without
a TPU.  CPU only; nothing here serves a model."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, model, run, traffic as tr

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"


def _cell(name="tiny.react", bench=FIX / "BENCHMARK.json",
          configs=FIX / "configs", mixes=FIX / "traffic"):
    return harness.load_cell(name, bench, configs, mixes)


def test_benchmark_cells_resolve_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config_name == w["config"]
        assert cell.mix["arrivals"]["kind"] in tr.ARRIVAL_KINDS
        assert model.model_config(cell.config).num_layers == \
            cell.config["num_hidden_layers"]
    for m in spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_output_tok_s_is_reported_only_where_listed():
    """``output_tok_s`` is reported only by the cells its entry lists:
    the open-loop ReAct cell, where it would read the offered load,
    keeps its three metrics, and a closed-loop cell reports it once an
    entry of its own names it, as the fixture's does."""
    cell = harness.load_cell("smollm-360m.react.steady")
    assert [m["name"] for m in cell.end_to_end] == \
        ["ttft_p90_ms", "tpot_mean_ms", "setup_s"]
    closed = {m["name"]: m for m in _cell("tiny.plan.closed").end_to_end}
    entry = closed["output_tok_s"]
    assert entry["unit"] == "tok/s" and entry["better"] == "higher"
    assert entry["workloads"] == ["tiny.plan.closed"]
    assert "output_tok_s" not in [m["name"] for m in _cell().end_to_end]


@pytest.mark.parametrize("lookup", [
    lambda: harness.load_cell("no.such.cell"),
    lambda: model.load_config("no-such-model"),
    lambda: tr.load_mix("no_such_mix"),
    lambda: run.metric_reader("no_such_metric"),
])
def test_unknown_names_are_refused(lookup):
    with pytest.raises(KeyError):
        lookup()


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a mix and a metric added as files, with one new
    entry in BENCHMARK.json, make a cell: no existing file changes."""
    configs, mixes, metrics = (tmp_path / d for d in
                               ("configs", "traffic", "metrics"))
    for d in (configs, mixes, metrics):
        d.mkdir()
    shutil.copytree(FIX / "configs", configs, dirs_exist_ok=True)
    shutil.copytree(FIX / "traffic", mixes, dirs_exist_ok=True)
    shutil.copy(FIX / "configs" / "tiny.json", configs / "tiny2.json")
    mix = json.loads((FIX / "traffic" / "tiny_react.json").read_text())
    mix["arrivals"] = {"kind": "poisson", "rate": 0.5}
    (mixes / "slow.json").write_text(json.dumps(mix))
    (metrics / "streams_seen.py").write_text(
        "def read(rec):\n    return float(len(rec.obs.streams))\n")
    spec = json.loads((FIX / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny2.slow", "config": "tiny2",
                              "traffic": "slow", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "streams_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "ttft_p90_ms",
                              "workloads": ["tiny2.slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("tiny2.slow", tmp_path / "BENCHMARK.json",
                             configs, mixes)
    assert cell.config["hidden_size"] == 64
    assert [m["name"] for m in cell.per_layer][-1] == "streams_seen"
    sched = tr.make_schedule(cell.mix, 4.0, seed=1)
    assert [a.at_s for a in sched][:1] == [1.0] and len(sched) == 2
    rec = type("R", (), {"obs": type("O", (), {"streams": [1, 2]})()})()
    assert run.metric_reader("streams_seen", metrics)(rec) == 2.0
    # the new metric applies to its own cell only
    assert "streams_seen" not in [
        m["name"] for m in harness.load_cell(
            "tiny.react", tmp_path / "BENCHMARK.json", configs,
            mixes).per_layer]


@pytest.mark.parametrize("seed", [1, 5, 2 ** 33 + 17])
def test_schedule_is_the_mixes_own(seed):
    """The mix fixes the sessions and gaps of the lead-in and of the
    window; the seed orders them.  The same seed gives the same
    schedule, another seed the same sets in another order."""
    mix = json.loads((FIX / "traffic" / "tiny_react.json").read_text())
    mix = {**mix, "lead_in_s": 10}
    a, b = tr.make_schedule(mix, 30.0, seed), tr.make_schedule(mix, 30.0,
                                                               seed)
    c = tr.make_schedule(mix, 30.0, seed + 1)
    assert a == b
    assert tr.spec_seeds(a) != tr.spec_seeds(c)
    for lo, hi in ((0.0, 10.0), (10.0, 40.0)):
        seg = [[x for x in s if lo <= x.at_s < hi] for s in (a, c)]
        assert len(seg[0]) == mix["arrivals"]["rate"] * (hi - lo)
        assert sorted(x.spec_seed for x in seg[0]) == \
            sorted(x.spec_seed for x in seg[1])
        gaps = [np.sort(np.diff([x.at_s for x in s] + [hi])) for s in seg]
        np.testing.assert_allclose(gaps[0], gaps[1], atol=1e-9)
        assert seg[0][0].at_s == lo


def test_session_plan_matches_what_the_server_builds():
    """The benchmark's copy of the session generator draws exactly what
    the server draws for the same spec seed, for ReAct and for
    Plan-and-Execute."""
    from repro.launch.serve import _session_from_spec
    from repro.configs.base import get_config
    react = json.loads((ROOT / "bench" / "traffic" /
                        "react_steady.json").read_text())
    plan_exec = {**json.loads((FIX / "traffic" /
                               "tiny_plan_closed.json").read_text()),
                 "token_scale": 1.0}
    mcfg = get_config("smollm-360m")
    for mix, seed in ((m, s) for m in (react, plan_exec)
                      for s in (100000, 100003, 2 ** 31 + 9)):
        spec = tr.request_spec(mix, seed)
        sess = _session_from_spec(spec, mcfg, 1.0, 1 << 20)
        plan = tr.make_session(mix["paradigm"], seed, mcfg.vocab_size,
                               mix["token_scale"])
        assert len(sess.turns) == len(plan.turns)
        for s, p in zip(sess.turns, plan.turns):
            np.testing.assert_array_equal(s.prefill_tokens,
                                          p.prefill_tokens)
            assert s.decode_len == p.decode_len
            assert s.tool_latency_s == p.tool_latency_s


def _run_cli(cwd: Path, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.react.steady", "--seed", str(2 ** 32 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    res = _run_cli(ROOT)
    assert res.returncode != 0
    assert "needs 1 TPU" in res.stderr
    assert not res.stdout.strip()


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_cli(tmp_path)
    assert res.returncode != 0
    assert not res.stdout.strip()


def test_cost_positions_agree_with_the_compared_context():
    """The context a decode token's cost is counted at is the row the
    correctness check reads its logits from, plus the new key."""
    from bench import correctness
    from bench.record import prefill_context, tokens_context
    mix = json.loads((FIX / "traffic" / "tiny_react.json").read_text())
    plan = tr.make_session(mix["paradigm"], 7, 512, 0.2)
    served = [list(range(t.decode_len)) for t in plan.turns]
    s = correctness.session_sample(plan, served)
    j = 0
    for k, turn in enumerate(plan.turns):
        assert s.rows[j] == prefill_context(plan, k) \
            + len(turn.prefill_tokens) - 1
        for i in range(1, turn.decode_len):
            assert tokens_context(plan, k, i) == s.rows[j + i] + 1
        j += turn.decode_len
    assert j == len(s.rows)


def test_slot_occupancy_is_weighted_by_time():
    """Many short idle cycles do not outweigh one long busy one."""
    obs = type("O", (), {"open_s": 0.0, "close_s": 10.0, "cycles": [
        (0.0, 0.0, 6.0), *[(0.0, 0.01 * i, 0.0) for i in range(1, 100)],
        (0.99, 1.0, 6.0), (1.0, 10.0, 0.0)]})()
    rec = type("R", (), {"obs": obs, "num_slots": 6})()
    got = run.metric_reader("slot_occupancy_pct")(rec)
    assert got == pytest.approx(100.0 * (0.01 + 9.0) / 10.0)


@pytest.mark.parametrize("end, pending", [("", True), ("cut", False)])
def test_a_cut_stream_is_failed_and_not_waited_for(end, pending):
    """A stream whose client broke off (``end == "cut"``) holds up no
    drain, and its unfinished turn counts as failed."""
    from bench.client import Stream
    mix = tr.load_mix("tiny_react", FIX / "traffic")
    plans = tr.session_plans(mix, [tr.Arrival(1.0, 7)], 512)
    st = Stream(7, status=200, end=end)
    assert harness._pending([st], [1.0], {}, plans, 0.0, 10.0) is pending
    obs = type("O", (), {"open_s": 0.0, "close_s": 10.0, "streams": [st],
                         "due_cold": [1.0], "tool_done": {}})()
    reqs, refused, bad = harness.requests_in_window(obs, plans)
    assert (len(reqs), refused, bad) == (1, 0, [])
    assert reqs[0].failed and reqs[0].first_s is None
