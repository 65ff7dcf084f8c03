"""A toy-width cell served end to end on the CPU: the harness drives the
program's HTTP/SSE front and gateway, and ``correct`` is decided by the
plain reference.  Skips only the harness's look for a chip.  The same
run with a served token altered where the engine produces it, and with
the program's own bfloat16 path, must come out not correct, and so must
the reference at int8 and bfloat16 put in the program's place (the
controls)."""
from __future__ import annotations

import argparse
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness, run

FIX = Path(__file__).resolve().parent / "fixtures"
SECONDS = 3.0


def _serve(seed, workload="tiny.react", **kw):
    cell = harness.load_cell(workload, FIX / "BENCHMARK.json",
                             FIX / "configs", FIX / "traffic")
    args = argparse.Namespace(workload=cell.name, seed=seed,
                              seconds=SECONDS, trace=0, keep_trace="")
    return run.run(args, skip_device_check=True, cell=cell, **kw)


def _alter_tokens(srv):
    """Every fifth token the engine emits is replaced by its neighbour."""
    eng = srv.engine
    emit = eng._emit
    count = [0]

    def emit_altered(sess, token, *a, **k):
        count[0] += 1
        if count[0] % 5 == 0:
            token = (int(token) + 1) % eng.mcfg.vocab_size
        return emit(sess, token, *a, **k)
    eng._emit = emit_altered


@pytest.fixture(scope="module")
def sound():
    return _serve(2 ** 32 + 11, reference_control=True)


def test_served_cell_is_correct(sound):
    """Fails while the engine reads host arrays that it has already
    changed under an unfinished transfer (PERF.md, open question 1):
    on a loaded CPU that serves wrong greedy tokens."""
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0
    assert sound["attempted"] > 0
    names = set(sound["metrics"])
    assert names == {"ttft_p90_ms", "tpot_mean_ms", "setup_s"}
    assert all(v["value"] > 0 for v in sound["metrics"].values())
    assert list(sound)[-1] == "checks"


def test_closed_loop_cell_is_correct():
    """Six closed-loop agents of Plan-and-Execute sessions: every
    request due in the window is served and correct, and the window's
    tokens per second are reported."""
    res = _serve(2 ** 32 + 13, workload="tiny.plan.closed")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["metrics"]["output_tok_s"]["unit"] == "tok/s"


@pytest.mark.parametrize("fault", ["altered_token", "bf16_control"])
def test_fault_and_control_are_not_correct(fault):
    kw = ({"fault": _alter_tokens} if fault == "altered_token"
          else {"dtype": jnp.bfloat16})
    res = _serve(2 ** 32 + 11, **kw)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("numerics", ["int8", "bfloat16"])
def test_reference_controls_are_not_correct(sound, numerics):
    """The reference at lower numerics, put in the program's place on
    the sound run's sample, reads above the limit."""
    assert sound["readings"][f"ref_{numerics}_max_logit_gap"] > \
        sound["checks"]["max_logit_gap"]["limit"]


def test_traced_run_reports_host_side_layers():
    """On the CPU there is no device trace: the device metrics are left
    out, the host-side ones are read."""
    cell = harness.load_cell("tiny.react", FIX / "BENCHMARK.json",
                             FIX / "configs", FIX / "traffic")
    args = argparse.Namespace(workload=cell.name, seed=3, seconds=SECONDS,
                              trace=1, keep_trace="")
    traced = run.run(args, skip_device_check=True, cell=cell)
    assert set(traced["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "slot_occupancy_pct" in traced["metrics"]
    assert 0 < traced["metrics"]["slot_occupancy_pct"]["value"] <= 100
