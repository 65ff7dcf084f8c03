"""The readers of the per-request stage stamps and of the tokens'
emission stamps, on hand-built records: each returns the p95 of what
it reads, and ``None`` where the program kept no such stamp (an older
program) or nothing fell in the window."""
from __future__ import annotations

import types

import numpy as np
import pytest

from bench import run
from bench.client import Stream
from bench.harness import Observed, Request
from bench.record import Record

OFFSET = 100.0            # client clock - engine clock


def _session(k, **drop):
    """Turn ``k`` of session 7: staged 1, ready +0.5 ms, admitted
    +1 ms, dispatched +(2 + k) ms, first token +(102 + k) ms (engine
    clock, seconds)."""
    base = 10.0 * np.arange(k + 1) + 1.0
    stamps = dict(staged_s=base, readied_s=base + 0.0005,
                  request_arrivals=base + 0.0015,
                  dispatched_s=base + 0.0015 + 0.002 * (1 + np.arange(k + 1)),
                  first_token_s=base + 0.1015 + 0.001 * np.arange(k + 1))
    for name in drop:
        stamps.pop(name)
    return types.SimpleNamespace(**{n: list(v) for n, v in stamps.items()})


def _record(sess, events, received, reqs):
    """One stream of session 7; the stream names its session by its
    first event."""
    st = Stream(spec_seed=1, status=200, events=events, received_s=received)
    obs = Observed(t_start=0.0, open_s=OFFSET, close_s=OFFSET + 60.0,
                   end_s=OFFSET + 60.0, streams=[st], due_cold=[OFFSET],
                   tool_done={}, cycles=[], dispatch_gaps=[],
                   sessions={7: sess}, engine_offset=OFFSET)
    return Record(cfg={}, peaks={}, obs=obs, reqs=reqs, plans={},
                  trace=None, memory={}, num_slots=1)


def _event(t, t_emit=None, index=1, error=False):
    ev = {"session_id": 7, "t": t, "turn_idx": 0, "index": index,
          "error": error}
    if t_emit is not None:
        ev["t_emit"] = t_emit
    return ev


def _reqs(n):
    return [Request(stream=0, turn=k, due_s=OFFSET, first_s=None,
                    done_s=None, failed=False) for k in range(n)]


def _p95_ms(xs):
    return 1e3 * float(np.percentile(xs, 95))


STAGE_READERS = [
    ("gateway_wait_p95_ms", "staged_s", [0.0005] * 3),
    ("prefill_queue_p95_ms", "dispatched_s", [0.002, 0.004, 0.006]),
    ("prefill_run_p95_ms", "first_token_s", [0.098, 0.097, 0.096]),
]


@pytest.mark.parametrize("name,stage,want", STAGE_READERS)
def test_stage_reader_by_hand(name, stage, want):
    def rec(sess, reqs):
        return _record(sess, [_event(1.0)], [OFFSET + 1.0], reqs)
    read = run.metric_reader(name)
    assert read(rec(_session(2), _reqs(3))) == pytest.approx(_p95_ms(want))
    # only the requests due in the window are read
    assert read(rec(_session(2), _reqs(1))) == pytest.approx(1e3 * want[0])
    # a program that keeps no such stamp, or no request: nothing
    assert read(rec(_session(2, **{stage: None}), _reqs(3))) is None
    assert read(rec(_session(2), [])) is None


def test_token_hold_and_front_lag_by_hand():
    """Hold: the host read less the engine's stamp; front: the client's
    receipt less the host read.  Errors and tokens received outside the
    window are left out."""
    events = [_event(1.0, 1.3), _event(1.1, 1.3), _event(1.2, 1.3),
              _event(1.3, 1.3, index=0),
              _event(2.0, 2.5, error=True),      # terminal error event
              _event(70.0, 70.4)]                # received after close
    received = [OFFSET + 1.31, OFFSET + 1.31, OFFSET + 1.32,
                OFFSET + 1.34, OFFSET + 2.6, OFFSET + 70.5]
    rec = _record(_session(0), events, received, _reqs(1))
    hold = run.metric_reader("token_hold_p95_ms")(rec)
    front = run.metric_reader("front_lag_p95_ms")(rec)
    assert hold == pytest.approx(_p95_ms([0.3, 0.2, 0.1, 0.0]))
    assert front == pytest.approx(_p95_ms([0.01, 0.01, 0.02, 0.04]))
    # sse_lag (client receipt less t) is the hold plus the front
    sse = run.metric_reader("sse_lag_p95_ms")(rec)
    assert sse == pytest.approx(_p95_ms([0.31, 0.21, 0.12, 0.04]))


@pytest.mark.parametrize("name", ["token_hold_p95_ms", "front_lag_p95_ms"])
def test_emission_readers_find_nothing(name):
    """Events without ``t_emit`` (an older program), and a window with
    no token received, give ``None``."""
    older = _record(_session(0), [_event(1.0), _event(1.1)],
                    [OFFSET + 1.3, OFFSET + 1.3], _reqs(1))
    assert run.metric_reader(name)(older) is None
    late = _record(_session(0), [_event(70.0, 70.4)], [OFFSET + 70.5],
                   _reqs(1))
    assert run.metric_reader(name)(late) is None


def test_ttft_parts_add_up_to_the_client_ttft():
    """The six parts of ``bench/ttft_parts.py`` tile the client's time
    to first token; a program without the stamps gives no parts."""
    from bench import ttft_parts
    first_s = OFFSET + 1.1115
    req = Request(stream=0, turn=0, due_s=OFFSET + 0.99, first_s=first_s,
                  done_s=None, failed=False)
    rec = _record(_session(0), [_event(1.1015, 1.1015, index=0)],
                  [first_s], [req])
    got = ttft_parts.split(rec, req)
    want = {"hop": 10.0, "gateway": 0.5, "admission": 1.0, "planner": 2.0,
            "prefill_run": 98.0, "front": 10.0}
    for name, v in want.items():
        assert got[name] == pytest.approx(v), name
    assert got["sum"] == pytest.approx(got["ttft"]) == pytest.approx(121.5)
    older = _record(_session(0, staged_s=None), [_event(1.1015, 1.1015)],
                    [first_s], [req])
    assert ttft_parts.split(older, req) is None
    rows = [{"ttft": float(t)} for t in range(1, 11)]
    assert ttft_parts.at_p90(rows)["ttft"] == 9.0
    assert ttft_parts.at_p90([]) is None


def test_ttft_parts_cycle_summary():
    """Cycles that end in the window, by hand."""
    from bench import ttft_parts
    obs = types.SimpleNamespace(open_s=10.0, close_s=20.0, cycles=[
        (9.0, 9.5, 0), (9.9, 10.1, 0), (10.1, 12.1, 0), (12.3, 12.4, 0),
        (19.0, 21.0, 0)])
    got = ttft_parts.cycles(obs)
    assert got["n"] == 3
    assert got["max_ms"] == pytest.approx(2000.0)
    assert got["in_long_ms"] == pytest.approx(2000.0)
    assert got["longest_between_ms"] == pytest.approx(200.0)
    assert got["longest_between_at_s"] == pytest.approx(2.1)
    assert ttft_parts.cycles(types.SimpleNamespace(
        open_s=0.0, close_s=1.0, cycles=[])) == {}
