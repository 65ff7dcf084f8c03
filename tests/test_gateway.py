"""Online gateway: async streaming sessions, open-loop arrivals,
watermark backpressure, tool-wait slot policy, and the HTTP/SSE front.

Every token-stream assertion goes through the scheduling-independent
greedy oracle (tests/_serving_util.py), so concurrency bugs that
corrupt KV state cannot hide behind 'all sessions finished'."""
import asyncio
import json
import logging

import jax
import numpy as np
import pytest
from _serving_util import events_by_session, oracle_streams

from repro.configs.base import ModelConfig
from repro.core.admission import WatermarkGate
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.gateway import (AgentGateway, GatewayConfig,
                                   GatewayStopped, Rejected,
                                   drive_open_loop)
from repro.serving.metrics import (OpenLoopReport, SLOThresholds,
                                   build_open_loop_report)
from repro.serving.policies import POLICIES
from repro.serving.request import SessionState
from repro.serving.workload import (load_arrival_trace,
                                    make_open_loop_workload,
                                    poisson_arrivals, save_arrival_trace)

TINY = ModelConfig(name="tiny-serve", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=128, tie_embeddings=True, source="test")


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _engine(params, *, num_slots=4):
    ecfg = EngineConfig(num_slots=num_slots, max_seq=512, cycle_budget=80,
                        granularity=8, b_min=8, b_max=128, b_init=32,
                        delta_b=8, control_interval_s=0.05,
                        max_wall_s=float("inf"))
    return ServingEngine(TINY, params, POLICIES["agentserve"], ecfg)


def _sessions(n, *, seed=0, rate=8.0):
    return make_open_loop_workload(n, workload="react",
                                   vocab_size=TINY.vocab_size,
                                   token_scale=0.0625, seed=seed,
                                   rate_rps=rate)


def _drive(gateway, sessions, *, stop_timeout=60.0):
    arrivals = [s.ready_s for s in sessions]

    async def go():
        await gateway.start()
        run = await drive_open_loop(gateway, sessions, arrivals)
        await gateway.stop(timeout_s=stop_timeout)
        return run

    return asyncio.run(go())


# ---------------------------------------------------------------------------
# open-loop arrival processes
# ---------------------------------------------------------------------------

def test_poisson_arrivals_seeded_deterministic():
    a = poisson_arrivals(5.0, 50, seed=3)
    b = poisson_arrivals(5.0, 50, seed=3)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, poisson_arrivals(5.0, 50, seed=4))
    assert np.all(np.diff(a) > 0)
    # mean inter-arrival ~ 1/rate (loose: 50 samples)
    assert 0.5 / 5.0 < np.mean(np.diff(a)) < 2.0 / 5.0


def test_arrival_trace_roundtrip(tmp_path):
    path = str(tmp_path / "trace.txt")
    times = poisson_arrivals(2.0, 10, seed=1)
    save_arrival_trace(path, times)
    np.testing.assert_allclose(load_arrival_trace(path), times, atol=1e-8)
    sessions = make_open_loop_workload(10, vocab_size=64, token_scale=0.05,
                                      trace_path=path)
    assert [s.ready_s for s in sessions] == pytest.approx(list(times))


def test_open_loop_workload_argument_validation():
    with pytest.raises(ValueError):
        make_open_loop_workload(4, vocab_size=64)         # no source
    with pytest.raises(ValueError):
        make_open_loop_workload(4, vocab_size=64, rate_rps=1.0,
                                arrivals=np.arange(4.0))  # two sources
    with pytest.raises(ValueError):
        make_open_loop_workload(4, vocab_size=64, arrivals=np.arange(2.0))


# ---------------------------------------------------------------------------
# watermark gate
# ---------------------------------------------------------------------------

def test_watermark_gate_hysteresis():
    gate = WatermarkGate(high=4, low=2)
    assert gate.offer(3)                 # below high: admit
    assert not gate.offer(4)             # at high: close
    assert not gate.offer(3)             # hysteresis: still shedding
    assert gate.offer(2)                 # at low: reopen
    assert gate.admitted == 2 and gate.rejected == 2
    with pytest.raises(ValueError):
        WatermarkGate(high=2, low=2)


# ---------------------------------------------------------------------------
# gateway end-to-end
# ---------------------------------------------------------------------------

def test_gateway_streams_complete_interleaved_and_token_exact(tiny_params):
    """≥4 concurrent open-loop agents: every stream completes, events
    from different sessions interleave (live concurrency), and every
    stream is token-for-token the isolated greedy reference."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    sessions = _sessions(5, rate=6.0)
    run = _drive(gw, sessions)

    assert len(run.completed) == 5 and not run.rejected
    assert all(s.state == SessionState.FINISHED for s in run.completed)
    assert run.interleaved()
    assert gw.counters["tool_calls"] == sum(
        len(s.turns) - 1 for s in sessions)

    streams = events_by_session([ev for _, ev in run.events])
    want = oracle_streams(TINY, tiny_params, sessions,
                          num_slots=4, max_seq=512)
    for s in sessions:
        assert streams[s.session_id] == want[s.session_id]

    rep = build_open_loop_report("agentserve", run.completed, run.wall_s,
                                 6.0, rejected=0,
                                 thresholds=SLOThresholds(10.0, 2.0))
    assert rep.completed == 5
    assert rep.goodput_tok_s > 0
    assert np.isfinite(rep.queue_delay_p95_s)
    assert len(rep.row().split(",")) == len(OpenLoopReport.HEADER.split(","))


def test_gateway_backpressure_429_above_watermark(tiny_params):
    """A burst above the watermark is shed with 429-style results; the
    admitted subset still completes and streams correctly."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=1, low_watermark=0))
    sessions = _sessions(4, rate=1000.0)     # effectively simultaneous
    run = _drive(gw, sessions)

    assert len(run.rejected) >= 1
    assert len(run.completed) >= 1
    assert len(run.completed) + len(run.rejected) == 4
    assert gw.counters["rejected"] == len(run.rejected)
    assert gw.gate.rejected >= len(run.rejected)
    streams = events_by_session([ev for _, ev in run.events])
    want = oracle_streams(TINY, tiny_params, sessions,
                          num_slots=4, max_seq=512)
    for s in run.completed:
        assert streams[s.session_id] == want[s.session_id]


def test_gateway_rejected_result_shape(tiny_params):
    """submit() surfaces shedding as a 429-style value, not an
    exception."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=1, low_watermark=0))

    async def go():
        await gw.start()
        first = await gw.submit(_sessions(1, seed=11)[0])
        second = await gw.submit(_sessions(1, seed=12)[0])
        assert not isinstance(first, Rejected)
        assert isinstance(second, Rejected)
        assert second.status == 429 and second.occupancy >= 1
        async for _ in first.events():
            pass
        await gw.stop(timeout_s=60.0)

    asyncio.run(go())


def test_gateway_queue_admission_waits_instead_of_shedding(tiny_params):
    """admission='queue': over-watermark submissions wait for the gate
    to reopen (bounded) rather than shedding immediately."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(
        high_watermark=2, low_watermark=1, admission="queue",
        queue_timeout_s=30.0))
    sessions = _sessions(4, rate=1000.0)
    run = _drive(gw, sessions)
    assert len(run.completed) == 4 and not run.rejected


def test_tool_wait_holds_slot_by_default(tiny_params):
    """hold policy: a session in TOOL_WAIT keeps its KV slot and cached
    length across the (gateway-clocked) tool wait."""
    eng = _engine(tiny_params)
    observed = []

    async def tool_fn(sess, turn_idx):
        observed.append((sess.slot, int(eng.pool.lengths[sess.slot])
                         if sess.slot >= 0 else -1))
        await asyncio.sleep(0.01)
        return None

    gw = AgentGateway(eng, GatewayConfig(high_watermark=32,
                                         tool_policy="hold"),
                      tool_fn=tool_fn)
    sessions = _sessions(2, rate=6.0)
    run = _drive(gw, sessions)

    assert len(run.completed) == 2
    assert observed and all(slot >= 0 and cached > 0
                            for slot, cached in observed)
    assert eng.hotpath_stats["parks"] == 0
    streams = events_by_session([ev for _, ev in run.events])
    want = oracle_streams(TINY, tiny_params, sessions,
                          num_slots=4, max_seq=512)
    for s in run.completed:
        assert streams[s.session_id] == want[s.session_id]


def test_tool_wait_release_under_pressure(tiny_params):
    """release policy: with more live agents than KV slots, TOOL_WAIT
    sessions give up their slot to waiting sessions (parks observed)
    and every resume is still token-exact — the restore is lossless."""
    eng = _engine(tiny_params, num_slots=2)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=64,
                                         tool_policy="release"))
    sessions = _sessions(3, rate=1000.0)     # all arrive together
    run = _drive(gw, sessions, stop_timeout=120.0)

    assert len(run.completed) == 3
    assert gw.counters["parked"] >= 1
    assert (eng.hotpath_stats["unparks"] == eng.hotpath_stats["parks"]
            >= 1)
    streams = events_by_session([ev for _, ev in run.events])
    want = oracle_streams(TINY, tiny_params, sessions,
                          num_slots=2, max_seq=512)
    for s in run.completed:
        assert streams[s.session_id] == want[s.session_id]


def test_tool_fn_failure_does_not_wedge_session(tiny_params):
    """A raising tool_fn must not strand the session in TOOL_WAIT: the
    error is counted and the session resumes with its scripted
    tokens — the client stream still terminates."""
    eng = _engine(tiny_params)

    async def tool_fn(sess, turn_idx):
        raise RuntimeError("tool exploded")

    gw = AgentGateway(eng, GatewayConfig(high_watermark=32),
                      tool_fn=tool_fn)
    sessions = _sessions(1, seed=9)
    run = _drive(gw, sessions)
    assert len(run.completed) == 1
    assert gw.counters["tool_errors"] == len(sessions[0].turns) - 1
    assert list(gw.completed_sessions) == run.completed


def test_tool_fn_can_replace_next_turn_prefill(tiny_params):
    """A real tool's output becomes the next turn's prefill tokens."""
    eng = _engine(tiny_params)
    marker = np.full((7,), 5, np.int32)

    async def tool_fn(sess, turn_idx):
        return marker

    gw = AgentGateway(eng, GatewayConfig(high_watermark=32),
                      tool_fn=tool_fn)
    sessions = _sessions(1, seed=6)
    run = _drive(gw, sessions)
    assert len(run.completed) == 1
    s = run.completed[0]
    for turn in s.turns[1:]:
        np.testing.assert_array_equal(turn.prefill_tokens, marker)
    # and the stream still matches the oracle for the *replaced* turns
    streams = events_by_session([ev for _, ev in run.events])
    want = oracle_streams(TINY, tiny_params, [s], num_slots=4, max_seq=512)
    assert streams[s.session_id] == want[s.session_id]


def test_swallowed_engine_error_is_kept_and_logged(tiny_params, caplog):
    """An exception escaping one loop iteration is counted, logged with
    its traceback and kept on the gateway; the loop carries on and the
    sessions still finish."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    step, calls = gw.reactor.step, []

    def flaky_step():
        calls.append(1)
        if len(calls) in (2, 3):
            raise RuntimeError("injected device fault")
        return step()

    gw.reactor.step = flaky_step
    with caplog.at_level(logging.ERROR, logger="repro.serving.gateway"):
        run = _drive(gw, _sessions(2, rate=8.0))
    assert len(run.completed) == 2
    assert gw.counters["engine_errors"] == 2
    assert "injected device fault" in gw.last_engine_error
    assert gw.last_engine_error.startswith("Traceback")
    assert "injected device fault" in caplog.text


def test_dead_gateway_loop_refuses_new_sessions(tiny_params):
    """After max_engine_errors consecutive failures the loop exits; a
    later submit raises with the cause instead of queueing a session
    nothing will ever serve, and the HTTP front answers 503."""
    from repro.launch.serve import handle_connection, sse_submit

    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32,
                                         max_engine_errors=2))

    def broken_step():
        raise RuntimeError("device lost")

    gw.reactor.step = broken_step

    async def go():
        await gw.start()
        await asyncio.wait_for(asyncio.shield(gw._task), 30.0)
        with pytest.raises(GatewayStopped, match="device lost"):
            await gw.submit(_sessions(1)[0])
        server = await asyncio.start_server(
            lambda r, w: handle_connection(gw, TINY, 0.0625, r, w),
            "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        status, events = await sse_submit("127.0.0.1", port, {"seed": 1})
        server.close()
        await server.wait_closed()
        await gw.stop(timeout_s=5.0)
        return status, events

    status, events = asyncio.run(go())
    assert status == 503 and events == []
    assert gw.counters["engine_errors"] == 2


# ---------------------------------------------------------------------------
# HTTP/SSE front (stdlib asyncio, real sockets)
# ---------------------------------------------------------------------------

def test_http_sse_front_end_to_end(tiny_params):
    """Boot the SSE server on an ephemeral port; three concurrent
    clients stream tokens; /healthz and /stats respond; a tiny
    watermark then yields an observable 429."""
    from repro.launch.serve import (handle_connection, sse_get, sse_submit)

    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))

    async def go():
        await gw.start()
        server = await asyncio.start_server(
            lambda r, w: handle_connection(gw, TINY, 0.0625, r, w),
            "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]

        status, body = await sse_get("127.0.0.1", port, "/healthz")
        assert status == 200 and body == {"ok": True}

        results = await asyncio.gather(*(
            sse_submit("127.0.0.1", port,
                       {"workload": "react", "seed": 20 + i,
                        "token_scale": 0.05})
            for i in range(3)))
        for status, events in results:
            assert status == 200
            assert len(events) > 0
            assert {"session_id", "token", "t", "turn_idx"} <= set(
                events[0])

        status, stats = await sse_get("127.0.0.1", port, "/stats")
        assert status == 200 and stats["completed"] == 3.0

        status, _ = await sse_get("127.0.0.1", port, "/nope")
        assert status == 404

        server.close()
        await server.wait_closed()
        await gw.stop(timeout_s=60.0)

    asyncio.run(go())


def test_http_429_surfaced_over_sse(tiny_params):
    from repro.launch.serve import handle_connection, sse_submit

    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=1, low_watermark=0))

    async def go():
        await gw.start()
        server = await asyncio.start_server(
            lambda r, w: handle_connection(gw, TINY, 0.05, r, w),
            "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        results = await asyncio.gather(*(
            sse_submit("127.0.0.1", port, {"seed": 30 + i})
            for i in range(4)))
        statuses = sorted(st for st, _ in results)
        assert statuses.count(429) >= 1
        assert statuses.count(200) >= 1
        server.close()
        await server.wait_closed()
        await gw.stop(timeout_s=60.0)

    asyncio.run(go())
