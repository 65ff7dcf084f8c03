"""Unified telemetry layer (DESIGN.md §11): metrics registry, span
lifecycle, Prometheus text format, Chrome trace export, and the
span-based latency reconstruction cross-check.

The two load-bearing invariants:

  * every terminal session state — DONE, ABORTED (tool failure, step
    fault, disconnect, deadline, kv_exhausted) — closes all of the
    session's spans and its slot span, so ``open_span_count() == 0``
    after any drained run;
  * the engine's stats surface is ONE registry: ``engine.stats()``,
    ``gateway.stats()`` and the Prometheus rendering are views of the
    same object, so their key sets cannot drift.
"""
import asyncio
import dataclasses
import glob
import math
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.faults import FaultPlan, FaultSpec, drive_chaos
from repro.serving.gateway import (AgentGateway, GatewayConfig,
                                   drive_open_loop)
from repro.serving.metrics import collect_tpots, collect_ttfts
from repro.serving.policies import POLICIES
from repro.serving.telemetry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, RegistryDict,
                                     SpanTracer, Telemetry, _main,
                                     export_trace, parse_prometheus_text,
                                     reconstruct_latency,
                                     validate_trace_events)
from repro.serving.workload import make_open_loop_workload

TINY = ModelConfig(name="tiny-telemetry", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=128, tie_embeddings=True, source="test")
TINY_PAGED = dataclasses.replace(TINY, name="tiny-telemetry-paged",
                                 kv_layout="paged", kv_page_size=64)


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _engine(params, *, cfg=TINY, num_slots=4, **over):
    ecfg = EngineConfig(num_slots=num_slots, max_seq=512, cycle_budget=80,
                        granularity=8, b_min=8, b_max=128, b_init=32,
                        delta_b=8, control_interval_s=0.05,
                        max_wall_s=float("inf"), **over)
    return ServingEngine(cfg, params, POLICIES["agentserve"], ecfg)


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


def _terminal_markers(tracer):
    """sid -> (terminal phase, abort reason) from the span ring."""
    out = {}
    for track, sid, name, _t0, _t1, args in tracer.spans:
        if track == "session" and name in ("DONE", "ABORTED"):
            out[sid] = (name, (args or {}).get("reason"))
    return out


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("requests", help="total requests")
    assert reg.counter("requests") is c         # get-or-create
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = reg.gauge("depth")
    g.set(7.0)
    assert g.read() == 7.0
    reg.gauge("depth", fn=lambda: 9.0)          # re-register binds the fn
    assert g.read() == 9.0
    with pytest.raises(ValueError):
        reg.gauge("requests")                   # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name with spaces")
    assert [m.name for m in reg.metrics()] == ["requests", "depth"]


def test_registry_snapshot_is_flat_and_nan_free():
    reg = MetricsRegistry()
    reg.counter("hits").inc(2)
    reg.gauge("occ", fn=lambda: 0.5)
    h = reg.histogram("lat_s")
    snap = reg.snapshot()                       # histogram still empty
    assert snap["hits"] == 2.0 and snap["occ"] == 0.5
    assert snap["lat_s_count"] == 0.0 and snap["lat_s_p95"] == 0.0
    h.observe(0.01)
    h.observe(0.02, count=3)                    # weighted flush-style call
    snap = reg.snapshot()
    assert snap["lat_s_count"] == 4.0
    assert snap["lat_s_sum"] == pytest.approx(0.01 + 3 * 0.02)
    assert all(isinstance(v, float) and not math.isnan(v)
               for v in snap.values())


def test_histogram_percentiles_from_samples():
    h = Histogram("t")
    for v in np.linspace(0.001, 0.1, 100):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(0.05, rel=0.05)
    assert h.percentile(99) == pytest.approx(0.1, rel=0.05)
    assert h.total == 100


def test_registry_dict_keeps_dict_syntax_and_rename():
    reg = MetricsRegistry()
    d = RegistryDict(reg, {"steps": 0, "aborted": 0},
                     rename={"aborted": "engine_aborted"})
    d["steps"] += 5                             # legacy call-site syntax
    d["aborted"] += 1
    assert d["steps"] == 5 and dict(d) == {"steps": 5, "aborted": 1}
    snap = reg.snapshot()                       # renamed in the registry,
    assert snap["steps"] == 5.0                 # plain at the call site
    assert snap["engine_aborted"] == 1.0 and "aborted" not in snap
    with pytest.raises(KeyError):
        d["unknown"] += 1                       # keys fixed at construction
    with pytest.raises(TypeError):
        del d["steps"]


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def test_prometheus_text_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs", help="requests served").inc(3)
    reg.gauge("q", fn=lambda: 2.0)
    h = reg.histogram("lat_s", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)
    h.observe(0.05, count=2)
    h.observe(5.0)                              # above every finite bucket
    text = reg.prometheus_text()
    assert "# TYPE reqs counter" in text
    assert "# HELP reqs requests served" in text
    samples = parse_prometheus_text(text)
    assert samples["reqs"] == 3.0 and samples["q"] == 2.0
    assert samples['lat_s_bucket{le="0.01"}'] == 1.0      # cumulative
    assert samples['lat_s_bucket{le="0.1"}'] == 3.0
    assert samples['lat_s_bucket{le="+Inf"}'] == 4.0
    assert samples["lat_s_count"] == 4.0
    assert samples["lat_s_sum"] == pytest.approx(0.005 + 0.1 + 5.0)


@pytest.mark.parametrize("bad", [
    "# TYPE x wibble\nx 1\n",                   # unknown type
    "no_type_header 1\n",                       # sample precedes TYPE
    "# TYPE x counter\nx notanumber\n",         # bad value
    "# WAT x counter\n",                        # malformed comment
    '# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="2"} 3\n',
])
def test_prometheus_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad)


# ---------------------------------------------------------------------------
# span tracer + trace_event export
# ---------------------------------------------------------------------------

def test_span_tracer_lifecycle_and_terminal_markers():
    tr = SpanTracer()
    tr.transition(7, "QUEUED", 0.0)
    tr.slot_bind(0, 7, 0.1)
    tr.transition(7, "PREFILL", 0.1, turn=0)
    tr.transition(7, "DECODE", 0.2, tokens=5)
    tr.child(7, "tool_attempt", 0.3, 0.35, attempt=0, outcome="ok")
    assert tr.open_span_count() == 2            # session + slot
    tr.slot_free(0, 0.4)
    tr.transition(7, "DONE", 0.4)
    assert tr.open_span_count() == 0
    assert _terminal_markers(tr) == {7: ("DONE", None)}
    # terminal marker is zero-length, QUEUED->PREFILL->DECODE all closed
    names = [s[2] for s in tr.spans if s[0] == "session"]
    assert names == ["QUEUED", "PREFILL", "tool_attempt", "DECODE", "DONE"]

    tr2 = SpanTracer(spans_max=4)               # bounded ring
    for i in range(10):
        tr2.cycle(i, "decode", float(i), float(i) + 0.5)
    assert len(tr2.spans) == 4


def test_trace_export_validates_and_keeps_open_spans_loadable():
    tr = SpanTracer()
    tr.transition(0, "QUEUED", 0.0)
    tr.transition(0, "PREFILL", 0.5, turn=0)    # stays open: live dump
    tr.slot_bind(2, 0, 0.5)
    tr.cycle(3, "mega+admit", 0.1, 0.2, planned=64, actual=60)
    doc = export_trace(tr)
    n = validate_trace_events(doc)
    assert n == len(doc["traceEvents"])
    phases = [ev["ph"] for ev in doc["traceEvents"]]
    assert phases.count("B") == 2               # open session + slot span
    cyc = [ev for ev in doc["traceEvents"]
           if ev["ph"] == "X" and ev["pid"] == 3]
    assert cyc[0]["args"]["plan_id"] == 3
    assert doc["displayTimeUnit"] == "ms"


@pytest.mark.parametrize("bad", [
    {"foo": 1},                                  # no traceEvents
    {"traceEvents": []},                         # empty
    {"traceEvents": [{"ph": "Z", "pid": 1, "tid": 1, "name": "x"}]},
    {"traceEvents": [{"ph": "X", "pid": "a", "tid": 1, "name": "x",
                      "ts": 0, "dur": 1}]},      # non-int pid
    {"traceEvents": [{"ph": "X", "pid": 1, "tid": 1, "name": "x",
                      "ts": 0, "dur": -5}]},     # negative dur
    {"traceEvents": [{"ph": "B", "pid": 1, "tid": 1, "name": "x"}]},
])
def test_trace_validation_rejects_malformed(bad):
    with pytest.raises(ValueError):
        validate_trace_events(bad)


# ---------------------------------------------------------------------------
# engine integration: spans close, latency reconstructs, stats unify
# ---------------------------------------------------------------------------

def test_normal_run_spans_close_and_latency_reconstructs(
        tiny_params, tmp_path):
    """A clean multi-agent gateway run: every session timeline reaches
    DONE, zero spans leak, and TTFT/TPOT recovered *from the spans
    alone* match metrics.py within the 1% acceptance bound.  Cycle
    spans correlate with the plan journal by plan id."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    sessions = _sessions(4, rate=8.0)
    run = _drive(gw, sessions)
    assert len(run.completed) == 4

    tr = eng.telemetry.tracer
    assert tr.open_span_count() == 0
    marks = _terminal_markers(tr)
    assert all(marks[s.session_id][0] == "DONE" for s in sessions)
    # tool attempts ride the session track as child spans
    tools = [s for s in tr.spans if s[2] == "tool_attempt"]
    assert len(tools) == sum(len(s.turns) - 1 for s in sessions)
    assert all((s[5] or {}).get("outcome") == "ok" for s in tools)

    # --- the acceptance cross-check: spans vs metrics.py ---------------
    span_ttfts, span_tpot = reconstruct_latency(tr.spans)
    want_ttfts = collect_ttfts(run.completed)
    want_tpots = collect_tpots(run.completed)
    assert len(span_ttfts) == len(want_ttfts)
    assert np.mean(span_ttfts) == pytest.approx(
        np.mean(want_ttfts), rel=0.01)
    assert span_tpot == pytest.approx(float(np.mean(want_tpots)), rel=0.01)

    # --- plan-journal correlation --------------------------------------
    cycle_ids = {(s[5] or {})["plan_id"] for s in tr.spans
                 if s[0] == "cycle"}
    journal_ids = {r.plan.plan_id for r in eng.journal.records}
    assert cycle_ids and cycle_ids <= journal_ids

    # --- hot-path histograms populated ---------------------------------
    snap = eng.stats()
    assert snap["prefill_run_s_count"] >= len(want_ttfts)
    assert snap["dispatch_gap_s_count"] > 0
    assert snap["device_wait_s_count"] > 0

    # --- the dumped trace validates end to end -------------------------
    path = str(tmp_path / "trace.json")
    assert eng.telemetry.export_trace(path) > 0
    assert _main([path]) == 0


def test_stats_views_are_one_registry(tiny_params, tmp_path):
    """engine.stats(), gateway.stats() and the Prometheus rendering are
    views of one registry — identical key sets by construction, and the
    exposition text parses with every counter/gauge present."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    run = _drive(gw, _sessions(2, rate=16.0))
    assert len(run.completed) == 2

    es, gs = eng.stats(), gw.stats()
    assert set(es) == set(gs)
    assert es == gs                             # same registry, same values
    text = eng.telemetry.registry.prometheus_text()
    samples = parse_prometheus_text(text)
    for m in eng.telemetry.registry.metrics():
        if isinstance(m, (Counter, Gauge)):
            assert m.name in samples, f"{m.name} missing from /metrics"
        else:
            assert f"{m.name}_count" in samples
    # legacy dict facades still read/write through the same registry
    assert gs["fused_steps"] == eng.hotpath_stats["fused_steps"]
    assert gs["completed"] == gw.counters["completed"] == 2
    mpath = tmp_path / "metrics.txt"
    mpath.write_text(text)
    tpath = tmp_path / "trace.json"
    eng.telemetry.export_trace(str(tpath))
    assert _main([str(tpath), str(mpath)]) == 0


def test_faulted_terminals_close_all_spans(tiny_params):
    """Chaos run mixing tool-failure, step-fault, disconnect and an
    injected page-exhaustion burst over the paged engine with
    kv_defer_limit=0 (first deferral -> kv_exhausted abort): every
    terminal path must close its session and slot spans."""
    eng = _engine(tiny_params, cfg=TINY_PAGED, kv_defer_limit=0)
    plan = FaultPlan((
        FaultSpec(kind="tool_hang", session_id=1),
        FaultSpec(kind="step_error", session_id=2, at_count=2),
        FaultSpec(kind="disconnect", session_id=3, at_token=3),
        FaultSpec(kind="page_exhaustion", at_count=6, count=1),
    ), seed=3)
    gw = AgentGateway(eng, GatewayConfig(
        high_watermark=32, tool_timeout_s=0.5, tool_retries=1,
        tool_backoff_base_s=0.01, tool_failure_policy="abort"),
        faults=plan)
    sessions = _sessions(5)
    arrivals = [0.05 * i for i in range(5)]

    async def go():
        await gw.start()
        run = await asyncio.wait_for(
            drive_chaos(gw, sessions, arrivals, plan), timeout=120.0)
        await gw.stop(timeout_s=60.0)
        return run

    run = asyncio.run(go())
    assert run.wedged() == 0
    tr = eng.telemetry.tracer
    assert tr.open_span_count() == 0, \
        f"leaked spans: {tr.open_spans()}"
    marks = _terminal_markers(tr)
    reasons = {sid: r for sid, (ph, r) in marks.items() if ph == "ABORTED"}
    for s in run.aborted:
        assert reasons.get(s.session_id) == s.abort_reason
    for s in run.completed:
        assert marks[s.session_id][0] == "DONE"
    # the exhaustion burst actually fired and attributed its abort
    assert plan.injected["page_exhaustion"] >= 1
    assert eng.hotpath_stats["kv_deferred"] >= 1
    assert "kv_exhausted" in reasons.values()
    # a faulted run's trace still exports clean
    validate_trace_events(export_trace(tr))


def test_deadline_abort_closes_spans(tiny_params):
    """A submit-time deadline in the past aborts on the next cycle; the
    ABORTED marker carries reason='deadline' and nothing leaks."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    doomed, fine = _sessions(2, seed=8)

    async def go():
        await gw.start()
        res_d = await gw.submit(doomed, deadline_s=0.0)
        res_f = await gw.submit(fine, deadline_s=600.0)
        evs_d = [ev async for ev in res_d.events()]
        evs_f = [ev async for ev in res_f.events()]
        await gw.stop(timeout_s=60.0)
        return evs_d, evs_f

    evs_d, evs_f = asyncio.run(go())
    assert evs_d[-1].abort_reason == "deadline"
    assert not any(ev.error for ev in evs_f)
    tr = eng.telemetry.tracer
    assert tr.open_span_count() == 0
    marks = _terminal_markers(tr)
    assert marks[doomed.session_id] == ("ABORTED", "deadline")
    assert marks[fine.session_id][0] == "DONE"


def test_telemetry_off_still_serves_and_stats(tiny_params):
    """telemetry=False drops the tracer (export is a hard error) but
    the registry — the stats surface — stays fully live."""
    eng = _engine(tiny_params, telemetry=False)
    assert eng.telemetry.tracer is None
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    run = _drive(gw, _sessions(2, rate=16.0))
    assert len(run.completed) == 2
    assert eng.stats()["completed"] == 2.0
    assert eng.stats()["dispatch_gap_s_count"] > 0
    with pytest.raises(RuntimeError):
        eng.telemetry.export_trace("/tmp/nope.json")


def test_telemetry_shared_registry_two_gateways(tiny_params):
    """Two gateways over one engine must not collide in the registry:
    get-or-create returns the same counters and the callback gauges
    rebind to the latest gateway."""
    eng = _engine(tiny_params)
    gw1 = AgentGateway(eng, GatewayConfig(high_watermark=32))
    gw2 = AgentGateway(eng, GatewayConfig(high_watermark=32))
    gw1.counters["completed"] += 1
    assert gw2.counters["completed"] == 1       # same underlying counter
    assert eng.stats()["completed"] == 1.0


def test_run_resets_spans_between_runs(tiny_params):
    """Closed-loop ServingEngine.run() starts a fresh trace per run —
    spans from a previous run never bleed into the next timeline."""
    eng = _engine(tiny_params)

    def cohort(seed):
        ss = make_open_loop_workload(
            2, workload="react", vocab_size=TINY.vocab_size,
            token_scale=0.0625, seed=seed, rate_rps=1000.0)
        for s in ss:
            s.ready_s = 0.0
        return ss

    eng.run(cohort(1))
    tr = eng.telemetry.tracer
    assert tr.open_span_count() == 0
    assert sum(1 for s in tr.spans
               if s[0] == "session" and s[2] == "DONE") == 2

    eng.run(cohort(2))                          # same engine, fresh trace
    assert tr.open_span_count() == 0
    assert sum(1 for s in tr.spans
               if s[0] == "session" and s[2] == "DONE") == 2


def test_dispatch_gap_excludes_block_time(tiny_params):
    """``dispatch_gap_s`` measures host-side overhead between
    consecutive decode submissions EXCLUDING time the host spent
    blocked on device results in between (DESIGN.md §12): under
    overlapped dispatch the block happens after the next submission,
    and counting it would make the gap metric regress exactly when the
    double buffer is doing its job.  The engine subtracts the
    accumulated ``_block_since_dispatch`` from each raw gap."""
    eng = _engine(tiny_params)
    hist = eng._h_gap
    assert "block" in hist.help          # semantics documented in-help
    base_n, base_sum = hist.total, hist.sum

    eng._last_dispatch_t = 100.0
    eng._block_since_dispatch = 0.25     # host blocked 250 ms in between
    eng._observe_gap(100.4)              # raw gap 400 ms
    assert hist.total == base_n + 1
    assert hist.sum - base_sum == pytest.approx(0.15)   # 400 - 250 ms

    # blocking longer than the raw gap must clamp at zero, not go
    # negative (the detached-window drain can exceed the next gap)
    eng._last_dispatch_t = 200.0
    eng._block_since_dispatch = 1.0
    eng._observe_gap(200.1)
    assert hist.sum - base_sum == pytest.approx(0.15)
    assert hist.samples[-1] == 0.0


# ---------------------------------------------------------------------------
# per-request stage stamps, emission stamps, profiler spans
# ---------------------------------------------------------------------------

def _stages(sess):
    """Per finished request: (staged, ready, admitted, dispatched,
    first token)."""
    n = len(sess.first_token_s)
    return list(zip(sess.staged_s[:n], sess.readied_s[:n],
                    sess.request_arrivals[:n], sess.dispatched_s[:n],
                    sess.first_token_s))


@pytest.mark.parametrize("admission", ["reject", "queue"])
def test_stage_stamps_ordered_and_add_up(tiny_params, admission):
    """Every finished request of a gateway run carries its five stage
    stamps in order, and the four parts (gateway, admission, planner,
    prefill run) add up to staged -> first token.  The stage histograms
    count one observation per request."""
    eng = _engine(tiny_params, num_slots=2)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32,
                                         admission=admission))
    sessions = _sessions(4, rate=16.0)
    run = _drive(gw, sessions)
    assert len(run.completed) == 4
    n_req = 0
    for sess in run.completed:
        assert len(sess.staged_s) == len(sess.readied_s) == len(
            sess.request_arrivals) == len(sess.dispatched_s) == len(
            sess.first_token_s) == len(sess.turns)
        for st, rd, adm, disp, first in _stages(sess):
            assert st <= rd <= adm <= disp <= first
            parts = (rd - st) + (adm - rd) + (disp - adm) + (first - disp)
            assert parts == pytest.approx(first - st, abs=1e-9)
            n_req += 1
        assert sess.queue_delays_s == pytest.approx(
            [a - r for a, r in zip(sess.request_arrivals,
                                   sess.readied_s)])
    snap = eng.stats()
    for name in ("gateway_wait_s", "prefill_queue_s", "prefill_run_s"):
        assert snap[f"{name}_count"] == n_req, name
    assert snap["token_hold_s_count"] > 0


def test_stage_histograms_replace_ttft_tpot(tiny_params):
    """The stage histograms are on every stats surface; the unread
    ``ttft_s`` / ``tpot_s`` are gone from the registry and /metrics."""
    eng = _engine(tiny_params)
    snap = eng.stats()
    samples = parse_prometheus_text(eng.telemetry.registry.prometheus_text())
    for name in ("gateway_wait_s", "prefill_queue_s", "prefill_run_s",
                 "token_hold_s"):
        assert f"{name}_count" in snap and f"{name}_count" in samples
    for gone in ("ttft_s", "tpot_s"):
        assert f"{gone}_count" not in snap
        assert f"{gone}_count" not in samples


def test_run_without_gateway_has_no_staged_stamps(tiny_params):
    """The closed-loop ``run()`` stamps ready, admitted, dispatched and
    first token; it has no gateway, so no staging wait is observed."""
    eng = _engine(tiny_params)
    sessions = _sessions(2, rate=16.0)
    eng.run(sessions)
    for sess in sessions:
        assert sess.staged_s == []
        for rd, adm, disp, first in zip(sess.readied_s,
                                        sess.request_arrivals,
                                        sess.dispatched_s,
                                        sess.first_token_s):
            assert rd <= adm <= disp <= first
    assert eng.stats()["gateway_wait_s_count"] == 0


def test_token_events_carry_emission_stamp(tiny_params):
    """``t_emit`` (host read) is never before ``t`` (the interpolated
    device stamp); a first token is read when it is made."""
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    run = _drive(gw, _sessions(3, rate=16.0))
    evs = [ev for _, ev in run.events]
    assert evs and any(not ev.first for ev in evs)
    for ev in evs:
        assert ev.t_emit >= ev.t
        if ev.first:
            assert ev.t_emit == ev.t
    # a decode window's tokens share one host read
    assert any(ev.t_emit > ev.t for ev in evs if not ev.first)


def test_profiler_trace_holds_program_spans(tiny_params, tmp_path):
    """A profiler trace of a gateway stepping, with nothing wrapped from
    outside, holds the program's own host spans."""
    from jax.profiler import ProfileData
    eng = _engine(tiny_params)
    gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        run = _drive(gw, _sessions(2, rate=16.0))
    finally:
        jax.profiler.stop_trace()
    assert len(run.completed) == 2
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(files[-1]).planes
             for line in plane.lines for e in line.events}
    for want in ("engine.step", "engine.plan", "engine.execute",
                 "engine.flush", "engine.block", "engine.reactor.ops",
                 "engine.reactor.route", "engine.dispatch.decode",
                 "engine.dispatch.prefill"):
        assert want in names, want
