"""Runs one cell: builds the served engine and gateway, drives the cell's
traffic through the HTTP/SSE front, and records what the clients saw.

The entry the window drives is the program's own:
``launch/serve.py::handle_connection`` -> ``AgentGateway.submit`` ->
``ServingEngine`` under the planner the configuration names.  The
benchmark adds only its clients, its tool (a sleep of the turn's
scripted latency, timed on the client's clock) and host spans around
the engine's cycle.

Clock: everything is ``time.perf_counter()`` in this one process; the
engine's clock (``engine.clock()``) is the same counter less a fixed
offset, read once.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import threading
import time
from pathlib import Path
from typing import Awaitable, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as tr
from bench.client import Stream, submit
from bench.model import load_config, make_params, model_config

ROOT = Path(__file__).resolve().parents[1]
BENCHMARK_FILE = ROOT / "BENCHMARK.json"
HOST = "127.0.0.1"
# the profiler runs for TRACE_S seconds from TRACE_AT_S into the window
TRACE_AT_S = 5.0
TRACE_S = 8.0
# how long the server may take to end a stopped session's stream
STOP_S = 10.0


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    traffic_name: str
    mix: Dict
    chips: int
    per_layer: List[Dict]
    end_to_end: List[Dict]


def load_cell(name: str, bench_file: Path = BENCHMARK_FILE,
              config_dir: Optional[Path] = None,
              traffic_dir: Optional[Path] = None) -> Cell:
    spec = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    kw_c = {} if config_dir is None else {"config_dir": config_dir}
    kw_t = {} if traffic_dir is None else {"traffic_dir": traffic_dir}

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name=name, config_name=w["config"],
                config=load_config(w["config"], **kw_c),
                traffic_name=w["traffic"],
                mix=tr.load_mix(w["traffic"], **kw_t),
                chips=int(w["chips"]),
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                end_to_end=[m for m in spec["end_to_end"] if applies(m)])


def max_seq(cell: Cell, page: int = 64, b_max: int = 512) -> int:
    """Slot rows: the longest session the mix can make plus the scratch
    row, at least one largest resume bucket, rounded up to a KV page
    (the rule of ``launch/serve.py::engine_config``)."""
    need = max(tr.max_session_tokens(cell.mix["paradigm"],
                                     float(cell.mix["token_scale"])) + 1,
               b_max)
    return -(-need // page) * page


@dataclasses.dataclass
class Observed:
    """What one run saw, on the client's clock."""
    t_start: float
    open_s: float
    close_s: float
    end_s: float
    streams: List[Stream]
    due_cold: List[float]                    # per stream
    tool_done: Dict[Tuple[int, int], float]  # (session_id, turn) -> s
    cycles: List[Tuple[float, float, float]]  # (t0, t1, slots in use)
    dispatch_gaps: List[float]
    sessions: Dict[int, object]              # session_id -> program Session
    engine_offset: float                     # client clock - engine clock
    trace_dir: Optional[str] = None
    trace_span: Optional[Tuple[float, float]] = None
    marker_s: Optional[float] = None         # client clock of the marker


class Server:
    """The system under test, built from the cell's configuration."""

    def __init__(self, cell: Cell, seed: int, dtype=None,
                 annotate: bool = False):
        import jax.numpy as jnp
        from repro.serving.engine import EngineConfig, ServingEngine
        from repro.serving.gateway import AgentGateway, GatewayConfig
        from repro.serving.policies import PLANNERS
        dtype = jnp.float32 if dtype is None else dtype
        c = cell.config
        eng, gw = c["engine"], c["gateway"]
        self.cell = cell
        self.mcfg = model_config(c)
        self.params = make_params(c, seed, dtype)
        ecfg = EngineConfig(
            num_slots=int(eng["num_slots"]),
            cycle_budget=int(eng["cycle_budget"]),
            granularity=int(eng["granularity"]),
            control_interval_s=float(eng["control_interval_s"]),
            tpot_slo_ms=float(eng["tpot_slo_ms"]),
            max_wall_s=float("inf"), spec_k=0, overlap_dispatch=None)
        ecfg.max_seq = max_seq(cell, self.mcfg.kv_page_size, ecfg.b_max)
        self.engine = ServingEngine(self.mcfg, self.params,
                                    PLANNERS[eng["policy"]], ecfg,
                                    dtype=dtype)
        self.tool_done: Dict[Tuple[int, int], float] = {}
        self.gateway = AgentGateway(
            self.engine,
            GatewayConfig(high_watermark=int(gw["high_watermark"]),
                          admission=gw["admission"],
                          tool_policy=gw["tool_policy"]),
            tool_fn=self._tool)
        self.annotate = annotate
        self.cycles: List[Tuple[float, float, float]] = []
        self.in_step = threading.Event()
        self._wrap_engine()

    async def _tool(self, sess, turn_idx: int):
        """The agent's tool: the turn's scripted wait, timed here."""
        await asyncio.sleep(sess.turns[turn_idx].tool_latency_s)
        self.tool_done[(sess.session_id, turn_idx + 1)] = time.perf_counter()
        return None

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self._span(name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)

    def _wrap_engine(self) -> None:
        """Host spans around the engine's cycle and its phases, and one
        sample of the slots in use per cycle."""
        reg = self.engine.telemetry.registry
        slots = reg.gauge("slots_in_use")
        self.gap_hist = reg.histogram("dispatch_gap_s")
        self.on_cycle: List[Callable[[], None]] = []
        eng = self.engine
        self._wrap(eng.planner, "plan", "engine.plan")
        self._wrap(eng.dispatcher, "execute", "engine.execute")
        self._wrap(eng, "_flush_decode", "engine.flush")
        step = self.gateway.reactor.step

        def cycle():
            self.in_step.set()
            try:
                t0 = time.perf_counter()
                with self._span("engine.step"):
                    out = step()
                self.cycles.append((t0, time.perf_counter(), slots.read()))
                for cb in self.on_cycle:
                    cb()
                return out
            finally:
                self.in_step.clear()
        self.gateway.reactor.step = cycle

    def free(self) -> None:
        """Drop every device buffer the program holds."""
        self.engine.pool.cache = None
        self.engine.params = None
        self.params = None
        self.gateway = None
        self.engine = None


class _GapWindow:
    """``dispatch_gap_s`` samples observed between window open and
    close, read on the engine's own thread between cycles."""

    def __init__(self, hist, open_s: float, close_s: float):
        self.h, self.open_s, self.close_s = hist, open_s, close_s
        self.n_open: Optional[int] = None
        self.samples: Optional[List[float]] = None

    def __call__(self) -> None:
        now = time.perf_counter()
        if self.n_open is None and now >= self.open_s:
            self.n_open = self.h.total
        if self.samples is None and self.n_open is not None \
                and now >= self.close_s:
            n = self.h.total - self.n_open
            xs = list(self.h.samples)
            self.samples = xs[len(xs) - min(n, len(xs)):] if n else []


async def run_agent(pool: List[int], at: float, close_s: float,
                    serve: Callable[[int, float], Awaitable],
                    clock: Callable[[], float]) -> None:
    """One closed-loop agent: serve the sessions of ``pool`` in turn,
    the first due at ``at`` and each next one due when the previous
    stream has ended, however it ended; none starts at or after
    ``close_s``."""
    for spec_seed in pool:
        if at >= close_s:
            return
        try:
            await serve(spec_seed, at)
        except Exception:   # the stream, cut or unread, counts as failed
            pass
        at = clock()
    if at < close_s:
        raise tr.PoolExhausted(f"a closed-loop agent ran through its "
                               f"{len(pool)} sessions before the window "
                               f"closed")


async def drive(srv: Server, schedule: tr.Schedule,
                plans: Dict[int, tr.SessionPlan], seconds: float,
                trace_dir: Optional[str] = None,
                on_open: Optional[Callable[[], None]] = None) -> Observed:
    """Serve the schedule: lead-in, the window, then the drain."""
    import jax
    from repro.launch.serve import handle_connection
    mix = srv.cell.mix
    gateway, engine = srv.gateway, srv.engine
    await gateway.start()
    server = await asyncio.start_server(
        lambda r, w: handle_connection(
            gateway, srv.mcfg, float(mix["token_scale"]), r, w), HOST, 0)
    port = server.sockets[0].getsockname()[1]
    clock = time.perf_counter
    offset = clock() - engine.clock()
    t_start = clock()
    open_s = t_start + float(mix["lead_in_s"])
    close_s = open_s + float(seconds)
    gaps = _GapWindow(srv.gap_hist, open_s, close_s)
    srv.on_cycle[:] = [gaps]
    srv.cycles.clear()
    srv.tool_done.clear()
    streams: List[Stream] = []
    due: List[float] = []
    tasks: List[asyncio.Task] = []

    async def one(spec_seed: int, at: float) -> Stream:
        st = Stream(spec_seed)
        streams.append(st)
        due.append(at)
        delay = at - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            return await submit(HOST, port, tr.request_spec(mix, spec_seed),
                                st)
        except Exception:
            st.end = "cut"      # nothing more comes: not pending, failed
            raise

    if isinstance(schedule, tr.ClosedLoop):
        tasks += [asyncio.create_task(run_agent(
            pool, t_start + first, close_s, one, clock))
            for first, pool in zip(schedule.first_s, schedule.pools)]
    else:
        for a in schedule:
            if t_start + a.at_s < close_s:
                tasks.append(asyncio.create_task(
                    one(a.spec_seed, t_start + a.at_s)))

    await asyncio.sleep(max(0.0, open_s - clock()))
    if on_open is not None:
        on_open()
    trace_span = marker_s = None
    if trace_dir is not None and seconds >= TRACE_AT_S + TRACE_S:
        await asyncio.sleep(TRACE_AT_S)
        await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
        t_tr0 = clock()
        with jax.profiler.TraceAnnotation("bench.marker"):
            marker_s = clock()
        await asyncio.sleep(TRACE_S)
        t_tr1 = clock()
        await asyncio.to_thread(jax.profiler.stop_trace)
        trace_span = (t_tr0, t_tr1)
    await asyncio.sleep(max(0.0, close_s - clock()))
    deadline = close_s + float(mix["drain_s"])
    while clock() < deadline and _pending(streams, due, srv.tool_done,
                                         plans, open_s, close_s):
        await asyncio.sleep(0.05)
    end_s = clock()
    await gateway.stop(timeout_s=0.0)
    while srv.in_step.is_set():
        await asyncio.sleep(0.01)
    live = [t for t in tasks if not t.done()]
    if live:
        await asyncio.wait(live, timeout=STOP_S)
    for t in tasks:
        t.cancel()
    ended = await asyncio.gather(*tasks, return_exceptions=True)
    server.close()
    await server.wait_closed()
    for e in ended:
        if isinstance(e, tr.PoolExhausted):
            raise e
    sessions = {s.session_id: s for s in
                list(gateway.completed_sessions)
                + list(gateway.failed_sessions)}
    return Observed(t_start=t_start, open_s=open_s, close_s=close_s,
                    end_s=end_s, streams=streams, due_cold=due,
                    tool_done=dict(srv.tool_done), cycles=list(srv.cycles),
                    dispatch_gaps=gaps.samples or [], sessions=sessions,
                    engine_offset=offset, trace_dir=trace_dir,
                    trace_span=trace_span, marker_s=marker_s)


def _turn_events(st: Stream) -> Dict[int, List[int]]:
    by_turn: Dict[int, List[int]] = {}
    for i, ev in enumerate(st.events):
        if not ev.get("error"):
            by_turn.setdefault(ev["turn_idx"], []).append(i)
    return by_turn


def _pending(streams, due, tool_done, plans, open_s, close_s) -> bool:
    """Whether a request due inside the window is still unfinished."""
    for st, d in zip(streams, due):
        if st.end or (st.status not in (0, 200)):
            continue
        plan = plans[st.spec_seed]
        turns = _turn_events(st)
        sid = st.session_id
        for k, turn in enumerate(plan.turns):
            at = d if k == 0 else tool_done.get((sid, k))
            if at is None or not open_s <= at < close_s:
                continue
            if len(turns.get(k, [])) < turn.decode_len:
                return True
    return False


@dataclasses.dataclass
class Request:
    """One turn: a cold prefill or a resume, then its decode burst."""
    stream: int
    turn: int
    due_s: float
    first_s: Optional[float]
    done_s: Optional[float]
    failed: bool


def requests_in_window(obs: Observed, plans: Dict[int, tr.SessionPlan],
                       ) -> Tuple[List[Request], int, List[int]]:
    """Requests due in the window, sessions refused there, and the
    indices of finished turns whose token count or order is wrong."""
    out: List[Request] = []
    refused = 0
    bad: List[int] = []
    for i, (st, d) in enumerate(zip(obs.streams, obs.due_cold)):
        plan = plans[st.spec_seed]
        in_cold = obs.open_s <= d < obs.close_s
        if st.status not in (0, 200):
            refused += in_cold
            continue
        turns = _turn_events(st)
        sid = st.session_id
        for k, turn in enumerate(plan.turns):
            idx = turns.get(k, [])
            if len(idx) >= turn.decode_len and [
                    st.events[j]["index"] for j in idx] != list(
                    range(turn.decode_len)):
                bad.append(i)
            at = d if k == 0 else obs.tool_done.get((sid, k))
            if at is None or not obs.open_s <= at < obs.close_s:
                continue
            first = st.received_s[idx[0]] if idx else None
            done = (st.received_s[idx[turn.decode_len - 1]]
                    if len(idx) >= turn.decode_len else None)
            out.append(Request(i, k, at, first, done, done is None))
    return out, refused, bad


def finished_turns(st: Stream, plan: tr.SessionPlan) -> List[List[int]]:
    """Tokens of the session's finished turns, in order, up to the
    first unfinished one."""
    turns = _turn_events(st)
    out = []
    for k, turn in enumerate(plan.turns):
        idx = turns.get(k, [])
        if len(idx) < turn.decode_len:
            break
        out.append([st.events[j]["token"] for j in idx[:turn.decode_len]])
    return out


def percentile(xs, q: float) -> Optional[float]:
    xs = [x for x in xs if x is not None]
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def end_to_end(obs: Observed, reqs: List[Request]) -> Dict[str, float]:
    """Client-side statistics of the window: time to first token over
    the requests due in it that received one (a request that received
    none is counted in ``failed``, not here: its wait would be the drain
    limit's), gaps between consecutive tokens of one turn that end in
    it, and tokens received in it per second."""
    ttft = [r.first_s - r.due_s for r in reqs if r.first_s is not None]
    gaps, tokens = [], 0
    for st in obs.streams:
        turns = _turn_events(st)
        for idx in turns.values():
            ts = [st.received_s[j] for j in idx]
            tokens += sum(obs.open_s <= t < obs.close_s for t in ts)
            gaps += [b - a for a, b in zip(ts, ts[1:])
                     if obs.open_s <= b < obs.close_s]
    out = {"output_tok_s": tokens / (obs.close_s - obs.open_s),
           "requests": len(reqs)}
    cold = [r.first_s - r.due_s for r in reqs
            if r.first_s is not None and r.turn == 0]
    resume = [r.first_s - r.due_s for r in reqs
              if r.first_s is not None and r.turn > 0]
    for name, xs in (("ttft", ttft), ("tpot", gaps), ("ttft_cold", cold),
                     ("ttft_resume", resume)):
        if xs:
            out[f"{name}_mean_ms"] = 1e3 * float(np.mean(xs))
            for q in (50, 90, 95):
                out[f"{name}_p{q}_ms"] = 1e3 * percentile(xs, q)
    return out
