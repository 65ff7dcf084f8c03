"""Online serving gateway: async streaming sessions over the reactor.

This is the layer that makes the repro an *online* system (DESIGN.md
§6): live requests arrive at any time, stream their tokens back as they
are decoded, wait out their tool calls under the gateway's clock, and
are shed with 429-style rejections when open-loop pressure crosses the
admission watermark.  The gateway owns:

  * the **reactor loop** — a single asyncio task that serialises all
    engine access: it ingests queued submissions/resumes between
    cycles, advances the engine one ``step()`` at a time (in a worker
    thread, so the event loop keeps serving tool timers and HTTP
    clients during device work), and fans the cycle's ``TokenEvent``s
    out to per-session asyncio queues;
  * the **session state machine** — PREFILL → DECODE → TOOL_WAIT →
    RESUME → DONE.  ``turn_end`` events move a session into TOOL_WAIT,
    where the *gateway* (not the engine) runs the tool: either the
    configured ``tool_fn`` or an ``asyncio.sleep`` of the turn's
    simulated latency.  On completion the session re-enters the engine
    via ``reactor.resume`` (RESUME) and decodes its next turn with its
    KV intact;
  * the **KV-slot policy** during TOOL_WAIT — ``hold`` keeps the slot
    (lowest resume latency), ``release`` parks the slot's cache rows
    on device and frees it when another session is blocked on slot
    exhaustion (higher utilisation; the restore is lossless);
  * **admission** — a hysteretic ``WatermarkGate`` over queue + slot
    occupancy; ``reject`` mode sheds immediately (429), ``queue`` mode
    waits briefly for the gate to reopen before shedding;
  * the **fault domain** (DESIGN.md §10) — per-session isolation: a
    tool failure retries with timeout + exponential backoff and on
    exhaustion either finishes the turn with scripted tokens or aborts
    the session; an engine-side fault quarantines exactly the offending
    session (``abort_session``) and its stream terminates with an error
    event; client disconnects (``LiveSession.cancel()``) reclaim the
    slot/pages promptly; KV-pressure deferrals tighten the admission
    gate; and a crashed reactor loop fails every live stream loudly
    instead of leaving consumers awaiting forever.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import enum
import itertools
import logging
import traceback
from typing import AsyncIterator, Awaitable, Callable, Deque, Dict, List, \
    Optional, Tuple, Union

import numpy as np

from repro.core.admission import WatermarkGate
from repro.serving.reactor import EngineReactor, RequestHandle, TokenEvent
from repro.serving.request import Session, SessionState
from repro.serving.telemetry import RegistryDict, span

# tool_fn(session, completed_turn_idx) -> optional replacement tokens
# for the *next* turn's prefill (a real tool's output); None keeps the
# scripted tokens.
ToolFn = Callable[[Session, int], Awaitable[Optional[np.ndarray]]]

log = logging.getLogger(__name__)


class GatewayStopped(RuntimeError):
    """``submit`` after the reactor loop died (``max_engine_errors``
    consecutive failures): nothing would ever serve the session."""


@dataclasses.dataclass
class GatewayConfig:
    high_watermark: int = 8          # occupancy that closes the gate
    low_watermark: int = -1          # reopen level (default high // 2)
    admission: str = "reject"        # reject -> immediate 429 | queue
    max_queue: int = 32              # queue mode: max concurrent waiters
    queue_timeout_s: float = 2.0     # queue mode: wait bound before 429
    tool_policy: str = "hold"        # hold | release (KV slot in TOOL_WAIT)
    idle_sleep_s: float = 0.001      # reactor loop sleep when no work
    step_in_thread: bool = True      # run engine.step off the event loop
    completed_history: int = 10_000  # finished Sessions kept for reports
    # --- tool-call resilience (DESIGN.md §10) -------------------------
    tool_timeout_s: float = 30.0     # per-attempt tool call bound
    tool_retries: int = 2            # retries after the first attempt
    tool_backoff_base_s: float = 0.05   # backoff = base * 2^attempt ...
    tool_backoff_max_s: float = 2.0     # ... capped here ...
    tool_backoff_jitter: float = 0.25   # ... +- this fraction (seeded rng)
    tool_failure_policy: str = "finish_turn"  # on retry exhaustion:
    #   finish_turn -> resume with the scripted next-turn tokens
    #   abort       -> abort the session (terminal error event)
    seed: int = 0                    # backoff-jitter rng seed
    # --- deadlines & degradation --------------------------------------
    default_deadline_s: float = float("inf")  # relative SLO deadline
    #                                  applied at submit when the session
    #                                  has none (inf = no deadline)
    kv_pressure_tighten: int = -1    # watermark tightening while the
    #                                  engine reports KVExhausted
    #                                  deferrals (-1 = auto: high // 2)
    kv_pressure_window: int = 50     # engine cycles a deferral stays hot
    max_engine_errors: int = 8       # consecutive failed loop iterations
    #                                  before the gateway fails all live
    #                                  sessions and stops (never hangs)


class GatewayState(enum.Enum):
    PREFILL = "prefill"
    DECODE = "decode"
    TOOL_WAIT = "tool_wait"
    RESUME = "resume"
    DONE = "done"
    FAILED = "failed"                # aborted: fault/deadline/disconnect


@dataclasses.dataclass
class Rejected:
    """429-style admission result."""
    status: int = 429
    reason: str = "admission watermark exceeded"
    occupancy: int = 0


class LiveSession:
    """Gateway-owned handle for one streaming agent session."""

    def __init__(self, session: Session, gateway: "AgentGateway"):
        self.session = session
        self._gw = gateway
        self.handle: Optional[RequestHandle] = None
        self.state = GatewayState.PREFILL
        self.queue: "asyncio.Queue[Optional[TokenEvent]]" = asyncio.Queue()
        self.received: List[TokenEvent] = []
        self.cancelled = False
        self.tool_task: Optional[asyncio.Task] = None

    @property
    def session_id(self) -> int:
        return self.session.session_id

    def cancel(self, reason: str = "disconnected") -> None:
        """Client-side abort (disconnect): stage an abort op for the
        reactor loop — the engine reclaims the slot/pages promptly and
        the stream terminates with an error event.  Idempotent; a no-op
        once the session is terminal."""
        if self.cancelled or self.state in (GatewayState.DONE,
                                            GatewayState.FAILED):
            return
        self.cancelled = True
        self._gw.counters["cancelled"] += 1
        self._gw._ops.append(("abort", self, reason))

    async def events(self) -> AsyncIterator[TokenEvent]:
        """Stream this session's tokens as they are decoded; terminates
        after the final turn's last token — or after a terminal *error*
        event (``ev.error``) when the session was aborted (fault,
        deadline, disconnect): consumers never await forever."""
        while True:
            ev = await self.queue.get()
            if ev is None:
                return
            self.received.append(ev)
            yield ev


class AgentGateway:
    """Asyncio front for one ``ServingEngine`` (single engine, many
    concurrent streaming clients)."""

    def __init__(self, engine, config: Optional[GatewayConfig] = None,
                 tool_fn: Optional[ToolFn] = None, faults=None):
        self.engine = engine
        self.reactor = EngineReactor(engine)
        self.cfg = config or GatewayConfig()
        if self.cfg.tool_policy not in ("hold", "release"):
            raise ValueError(f"unknown tool_policy {self.cfg.tool_policy}")
        if self.cfg.admission not in ("reject", "queue"):
            raise ValueError(f"unknown admission mode {self.cfg.admission}")
        if self.cfg.tool_failure_policy not in ("finish_turn", "abort"):
            raise ValueError(
                f"unknown tool_failure_policy {self.cfg.tool_failure_policy}")
        self.gate = WatermarkGate(self.cfg.high_watermark,
                                  self.cfg.low_watermark)
        self.tool_fn = tool_fn
        # chaos plan (serving/faults.py): engine-side hooks installed
        # here; the gateway consults the plan inside tool calls
        self.faults = faults
        if faults is not None:
            engine.install_faults(faults)
        self._live: Dict[int, LiveSession] = {}
        # engine ops staged by submit()/tool tasks/cancel, drained by
        # the reactor loop between cycles — the engine is only ever
        # touched from the loop, so no locking is needed
        self._ops: Deque[Tuple[str, LiveSession, Optional[str]]] = \
            collections.deque()
        self._ids = itertools.count()
        self._task: Optional[asyncio.Task] = None
        self._running = False
        self._waiters = 0
        self._tool_tasks: set = set()
        self._rng = np.random.default_rng(self.cfg.seed)  # backoff jitter
        # formatted traceback of the last exception that escaped a loop
        # iteration (counted in engine_errors) — kept so a device fault
        # (OOM, compile failure) has a visible cause, not only failed
        # sessions
        self.last_engine_error: Optional[str] = None
        # finished sessions, retained (bounded) for open-loop reporting
        # — the engine/reactor detach them at session_end
        self.completed_sessions: Deque[Session] = collections.deque(
            maxlen=self.cfg.completed_history)
        # aborted sessions (fault/deadline/disconnect), same retention
        self.failed_sessions: Deque[Session] = collections.deque(
            maxlen=self.cfg.completed_history)
        # gateway metrics register into the ENGINE's registry
        # (DESIGN.md §11): engine.stats(), gateway.stats() and the HTTP
        # /stats + /metrics surfaces are all views of one object
        reg = engine.telemetry.registry
        self.counters = RegistryDict(
            reg,
            {"submitted": 0, "rejected": 0, "completed": 0,
             "parked": 0, "tool_calls": 0, "tool_errors": 0,
             "aborted": 0, "cancelled": 0, "tool_retries": 0,
             "tool_timeouts": 0, "engine_errors": 0},
            help_prefix="gateway counter: ")
        reg.gauge("gate_admitted", help="watermark-gate admissions",
                  fn=lambda: float(self.gate.admitted))
        reg.gauge("gate_rejected", help="watermark-gate sheds",
                  fn=lambda: float(self.gate.rejected))
        reg.gauge("gate_shedding", help="1 while the gate is closed",
                  fn=lambda: float(self.gate.shedding))
        reg.gauge("gate_pressure", help="KV-pressure watermark tighten",
                  fn=lambda: float(self.gate.pressure))
        reg.gauge("occupancy", help="admission occupancy (queues + "
                  "waiting sessions + staged ops)",
                  fn=lambda: float(self.occupancy()))
        reg.gauge("live_sessions", help="streaming sessions in flight",
                  fn=lambda: float(len(self._live)))
        reg.gauge("failed_sessions", help="aborted sessions retained "
                  "for reporting",
                  fn=lambda: float(len(self.failed_sessions)))

    # ---- lifecycle ----------------------------------------------------
    async def start(self) -> None:
        if self._task is not None:
            raise RuntimeError("gateway already started")
        self._running = True
        self._task = asyncio.create_task(self._loop())

    async def stop(self, timeout_s: Optional[float] = None) -> None:
        """Stop accepting new work and drain in-flight sessions; cancel
        the loop if the drain exceeds ``timeout_s``.  A timed-out drain
        pushes a terminal error event to every live session's queue so
        ``events()`` consumers unblock instead of hanging forever."""
        self._running = False
        if self._task is None:
            return
        try:
            await asyncio.wait_for(asyncio.shield(self._task), timeout_s)
        except asyncio.TimeoutError:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._fail_all_live("gateway_stopped")
        self._task = None

    def _fail_all_live(self, reason: str) -> None:
        """Terminate every live stream with an error event (and cancel
        outstanding tool tasks) — the no-consumer-awaits-forever
        backstop for loop death and drain timeouts."""
        for task in list(self._tool_tasks):
            task.cancel()
        for sid, live in list(self._live.items()):
            live.state = GatewayState.FAILED
            live.session.abort_reason = live.session.abort_reason or reason
            live.queue.put_nowait(TokenEvent(
                session_id=sid, token=-1, t=self.engine.clock(),
                turn_idx=live.session.turn_idx, index=-1,
                session_end=True, error=True, abort_reason=reason))
            live.queue.put_nowait(None)
            self.counters["aborted"] += 1
            self.failed_sessions.append(live.session)
            del self._live[sid]

    # ---- admission ----------------------------------------------------
    def occupancy(self) -> int:
        return self.engine.admission_occupancy() + len(self._ops)

    def _kv_pressure_gate(self) -> None:
        """Tighten the admission watermark while the engine reports
        KVExhausted deferrals — shed new load at the door instead of
        deferring it inside (DESIGN.md §10 degradation ladder)."""
        amount = self.cfg.kv_pressure_tighten
        if amount < 0:
            amount = self.cfg.high_watermark // 2
        hot = self.engine.kv_pressure_recent(self.cfg.kv_pressure_window)
        self.gate.set_pressure(amount if hot else 0)

    async def submit(self, session: Session,
                     deadline_s: Optional[float] = None,
                     ) -> Union[LiveSession, Rejected]:
        """Admit a live agent session — or shed it at the watermark.
        The returned ``LiveSession`` streams tokens via ``events()``.
        ``deadline_s`` (relative seconds, overrides the config default)
        arms an engine-enforced SLO deadline: past it the session is
        aborted and its stream ends with an error event."""
        if self._task is not None and self._task.done():
            raise GatewayStopped(
                f"gateway loop stopped; last engine error:\n"
                f"{self.last_engine_error}")
        t_staged = self.engine.clock()
        self._kv_pressure_gate()
        occ = self.occupancy()
        if not self.gate.check(occ) and self.cfg.admission == "queue":
            occ = await self._wait_for_gate(occ)
        if not self.gate.offer(occ):
            self.counters["rejected"] += 1
            return Rejected(occupancy=occ)
        session.session_id = next(self._ids)
        session.external_tools = True    # gateway owns the tool clock
        rel = (deadline_s if deadline_s is not None
               else self.cfg.default_deadline_s)
        if np.isfinite(rel):
            session.deadline_s = self.engine.clock() + float(rel)
        live = LiveSession(session, self)
        self._live[session.session_id] = live
        session.staged_s.append(t_staged)
        self._ops.append(("submit", live, None))
        self.counters["submitted"] += 1
        return live

    async def _wait_for_gate(self, occ: int) -> int:
        """Queue-mode admission: wait (bounded) for the gate to reopen
        instead of shedding immediately."""
        if self._waiters >= self.cfg.max_queue:
            return occ                   # queue full -> let offer() shed
        self._waiters += 1
        try:
            deadline = (asyncio.get_running_loop().time()
                        + self.cfg.queue_timeout_s)
            while not self.gate.check(occ := self.occupancy()):
                if asyncio.get_running_loop().time() >= deadline:
                    break
                await asyncio.sleep(self.cfg.idle_sleep_s * 5)
        finally:
            self._waiters -= 1
        return occ

    # ---- the reactor loop ---------------------------------------------
    async def _loop(self) -> None:
        """The serialised engine loop, fault-isolated (DESIGN.md §10):
        per-session faults are handled inside ``engine.step`` (quarantine
        via ``abort_session``); anything that still escapes an iteration
        is counted and retried — after ``max_engine_errors`` consecutive
        failures the gateway fails every live stream loudly and exits
        rather than leaving consumers blocked on silent streams."""
        cfg = self.cfg
        errors_in_row = 0
        while self._running or self._ops or self.reactor.pending():
            try:
                with span("engine.reactor.ops"):
                    self._drain_ops()
                    self._park_under_pressure()
                if cfg.step_in_thread:
                    events = await asyncio.to_thread(self.reactor.step)
                else:
                    events = self.reactor.step()
                    await asyncio.sleep(0)   # let clients/timers breathe
                with span("engine.reactor.route"):
                    for ev in events:
                        self._route(ev)
                errors_in_row = 0
            except asyncio.CancelledError:
                raise
            except Exception:
                self.counters["engine_errors"] += 1
                self.last_engine_error = traceback.format_exc()
                log.exception("engine loop iteration failed")
                errors_in_row += 1
                if errors_in_row >= cfg.max_engine_errors:
                    self._fail_all_live("engine_error")
                    return
                await asyncio.sleep(cfg.idle_sleep_s)
                continue
            if not events and not self.reactor.did_work and not self._ops:
                await asyncio.sleep(cfg.idle_sleep_s)
        self.engine.flush()

    def _drain_ops(self) -> None:
        """Apply staged submit/resume/abort ops to the engine, in FIFO
        order (a session's abort can therefore never precede its own
        submit).  Ops for already-terminal sessions are dropped — abort
        racing completion, resume racing abort — so a stale op can never
        corrupt another session's engine state."""
        while self._ops:
            op, live, arg = self._ops.popleft()
            if op == "submit":
                live.handle = self.reactor.submit(live.session)
                continue
            state = live.session.state
            if state in (SessionState.FINISHED, SessionState.ABORTED):
                continue                 # terminal: drop the stale op
            if op == "resume":
                if not live.cancelled:
                    self.reactor.resume(live.handle)
            else:                        # "abort" (cancel / tool failure)
                if live.tool_task is not None:
                    live.tool_task.cancel()
                self.reactor.abort(live.handle, arg or "aborted")

    def _route(self, ev: TokenEvent) -> None:
        live = self._live.get(ev.session_id)
        if live is None:
            return
        if ev.error:
            # terminal error event (abort_session): fail exactly this
            # stream — deliver the event so the consumer sees the abort
            # reason, then terminate the stream
            live.state = GatewayState.FAILED
            if live.tool_task is not None:
                live.tool_task.cancel()  # e.g. a still-hanging tool
            live.queue.put_nowait(ev)
            live.queue.put_nowait(None)
            self.counters["aborted"] += 1
            self.failed_sessions.append(live.session)
            del self._live[ev.session_id]
            return
        live.queue.put_nowait(ev)
        if ev.first:
            live.state = GatewayState.DECODE
        if ev.session_end:
            live.state = GatewayState.DONE
            live.queue.put_nowait(None)  # stream terminator
            self.counters["completed"] += 1
            self.completed_sessions.append(live.session)
            del self._live[ev.session_id]
        elif ev.turn_end:
            live.state = GatewayState.TOOL_WAIT
            task = asyncio.get_running_loop().create_task(
                self._tool_wait(live, ev.turn_idx))
            live.tool_task = task
            self._tool_tasks.add(task)
            task.add_done_callback(self._tool_tasks.discard)

    def _park_under_pressure(self) -> None:
        """release policy, checked every loop iteration (not just at
        TOOL_WAIT entry): whenever a waiting session is blocked on slot
        exhaustion, park TOOL_WAIT sessions that still hold a slot
        until the pressure clears."""
        if self.cfg.tool_policy != "release":
            return
        for live in list(self._live.values()):
            if not self.engine.slot_pressure():
                return
            if (live.state == GatewayState.TOOL_WAIT
                    and live.session.slot >= 0):
                self.engine.park_session(live.session_id)
                self.counters["parked"] += 1

    def _backoff_s(self, attempt: int) -> float:
        """Exponential backoff with seeded jitter: base * 2^attempt,
        capped, +- jitter fraction.  All on the gateway clock — engine
        determinism is untouched."""
        cfg = self.cfg
        base = min(cfg.tool_backoff_base_s * (2 ** attempt),
                   cfg.tool_backoff_max_s)
        jitter = 1.0 + cfg.tool_backoff_jitter * float(
            self._rng.uniform(-1.0, 1.0))
        return max(0.0, base * jitter)

    async def _call_tool(self, sess: Session, turn_idx: int,
                         attempt: int) -> Optional[np.ndarray]:
        """One tool-call attempt — the chaos plan may turn it into an
        injected error or a hang (which the per-attempt timeout cuts)."""
        if self.faults is not None:
            from repro.serving.faults import InjectedFault
            sp = self.faults.tool_fault(sess.session_id, turn_idx, attempt)
            if sp is not None:
                if sp.kind == "tool_hang":
                    await asyncio.sleep(sp.hang_s)
                raise InjectedFault(
                    f"injected tool_error (session {sess.session_id} "
                    f"turn {turn_idx} attempt {attempt})")
        if self.tool_fn is not None:
            return await self.tool_fn(sess, turn_idx)
        await asyncio.sleep(sess.turns[turn_idx].tool_latency_s)
        return None

    async def _run_tool(self, live: LiveSession, turn_idx: int) -> bool:
        """Tool-call resilience (DESIGN.md §10): per-attempt timeout,
        bounded retries with exponential backoff + jitter.  Returns
        whether any attempt succeeded."""
        cfg, sess = self.cfg, live.session
        tracer = self.engine.telemetry.tracer
        attempts = 1 + max(0, cfg.tool_retries)
        for attempt in range(attempts):
            t_att = self.engine.clock()
            outcome = "error"
            try:
                next_tokens = await asyncio.wait_for(
                    self._call_tool(sess, turn_idx, attempt),
                    timeout=cfg.tool_timeout_s)
                if next_tokens is not None:
                    # a real tool's output replaces the next turn's
                    # scripted prefill (safe: it hasn't started)
                    sess.turns[turn_idx + 1].prefill_tokens = np.asarray(
                        next_tokens, np.int32)
                outcome = "ok"
                return True
            except asyncio.CancelledError:
                raise
            except asyncio.TimeoutError:
                outcome = "timeout"
                self.counters["tool_timeouts"] += 1
            except Exception:
                pass
            finally:
                if tracer is not None:
                    # per-attempt child span under the session's open
                    # TOOL_WAIT span, annotated with retry/timeout fate
                    tracer.child(sess.session_id, "tool_attempt",
                                 t_att, self.engine.clock(),
                                 turn=turn_idx, attempt=attempt,
                                 outcome=outcome)
            if attempt + 1 < attempts:
                self.counters["tool_retries"] += 1
                await asyncio.sleep(self._backoff_s(attempt))
        self.counters["tool_errors"] += 1    # one per exhausted call
        return False

    async def _tool_wait(self, live: LiveSession, turn_idx: int) -> None:
        """The tool half of an agent turn, on the gateway's clock.

        A tool failure must not wedge the session in TOOL_WAIT (the
        client's stream would hang forever).  After retries are
        exhausted the configured policy decides: ``finish_turn`` resumes
        with the scripted next-turn tokens (degraded but complete);
        ``abort`` terminates the session with an error event."""
        sess = live.session
        self.counters["tool_calls"] += 1
        try:
            ok = await self._run_tool(live, turn_idx)
        except asyncio.CancelledError:
            raise
        live.tool_task = None
        if not ok and self.cfg.tool_failure_policy == "abort":
            self._ops.append(("abort", live, "tool_failed"))
            return
        live.state = GatewayState.RESUME
        sess.staged_s.append(self.engine.clock())
        self._ops.append(("resume", live, None))

    # ---- observability -------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """One snapshot of the unified registry — identical (by
        construction, not convention) to ``engine.stats()`` and to what
        ``GET /stats`` / ``GET /metrics`` serve.  The PR-6 drift where
        fault counters existed in some views but not others cannot
        recur: there is only one view."""
        return self.engine.stats()


# ---------------------------------------------------------------------------
# open-loop driver (benchmarks, tests, --serve-smoke)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpenLoopRun:
    completed: List[Session]
    rejected: List[Session]
    events: List[Tuple[float, TokenEvent]]   # (driver wall time, event)
    wall_s: float

    def interleaved(self) -> bool:
        """True when token events from different sessions interleave —
        the observable signature of concurrent streaming."""
        switches = sum(1 for a, b in zip(self.events, self.events[1:])
                       if a[1].session_id != b[1].session_id)
        return switches > len({e.session_id for _, e in self.events})


async def drive_open_loop(gateway: AgentGateway, sessions: List[Session],
                          arrivals, *, time_scale: float = 1.0,
                          ) -> OpenLoopRun:
    """Submit ``sessions`` at their open-loop ``arrivals`` offsets (wall
    clock, scaled by ``time_scale``) and consume every stream to
    completion.  One asyncio task per agent — the client side of the
    paper's overlapping multi-agent arrival pattern."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    run = OpenLoopRun(completed=[], rejected=[], events=[], wall_s=0.0)

    async def one(sess: Session, at: float) -> None:
        delay = at * time_scale - (loop.time() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        res = await gateway.submit(sess)
        if isinstance(res, Rejected):
            run.rejected.append(sess)
            return
        async for ev in res.events():
            run.events.append((loop.time() - t0, ev))
        run.completed.append(sess)

    await asyncio.gather(*(one(s, float(a))
                           for s, a in zip(sessions, arrivals)))
    run.wall_s = loop.time() - t0
    return run
