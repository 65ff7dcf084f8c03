"""Steppable engine reactor: the non-blocking serving surface.

The reactor is the seam between the cycle-synchronous engine (one
``ServingEngine.step()`` == one cycle: a decode megastep plus the
budgeted prefill work, DESIGN.md §2) and any *online* driver — the
asyncio gateway, a benchmark harness, or a test.  It owns request
handles, routes the engine's per-token events to them, and never
blocks: ``submit`` registers a session, ``step`` advances exactly one
cycle and returns the tokens it emitted, ``poll`` reads a handle's
progress.  The closed-loop ``ServingEngine.run()`` is reimplemented on
top of the same ``step()``, so the Fig-5 batch path and the online
gateway dispatch identical cycle code.

``TokenEvent`` is the engine's emission record: one decoded token for
one session, stamped with the engine clock — ``t`` when the device made
it, ``t_emit`` when the host read it.  ``turn_end`` marks the
last token of a decode burst (the agent is about to leave for a tool
call — the gateway's TOOL_WAIT trigger) and ``session_end`` the last
token of the final turn.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import time
from typing import Deque, Dict, List, Optional

from repro.serving.request import Session, SessionState


@dataclasses.dataclass
class TokenEvent:
    """One emitted token. ``t`` is engine-clock seconds; ``index`` is the
    token's position within its turn's decode burst (0 == first token,
    emitted by the prefill completion)."""
    session_id: int
    token: int
    t: float
    turn_idx: int
    index: int
    first: bool = False          # first token of a burst (TTFT event)
    turn_end: bool = False       # burst complete -> tool call next
    session_end: bool = False    # final token of the final turn
    # fault-domain terminal (DESIGN.md §10): an aborted session's last
    # event carries error=True (token == -1) so stream consumers
    # distinguish failure from completion; abort_reason attributes it
    error: bool = False
    abort_reason: str = ""
    # engine-clock time the host read the token: a decode token's flush
    # (``t`` is interpolated back across the flush window), else ``t``
    t_emit: Optional[float] = None

    def __post_init__(self):
        if self.t_emit is None:
            self.t_emit = self.t


class HandleStatus(enum.Enum):
    QUEUED = "queued"            # submitted, waiting for a KV slot
    PREFILL = "prefill"          # chunks in flight
    DECODE = "decode"
    TOOL_WAIT = "tool_wait"      # burst done; waiting on the tool clock
    DONE = "done"
    FAILED = "failed"            # aborted: fault / deadline / disconnect


_STATE_TO_STATUS = {
    SessionState.WAITING_PREFILL: HandleStatus.QUEUED,
    SessionState.PREFILLING: HandleStatus.PREFILL,
    SessionState.PREFILL_PAUSED: HandleStatus.PREFILL,
    SessionState.DECODING: HandleStatus.DECODE,
    SessionState.TOOL_CALL: HandleStatus.TOOL_WAIT,
    SessionState.TOOL_WAIT: HandleStatus.TOOL_WAIT,
    SessionState.FINISHED: HandleStatus.DONE,
    SessionState.ABORTED: HandleStatus.FAILED,
}


@dataclasses.dataclass
class RequestHandle:
    """Per-submission view: undelivered events plus live status."""
    session: Session
    events: Deque[TokenEvent] = dataclasses.field(
        default_factory=collections.deque)

    @property
    def session_id(self) -> int:
        return self.session.session_id


class EngineReactor:
    """submit/step/poll driver over one ``ServingEngine``.

    Single-threaded by contract: all calls must come from one thread
    (the gateway serialises engine access through its reactor loop).
    """

    def __init__(self, engine):
        self.engine = engine
        self._handles: Dict[int, RequestHandle] = {}
        engine.start_online()

    # ---- submission ---------------------------------------------------
    def submit(self, session: Session,
               arrival_s: Optional[float] = None) -> RequestHandle:
        """Register a live session.  ``arrival_s`` (engine clock) defaults
        to *now* — the open-loop driver controls offered load by when it
        calls submit, not by pre-staged ``ready_s`` offsets."""
        self.engine.mark_ready(session, self.engine.clock()
                               if arrival_s is None else arrival_s)
        self.engine.attach(session)
        handle = RequestHandle(session=session)
        self._handles[session.session_id] = handle
        return handle

    # ---- stepping -----------------------------------------------------
    def step(self) -> List[TokenEvent]:
        """Advance the engine exactly one cycle and route the emitted
        tokens to their handles.  Returns the cycle's events (callers
        that stream don't need to poll).

        Completed sessions are detached from the engine registry and
        this reactor's handle table on their ``session_end`` event, so
        a long-lived server's per-cycle cost and memory stay bounded by
        the *live* session count (the caller's handle object keeps
        working — poll reads the session state it already holds)."""
        events = self.engine.step()
        for ev in events:
            handle = self._handles.get(ev.session_id)
            if handle is not None:
                handle.events.append(ev)
            if ev.session_end:
                self.engine.detach(ev.session_id)
                self._handles.pop(ev.session_id, None)
        return events

    @property
    def did_work(self) -> bool:
        return self.engine.last_step_did_work

    def pending(self) -> bool:
        return self.engine.pending()

    # ---- handle-side --------------------------------------------------
    def poll(self, handle: RequestHandle) -> HandleStatus:
        return _STATE_TO_STATUS[handle.session.state]

    def take_events(self, handle: RequestHandle) -> List[TokenEvent]:
        out = list(handle.events)
        handle.events.clear()
        return out

    def resume(self, handle: RequestHandle) -> None:
        """Tool-completion hook: re-arm a TOOL_WAIT session for its next
        turn (the gateway owns the tool-wait clock)."""
        self.engine.resume_session(handle.session_id)

    def park(self, handle: RequestHandle) -> None:
        """Release the session's KV slot while it waits on a tool (the
        under-pressure policy); the resume path restores it losslessly."""
        self.engine.park_session(handle.session_id)

    def abort(self, handle: RequestHandle, reason: str = "aborted") -> bool:
        """Quarantine one session: reclaim its slot/pages and emit its
        terminal error event (delivered by the next ``step()``).  False
        when the session already reached a terminal state — abort races
        against completion are benign."""
        return self.engine.abort_session(handle.session_id, reason)

    # ---- convenience --------------------------------------------------
    def drain(self, max_wall_s: float = 300.0,
              idle_sleep_s: float = 0.0005) -> List[TokenEvent]:
        """Step until every submitted session finishes (bounded by wall
        clock).  Test/benchmark convenience — the gateway runs its own
        async loop instead."""
        out: List[TokenEvent] = []
        t0 = time.perf_counter()
        while self.pending() and time.perf_counter() - t0 < max_wall_s:
            out.extend(self.step())
            if not self.did_work:
                time.sleep(idle_sleep_s)
        self.engine.flush()
        return out
