"""The run record that per-layer metric readers take, and the helpers
they share.  A reader (``bench/metrics/<name>.py``) exposes
``read(rec) -> float | None`` and returns ``None`` where it finds
nothing to read."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

from bench import cost, trace_reduce
from bench.harness import Observed, Request, percentile
from bench.traffic import SessionPlan

DECODE_EXECUTABLES = ("fused_step", "mega_step")
PREFILL_EXECUTABLES = ("prefill_step", "resume_step")


def prefill_context(plan: SessionPlan, turn: int) -> int:
    """Positions the cache holds when ``turn``'s prompt starts: every
    earlier prompt and every earlier served token but each burst's
    last."""
    return sum(len(t.prefill_tokens) + t.decode_len - 1
               for t in plan.turns[:turn])


def tokens_context(plan: SessionPlan, turn: int, index: int) -> int:
    """Keys the query of token ``index`` (>= 1) of ``turn`` attends to:
    the decode step that made it fed the burst's previous token."""
    return (prefill_context(plan, turn)
            + len(plan.turns[turn].prefill_tokens) + index)


@dataclasses.dataclass
class Record:
    cfg: Dict
    peaks: Dict[str, float]
    obs: Observed
    reqs: List[Request]
    plans: Dict[int, SessionPlan]
    trace: Optional[Dict]
    memory: Dict[str, int]
    num_slots: int

    def in_window(self, t: float) -> bool:
        return self.obs.open_s <= t < self.obs.close_s

    def p95_ms(self, xs) -> Optional[float]:
        v = percentile(xs, 95)
        return None if v is None else 1e3 * v

    def in_trace(self, t: float) -> bool:
        a, b = self.trace["span"]
        return a <= t < b

    def device_s(self, names) -> float:
        ex = self.trace["executables"]
        return sum(ex.get(n, 0.0) for n in names)

    def decode_steps(self) -> List[List[int]]:
        """Decode steps whose tokens the engine stamped inside the
        traced span: per step, the keys each active lane attended to.
        The engine stamps every lane of one step with the same time."""
        steps: Dict[float, List[int]] = {}
        off = self.obs.engine_offset
        for st in self.obs.streams:
            plan = self.plans[st.spec_seed]
            for ev in st.events:
                if ev.get("error") or ev["index"] < 1:
                    continue
                t = ev["t"] + off
                if self.in_trace(t):
                    steps.setdefault(ev["t"], []).append(
                        tokens_context(plan, ev["turn_idx"], ev["index"]))
        return list(steps.values())

    def prefill_flops_in_trace(self) -> float:
        """Prompt FLOPs of the turns whose whole prefill (admission to
        first token, engine clock) lies inside the traced span.  A
        prefill that straddles an edge is left out: its chunks need not
        have run evenly (a cold prefill can wait while decode runs), so
        only what surely ran inside the span is counted."""
        a, b = self.trace["span"]
        off = self.obs.engine_offset
        total = 0.0
        for st in self.obs.streams:
            sess = self.obs.sessions.get(st.session_id)
            if sess is None:
                continue
            plan = self.plans[st.spec_seed]
            firsts = {ev["turn_idx"]: ev["t"] for ev in st.events
                      if not ev.get("error") and ev["index"] == 0}
            for k, t_adm in enumerate(sess.request_arrivals):
                if k not in firsts:
                    continue
                if a <= t_adm + off and firsts[k] + off < b:
                    total += cost.prefill_flops(
                        self.cfg, len(plan.turns[k].prefill_tokens),
                        prefill_context(plan, k))
        return total


def reduce_trace(obs: Observed, keep: str = "") -> Optional[Dict]:
    """Reduce the run's trace; its span is put on the client's clock by
    the marker span the harness stamped on both."""
    if obs.trace_dir is None or obs.trace_span is None:
        return None
    events = trace_reduce.load(obs.trace_dir)
    probe = trace_reduce.reduce(events)
    if probe["marker_s"] is None:
        raise ValueError("the trace lost the harness's marker span")
    offset = obs.marker_s - probe["marker_s"]        # client - trace
    a, b = obs.trace_span
    window = (int((a - offset) * 1e9), int((b - offset) * 1e9))
    out = trace_reduce.reduce(events, window_ns=window)
    out["span"] = (a, b)
    out["window_s"] = b - a
    if keep:
        Path(keep).mkdir(parents=True, exist_ok=True)
        mid = (window[0] + window[1]) // 2
        small = trace_reduce.trim(events, mid, mid + 300_000_000)
        small["layout"] = events["layout"]
        (Path(keep) / "trace_events.json").write_text(json.dumps(small))
        (Path(keep) / "trace_reduced.json").write_text(json.dumps(
            {k: v for k, v in out.items()}, default=list))
    return out
