"""Where each turn's time to first token goes, in one run of a cell.

    python3 bench/ttft_parts.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> --out <file.json>

Runs the cell as ``bench/run.py`` does and prints the same last line.
Besides, it splits the client-clock time to first token of each request
due in the window that received one into the stages the program stamps
on its ``Session`` (``bench/stages.py``), all on the client's clock:

``hop``        due (session start, or the benchmark tool's return) ->
               the gateway taking the request (HTTP parse and connect
               for a cold request)
``gateway``    -> its op reaching the engine
``admission``  -> admitted
``planner``    -> the first device call carrying its prompt
``prefill_run``-> the host reading its first token
``front``      -> the client reading it (``TokenEvent.t_emit`` to
               receipt)

and writes every request's parts, and those of the request at the TTFT
p90 (nearest rank), to ``--out``.  A program without the stamps gives
no parts.  The file also holds how long the engine's cycles inside the
window took (the gateway applies staged ops only between cycles) and
the longest stretch between two cycles.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

PARTS = ("hop", "gateway", "admission", "planner", "prefill_run", "front")


def split(rec, req) -> Optional[Dict[str, float]]:
    """The request's parts in ms, their sum and its client TTFT; ``None``
    when it received no token or the program kept not every stamp."""
    from bench.harness import _turn_events
    from bench.stages import STAGES, stamp
    if req.first_s is None:
        return None
    st = rec.obs.streams[req.stream]
    ev = st.events[_turn_events(st)[req.turn][0]]
    marks = [stamp(rec, req, name) for name in STAGES]
    if None in marks or ev.get("t_emit") is None:
        return None
    off = rec.obs.engine_offset
    edges = [req.due_s] + [m + off for m in marks[:-1]] + [
        ev["t_emit"] + off, req.first_s]
    out = {name: 1e3 * (b - a)
           for name, a, b in zip(PARTS, edges, edges[1:])}
    out["sum"] = sum(out[name] for name in PARTS)
    out["ttft"] = 1e3 * (req.first_s - req.due_s)
    out["turn"] = req.turn
    return out


def at_p90(rows: List[Dict[str, float]]) -> Optional[Dict[str, float]]:
    """The row at the p90 of client TTFT, by nearest rank."""
    if not rows:
        return None
    ranked = sorted(rows, key=lambda r: r["ttft"])
    return ranked[max(0, math.ceil(0.9 * len(ranked)) - 1)]


def cycles(obs) -> Dict[str, float]:
    """Engine cycles that ended in the window: count, duration
    percentiles and the time spent in cycles of 0.5 s or more (ms), and
    the longest stretch between two cycles (ms) with its start (s after
    window open)."""
    win = [(t0, t1) for t0, t1, _ in obs.cycles
           if obs.open_s <= t1 < obs.close_s]
    if not win:
        return {}
    d = np.asarray([t1 - t0 for t0, t1 in win]) * 1e3
    between = [(b[0] - a[1], a[1]) for a, b in zip(win, win[1:])]
    gap, at = max(between, default=(0.0, obs.open_s))
    return {"n": len(win), "p50_ms": float(np.percentile(d, 50)),
            "p99_ms": float(np.percentile(d, 99)), "max_ms": float(d.max()),
            "in_long_ms": float(d[d >= 500.0].sum()),
            "longest_between_ms": 1e3 * gap,
            "longest_between_at_s": at - obs.open_s}


def main(argv=None) -> int:
    from bench import harness, run
    from bench.record import Record
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args(argv)
    ns = run.parse(rest)
    run.check_device(1)
    run.enable_cache()
    seen = {}
    drive = harness.drive

    async def capture(srv, schedule, plans, *a, **k):
        seen["obs"] = await drive(srv, schedule, plans, *a, **k)
        seen["plans"] = plans
        return seen["obs"]
    harness.drive = capture
    result = run.run(ns)
    obs = seen["obs"]
    reqs, _, _ = harness.requests_in_window(obs, seen["plans"])
    rec = Record(cfg={}, peaks={}, obs=obs, reqs=reqs, plans={}, trace=None,
                 memory={}, num_slots=0)
    rows = [r for r in (split(rec, q) for q in reqs) if r is not None]
    doc = {"workload": ns.workload, "seed": ns.seed, "trace": ns.trace,
           "requests": rows, "p90": at_p90(rows), "cycles": cycles(obs)}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(doc))
    print(f"ttft_parts: p90 request {json.dumps(doc['p90'])}; cycles "
          f"{json.dumps(doc['cycles'])}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
