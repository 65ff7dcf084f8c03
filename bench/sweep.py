"""Find the knee of an open-loop cell: the highest session rate that
the server sustains with no growing admission backlog.

    python3 bench/sweep.py --workload <cell> --rates 0.12,0.16,0.2 \\
        --seconds 51 --seeds 5,6

One process serves the cell's mix at each rate and seed in turn, each
on an engine built afresh (the executables compile once), and prints
one JSON line per run: the backlog of sessions waiting for their first token at
window open and close and its mean over each half of the window, the
cold turns' median wait in each half, failed requests, and the window's
client-side statistics.  The knee is read from these lines once, on the
chip, and written into the mix's rate; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def backlog(obs, t: float) -> int:
    """Sessions sent by ``t`` that had not yet received a token."""
    n = 0
    for st in obs.streams:
        if st.sent_s and st.sent_s <= t and (
                not st.received_s or st.received_s[0] > t):
            n += 1
    return n


def refuse_closed(mix) -> None:
    """A closed mix sets its own load by its number of agents: it has
    no rate to sweep."""
    if mix["arrivals"]["kind"] != "poisson":
        raise SystemExit(f"bench/sweep.py: a {mix['arrivals']['kind']} "
                         f"mix has no session rate to sweep; its load is "
                         f"its number of agents")


def main(argv=None) -> int:
    import numpy as np

    from bench import harness, run, traffic as tr
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", default="0")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    refuse_closed(cell.mix)
    run.check_device(cell.chips)
    run.enable_cache()
    runs = [(float(r), int(s)) for r in args.rates.split(",")
            for s in args.seeds.split(",")]
    for rate, seed in runs:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate"] = rate
        at_rate = harness.Cell(**{**cell.__dict__, "mix": mix})
        sched = tr.make_schedule(mix, args.seconds, seed)
        plans = tr.session_plans(mix, sched, cell.config["vocab_size"])
        srv = harness.Server(at_rate, seed)
        obs = asyncio.run(harness.drive(srv, sched, plans, args.seconds))
        srv.free()
        del srv
        gc.collect()
        reqs, refused, bad = harness.requests_in_window(obs, plans)
        half = (obs.open_s + obs.close_s) / 2
        ts = np.linspace(obs.open_s, obs.close_s, 61)
        b = [backlog(obs, t) for t in ts]
        cold = [(r.due_s, r.first_s - r.due_s) for r in reqs
                if r.turn == 0 and r.first_s is not None]
        line = {"rate": rate, "seed": seed,
                "backlog_open": b[0], "backlog_close": b[-1],
                "backlog_mean_1st": float(np.mean(b[:30])),
                "backlog_mean_2nd": float(np.mean(b[31:])),
                "cold_wait_med_1st_s": float(np.median(
                    [w for t, w in cold if t < half] or [np.nan])),
                "cold_wait_med_2nd_s": float(np.median(
                    [w for t, w in cold if t >= half] or [np.nan])),
                "requests": len(reqs), "refused": refused,
                "unfinished": sum(r.failed for r in reqs),
                "bad_turns": len(bad),
                "drain_s": obs.end_s - obs.close_s,
                **harness.end_to_end(obs, reqs)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
