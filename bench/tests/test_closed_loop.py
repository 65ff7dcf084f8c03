"""Closed-loop arrivals: each agent's pool of sessions is its own and
fixed by the mix, in an order no seed changes, the seed gives the
agents their first starts, an agent starts its next session when the
last one's stream ends, however it ended, and none at or after the
window's close, and an agent that runs through its pool is an error.
CPU only; nothing here serves a model."""
from __future__ import annotations

import asyncio
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, sweep, traffic as tr

FIX = Path(__file__).resolve().parent / "fixtures"
SECONDS = 30.0


def _mix():
    return tr.load_mix("tiny_plan_closed", FIX / "traffic")


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 17])
def test_pools_are_disjoint_and_sized_by_the_mix(seed):
    mix = _mix()
    sched = tr.make_schedule(mix, SECONDS, seed)
    size = tr.pool_size(mix, SECONDS)
    # 4 x (1 + 30 + 30 s) over the shortest session, one 0.05 s tool wait
    assert size == 4 * 61 * 20
    assert len(sched.pools) == mix["arrivals"]["agents"]
    seen = set()
    for pool in sched.pools:
        assert len(pool) == len(set(pool)) == size
        assert not seen & set(pool)
        seen |= set(pool)


def test_seed_orders_the_sessions_and_keeps_the_set():
    """Two seeds serve the same sessions: the pools keep one order, and
    only the agents' first starts move with the seed."""
    mix = _mix()
    a, b = (tr.make_schedule(mix, SECONDS, s) for s in (3, 3))
    c = tr.make_schedule(mix, SECONDS, 2 ** 33 + 4)
    assert a == b
    assert a.first_s != c.first_s
    assert sorted(a.first_s) == sorted(c.first_s)
    assert [list(p) for p in a.pools] == [list(p) for p in c.pools]

    def served(sched):
        # every session lasts 4 s: each agent serves the same leading
        # sessions whichever first start it has
        return {s for first, pool in zip(sched.first_s, sched.pools)
                for s, _ in _agent(pool, first, SECONDS,
                                   [4.0] * len(pool))}
    assert served(a) == served(c)
    assert len(served(a)) == 8 * mix["arrivals"]["agents"]


def test_first_starts_at_midpoint_quantiles_of_the_stagger():
    mix = _mix()
    n, stagger = mix["arrivals"]["agents"], mix["arrivals"]["stagger_s"]
    for seed in range(5):
        first = tr.make_schedule(mix, SECONDS, seed).first_s
        np.testing.assert_allclose(sorted(first),
                                   stagger * (np.arange(n) + 0.5) / n)


def test_plans_cover_every_pooled_session_and_no_other():
    mix = _mix()
    sched = tr.make_schedule(mix, SECONDS, 9)
    plans = tr.session_plans(mix, sched, 512)
    for pool in sched.pools:
        for s in (pool[0], pool[-1]):
            want = tr.make_session(mix["paradigm"], s, 512,
                                   mix["token_scale"])
            assert plans[s].spec_seed == s
            for a, b in zip(plans[s].turns, want.turns, strict=True):
                np.testing.assert_array_equal(a.prefill_tokens,
                                              b.prefill_tokens)
                assert (a.decode_len, a.tool_latency_s) == \
                    (b.decode_len, b.tool_latency_s)
    with pytest.raises(KeyError):
        plans[-1]


class _Clock:
    """A fake client clock that a fake session moves on."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _agent(pool, at, close_s, lasts, fail=(),
           error=OSError("connection refused")):
    """Run one agent whose ``k``-th session lasts ``lasts[k]`` seconds
    (and raises ``error`` where ``k`` is in ``fail``); returns what it
    served, (spec seed, due) in order."""
    clock, served = _Clock(), []

    async def serve(spec_seed, due):
        served.append((spec_seed, due))
        clock.now = max(clock.now, due) + lasts[len(served) - 1]
        if len(served) - 1 in fail:
            raise error

    asyncio.run(harness.run_agent(pool, at, close_s, serve, clock))
    return served


@pytest.mark.parametrize("error", [
    OSError("connection refused"), ValueError("invalid literal for int"),
    IndexError("list index out of range"),
    json.JSONDecodeError("Unterminated string", 'data: {"tok', 7)])
def test_next_session_is_due_when_the_last_stream_ends(error):
    """However a stream ends, refused, cut or garbled, the agent goes on
    to its next session."""
    served = _agent([10, 11, 12, 13, 14], 0.5, 8.0, [2.0, 3.0, 0.5, 1.0,
                                                       1.0], fail={1, 3},
                    error=error)
    assert served == [(10, 0.5), (11, 2.5), (12, 5.5), (13, 6.0),
                      (14, 7.0)]


def test_no_session_starts_at_or_after_close():
    served = _agent(list(range(50)), 1.0, 10.0, [3.0] * 50)
    assert [d for _, d in served] == [1.0, 4.0, 7.0]
    assert _agent([1, 2], 10.0, 10.0, [1.0, 1.0]) == []


def test_an_agent_that_runs_through_its_pool_raises():
    with pytest.raises(tr.PoolExhausted):
        _agent([1, 2, 3], 0.0, 10.0, [1.0] * 3)
    # a pool that lasts exactly to the close is not exhausted
    assert len(_agent([1, 2, 3], 0.0, 3.0, [1.0] * 3)) == 3


def test_a_closed_mix_without_tool_waits_is_refused(tmp_path):
    mix = json.loads((FIX / "traffic" / "tiny_plan_closed.json").read_text())
    mix["paradigm"]["turns_range"] = [1, 3]
    (tmp_path / "one_turn.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="tool"):
        tr.load_mix("one_turn", tmp_path)


def test_sweep_refuses_a_closed_mix():
    with pytest.raises(SystemExit, match="no session rate to sweep"):
        sweep.refuse_closed(_mix())
    sweep.refuse_closed(tr.load_mix("tiny_react", FIX / "traffic"))
