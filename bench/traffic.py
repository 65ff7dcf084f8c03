"""The one traffic generator: a seeded schedule of agent sessions.

A traffic mix is a JSON file ``bench/traffic/<name>.json`` of
parameters, read by :func:`load_mix`:

``paradigm``
    the agent paradigm and its Table-I lengths (cold, resume and decode
    ranges, turns, tool waits), copied from ``serving/workload.py`` so
    that the benchmark's yardstick cannot move with the program.  The
    server builds each session from ``{"workload", "seed",
    "token_scale"}``; :func:`make_session` rebuilds the same session
    here, draw for draw, so the benchmark knows every prompt, decode
    length and tool wait without asking the program.
``arrivals``
    ``poisson``: open-loop session starts at ``rate`` sessions/s.
    ``closed``: ``agents`` clients, each of which starts its next
    session when it reads the end of the previous one's stream; their
    first sessions start over ``stagger_s`` seconds.
``lead_in_s`` / ``drain_s``
    traffic before the window opens (set-up) and the bound on the wait
    for requests still in flight when it closes.

The lead-in and the window each hold a fixed set of sessions and of
arrival gaps, and ``--seed`` orders them: a window of ``L`` seconds
holds ``round(rate * L)`` sessions from its own pool of spec seeds,
with gaps at the midpoint quantiles of the exponential law, scaled to
fill it.  A window holds about ten sessions, so a Poisson draw per seed
would change the work it measures by tens of percent (on a TPU v5e,
``output_tok_s`` read 9.8 to 13.0 across seeds when each seed drew its
own sessions); a fixed set in the seed's order changes only how they
overlap.

A closed loop serves a fixed set too: agent ``a`` takes its sessions,
in turn and in a fixed order, from a pool of spec seeds of its own, so
every seed serves the same leading sessions; ``--seed`` decides only
which agent takes which first start.  The first starts lie at the
midpoint quantiles ``(i + 0.5) / agents`` of ``stagger_s``.  A pool
holds more sessions than its agent could run in the lead-in, the window
and the drain at :data:`POOL_FACTOR` times the fastest session rate the
mix allows (one session per its shortest tool waits); an agent that
runs through it is an error, not a wrap-around.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Union

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
ARRIVAL_KINDS = ("poisson", "closed")
# the lead-in draws its sessions from spec seeds this far past the
# window's
LEAD_IN_POOL = 1000
# a closed-loop agent's pool covers this many times the sessions it
# could run at the fastest rate the mix allows
POOL_FACTOR = 4


@dataclasses.dataclass(frozen=True)
class Turn:
    prefill_tokens: np.ndarray
    decode_len: int
    tool_latency_s: float


@dataclasses.dataclass(frozen=True)
class SessionPlan:
    spec_seed: int
    turns: List[Turn]

    @property
    def output_tokens(self) -> int:
        return sum(t.decode_len for t in self.turns)


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One open-loop session start (client clock, seconds from t0)."""
    at_s: float
    spec_seed: int


@dataclasses.dataclass(frozen=True)
class ClosedLoop:
    """Closed-loop agents: agent ``a`` starts its first session at
    ``first_s[a]`` (client clock, seconds from t0) and every next one
    when its client reads the end of the previous stream, taking spec
    seeds from ``pools[a]`` in order."""
    first_s: List[float]
    pools: List[Sequence[int]]


Schedule = Union[List[Arrival], ClosedLoop]


class PoolExhausted(RuntimeError):
    """A closed-loop agent ran through its pool before the window
    closed."""


def load_mix(name: str, traffic_dir: Path = TRAFFIC_DIR) -> Dict:
    path = Path(traffic_dir) / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in Path(traffic_dir).glob("*.json"))
        raise KeyError(f"unknown traffic mix {name!r}; known: {known}")
    mix = json.loads(path.read_text())
    kind = mix["arrivals"]["kind"]
    if kind not in ARRIVAL_KINDS:
        raise ValueError(f"{path}: arrivals.kind {kind!r} not in "
                         f"{ARRIVAL_KINDS}")
    if kind == "closed":
        if int(mix["arrivals"]["agents"]) < 1 \
                or float(mix["arrivals"]["stagger_s"]) < 0:
            raise ValueError(f"{path}: a closed mix needs agents >= 1 "
                             f"and stagger_s >= 0")
        if fastest_session_s(mix["paradigm"]) <= 0:
            raise ValueError(f"{path}: a closed mix needs sessions that "
                             f"wait on a tool at least once, so that an "
                             f"agent's pool can be sized")
    return mix


def _clipped_lognormal(rng, lo, hi, mean):
    mu = np.log(max(mean - lo, 1.0))
    x = lo + np.exp(rng.normal(mu, 0.55, size=None))
    return int(np.clip(np.round(x), lo, hi))


def make_session(paradigm: Dict, spec_seed: int, vocab_size: int,
                 token_scale: float) -> SessionPlan:
    """The session the server builds for ``{"seed": spec_seed}``: the
    same draws, in the same order, from the same generator."""
    rng = np.random.default_rng(spec_seed)

    def scale(n):
        return max(1, int(round(n * token_scale)))

    cold_len = scale(rng.integers(*paradigm["cold_range"]))
    cold = rng.integers(0, vocab_size, size=cold_len, dtype=np.int32)
    n_turns = int(rng.integers(*paradigm["turns_range"]))
    turns = [Turn(cold, scale(_clipped_lognormal(
        rng, *paradigm["decode_range"], paradigm["decode_mean"])),
        float(rng.uniform(*paradigm["tool_latency_range_s"])))]
    for _ in range(n_turns - 1):
        r_len = scale(_clipped_lognormal(
            rng, *paradigm["resume_range"], paradigm["resume_mean"]))
        toks = rng.integers(0, vocab_size, size=r_len, dtype=np.int32)
        turns.append(Turn(toks, scale(_clipped_lognormal(
            rng, *paradigm["decode_range"], paradigm["decode_mean"])),
            float(rng.uniform(*paradigm["tool_latency_range_s"]))))
    return SessionPlan(spec_seed, turns)


def max_session_tokens(paradigm: Dict, token_scale: float) -> int:
    """Upper bound on the KV rows one session can hold (the server's own
    rule, ``workload.max_session_tokens``, which refuses a spec that
    could outgrow its slot)."""
    def scale(n):
        return max(1, int(round(n * token_scale)))
    cold = scale(paradigm["cold_range"][1] - 1)
    turns = paradigm["turns_range"][1] - 1
    return (cold + max(cold // 8, 1)
            + (turns - 1) * scale(paradigm["resume_range"][1])
            + turns * scale(paradigm["decode_range"][1]))


def fastest_session_s(paradigm: Dict) -> float:
    """The shortest a session can last whatever the server: the tool
    waits of its fewest turns at their shortest."""
    return (paradigm["turns_range"][0] - 1) \
        * paradigm["tool_latency_range_s"][0]


def pool_size(mix: Dict, seconds: float) -> int:
    """Sessions in each closed-loop agent's pool."""
    span = float(mix["lead_in_s"]) + float(seconds) + float(mix["drain_s"])
    return math.ceil(POOL_FACTOR * span / fastest_session_s(mix["paradigm"]))


def _segment(rate: float, start: float, length: float, pool: int,
             rng: np.random.Generator) -> List[Arrival]:
    """``round(rate * length)`` session starts in ``[start, start +
    length)``: sessions ``pool, pool + 1, ...`` and gaps at the midpoint
    quantiles of Exp(rate), scaled to sum to ``length``, both in the
    order ``rng`` draws."""
    n = int(round(rate * length))
    if n == 0:
        return []
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = rng.permutation(gaps * (length / gaps.sum()))
    times = start + np.cumsum(gaps) - gaps
    seeds = rng.permutation(pool + np.arange(n))
    return [Arrival(float(t), int(s)) for t, s in zip(times, seeds)]


def _closed(mix: Dict, seconds: float, base: int,
            rng: np.random.Generator) -> ClosedLoop:
    """Agent ``a``'s pool is spec seeds ``base + a * size`` onwards, in
    that order whatever the seed; ``rng`` gives each agent its first
    start."""
    n = int(mix["arrivals"]["agents"])
    size = pool_size(mix, seconds)
    first = np.empty(n)
    first[rng.permutation(n)] = (float(mix["arrivals"]["stagger_s"])
                                 * (np.arange(n) + 0.5) / n)
    pools = [range(base + a * size, base + (a + 1) * size)
             for a in range(n)]
    return ClosedLoop([float(t) for t in first], pools)


def make_schedule(mix: Dict, seconds: float, seed: int) -> Schedule:
    """Session starts for the lead-in and a window of ``seconds``, in
    the order ``seed`` draws (a closed loop: its agents)."""
    base = int(mix.get("spec_seed_base", 0))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    if mix["arrivals"]["kind"] == "closed":
        return _closed(mix, seconds, base, rng)
    rate = float(mix["arrivals"]["rate"])
    lead = float(mix["lead_in_s"])
    return (_segment(rate, 0.0, lead, base + LEAD_IN_POOL, rng)
            + _segment(rate, lead, float(seconds), base, rng))


def spec_seeds(schedule: Schedule) -> List[int]:
    """Every spec seed the schedule can start."""
    if isinstance(schedule, ClosedLoop):
        return [s for pool in schedule.pools for s in pool]
    return [a.spec_seed for a in schedule]


class Plans(dict):
    """Session plans by spec seed, each made on its first lookup: a
    closed loop's pools are far larger than what a run starts."""

    def __init__(self, mix: Dict, seeds: Iterable[int], vocab_size: int):
        super().__init__()
        self._mix, self._vocab = mix, vocab_size
        self._seeds = frozenset(seeds)

    def __missing__(self, spec_seed: int) -> SessionPlan:
        if spec_seed not in self._seeds:
            raise KeyError(spec_seed)
        plan = self[spec_seed] = make_session(
            self._mix["paradigm"], spec_seed, self._vocab,
            float(self._mix["token_scale"]))
        return plan


def session_plans(mix: Dict, schedule: Schedule, vocab_size: int,
                  ) -> Plans:
    """The plan of every session the schedule can start, by spec seed."""
    return Plans(mix, spec_seeds(schedule), vocab_size)


def request_spec(mix: Dict, spec_seed: int) -> Dict:
    """The JSON body a client POSTs for one session."""
    return {"workload": mix["paradigm"]["name"], "seed": int(spec_seed),
            "token_scale": float(mix["token_scale"])}
