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
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
ARRIVAL_KINDS = ("poisson",)
# the lead-in draws its sessions from spec seeds this far past the
# window's
LEAD_IN_POOL = 1000


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


def make_schedule(mix: Dict, seconds: float, seed: int) -> List[Arrival]:
    """Session starts for the lead-in and a window of ``seconds``, in
    the order ``seed`` draws."""
    rate = float(mix["arrivals"]["rate"])
    base = int(mix.get("spec_seed_base", 0))
    lead = float(mix["lead_in_s"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    return (_segment(rate, 0.0, lead, base + LEAD_IN_POOL, rng)
            + _segment(rate, lead, float(seconds), base, rng))


def spec_seeds(schedule: List[Arrival]) -> List[int]:
    return [a.spec_seed for a in schedule]


def session_plans(mix: Dict, schedule: List[Arrival], vocab_size: int,
                  ) -> Dict[int, SessionPlan]:
    return {s: make_session(mix["paradigm"], s, vocab_size,
                            float(mix["token_scale"]))
            for s in spec_seeds(schedule)}


def request_spec(mix: Dict, spec_seed: int) -> Dict:
    """The JSON body a client POSTs for one session."""
    return {"workload": mix["paradigm"]["name"], "seed": int(spec_seed),
            "token_scale": float(mix["token_scale"])}
