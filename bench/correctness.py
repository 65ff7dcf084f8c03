"""The comparison that decides ``correct``.

After the window, a sample of sessions drawn from the seed (the one with
the longest served context always among them) is run through the plain
reference (:mod:`bench.reference`) over its prompts and the tokens the
clients received, in the order the server's cache holds them: each
turn's prompt, then its served tokens but the last (the last token of
a burst is never fed back; the next turn's prompt follows it).  Every
served token of every finished turn in the sample is compared: the
number is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position.  The served
tokens are greedy, so a sound server reads near-ties only.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.traffic import SessionPlan

# sessions compared per run: the longest, then others from the seed
MAX_SESSIONS = 8
# the reference at these numerics is put in the program's place for
# the readings a limit is set from (``bench/control.py``); the last
# only for a configuration with q/k/v biases, which it leaves out
CONTROLS = ("int8", "bfloat16", "float32_default", "float32_nobias")


@dataclasses.dataclass
class Sample:
    tokens: np.ndarray        # the context the server's cache held
    rows: np.ndarray          # positions whose logits chose a token
    targets: np.ndarray       # the token served from each of those rows
    turns: np.ndarray         # the turn each target was served in


def session_sample(plan: SessionPlan, served: Sequence[Sequence[int]],
                   ) -> Sample:
    """``served[k]``: the tokens of turn ``k`` as received, finished
    turns only, in turn order."""
    toks: List[int] = []
    rows: List[int] = []
    targets: List[int] = []
    turns: List[int] = []
    for k, (turn, out) in enumerate(zip(plan.turns, served)):
        turns.extend([k] * len(out))
        toks.extend(int(t) for t in turn.prefill_tokens)
        rows.append(len(toks) - 1)
        targets.append(int(out[0]))
        for j in range(1, len(out)):
            toks.append(int(out[j - 1]))
            rows.append(len(toks) - 1)
            targets.append(int(out[j]))
    return Sample(np.asarray(toks, np.int32), np.asarray(rows, np.int32),
                  np.asarray(targets, np.int32), np.asarray(turns, np.int32))


def choose(candidates: Dict[int, int], rng: np.random.Generator,
           max_sessions: int) -> List[int]:
    """Keys of ``candidates`` (key -> context length): the longest, then
    up to ``max_sessions - 1`` others in the order ``rng`` draws."""
    if not candidates:
        return []
    keys = sorted(candidates)
    first = max(keys, key=lambda k: (candidates[k], -k))
    rest = [k for k in rng.permutation(keys).tolist() if k != first]
    return [first] + rest[:max_sessions - 1]


def token_gaps(params: Dict, cfg: Dict, samples: Sequence[Sample],
               pad_to: int) -> np.ndarray:
    """Per served token, the gap by which its float32 reference logit
    lies below the reference's best."""
    from bench import reference
    out = [np.zeros((0,), np.float32)]
    for s in samples:
        h = reference.final_hidden(params, cfg, s.tokens, pad_to)
        best, _, at = reference.logit_stats(params, h, s.rows, s.targets)
        out.append(np.asarray(best - at, np.float32))
    return np.concatenate(out)


def control_gaps(params: Dict, cfg: Dict, samples: Sequence[Sample],
                 pad_to: int, numerics: str) -> np.ndarray:
    """The reference computed at ``numerics`` (``reference.NUMERICS``)
    put in the program's place: at each compared position, the gap of
    the token it puts first, measured against the float32 reference's
    best."""
    from bench import reference
    out = [np.zeros((0,), np.float32)]
    for s in samples:
        h_lo = reference.final_hidden(params, cfg, s.tokens, pad_to,
                                      numerics=numerics)
        _, pick, _ = reference.logit_stats(params, h_lo, s.rows, s.targets,
                                           numerics=numerics)
        h = reference.final_hidden(params, cfg, s.tokens, pad_to)
        best, _, at = reference.logit_stats(params, h, s.rows, pick)
        out.append(np.asarray(best - at, np.float32))
    return np.concatenate(out)


def controls(cfg: Dict) -> Tuple[str, ...]:
    """The controls that apply to the configuration."""
    return tuple(c for c in CONTROLS
                 if c != "float32_nobias" or cfg.get("qkv_bias", False))


def gap_readings(gaps: np.ndarray) -> Dict[str, float]:
    """The widest gap, the mean gap, and the share of tokens that are
    not the reference's first choice."""
    if not len(gaps):
        return {"max_logit_gap": 0.0, "mean_logit_gap": 0.0,
                "mismatch_share": 0.0}
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps)),
            "mismatch_share": float(np.mean(gaps > 0))}


def check(cell, obs, plans: Dict[int, SessionPlan], seed: int,
          pad_to: int, bad_turns: Sequence[int],
          reference_control: bool = False) -> Tuple[Dict, Dict]:
    """Every compared number beside its limit (the configuration's
    ``correct`` block; PERF.md gives the readings each was set from),
    and the readings: the program's gaps and, with
    ``reference_control``, those of the reference at each of
    :data:`CONTROLS` in the program's place on the same sample."""
    from bench import harness, model
    rng = np.random.default_rng([7, seed & 0xFFFFFFFF, seed >> 32])
    done = {}
    for i, st in enumerate(obs.streams):
        turns = harness.finished_turns(st, plans[st.spec_seed])
        if turns:
            done[i] = session_sample(plans[st.spec_seed], turns)
    limits = cell.config["correct"]
    least = int(limits["tokens_compared_at_least"])
    pick = choose({i: len(s.tokens) for i, s in done.items()}, rng,
                  MAX_SESSIONS)
    samples = [done[i] for i in pick]
    params = model.make_params(cell.config, seed)
    t0 = time.perf_counter()
    gaps = token_gaps(params, cell.config, samples, pad_to)
    readings = gap_readings(gaps)
    gap, compared = readings["max_logit_gap"], len(gaps)
    control = {}
    for numerics in controls(cell.config) if reference_control else ():
        control.update({f"ref_{numerics}_{k}": v for k, v in gap_readings(
            control_gaps(params, cell.config, samples, pad_to,
                         numerics)).items()})
    print(f"bench: reference over {len(samples)} sessions "
          f"({sum(len(s.tokens) for s in samples)} tokens) in "
          f"{time.perf_counter() - t0:.2f} s; readings {readings}",
          file=sys.stderr)
    limit = float(limits["max_logit_gap"])
    at = 0
    for i, smp in zip(pick, samples):
        g = gaps[at:at + len(smp.targets)]
        at += len(smp.targets)
        over = np.flatnonzero(g > limit)
        where = (f"; first over the limit in turn {smp.turns[over[0]]}, "
                 f"token {over[0] - np.searchsorted(smp.turns, smp.turns[over[0]])}"
                 if len(over) else "")
        print(f"bench: session {obs.streams[i].spec_seed}: {len(g)} tokens,"
              f" widest gap {float(g.max(initial=0.0)):.4f}, {len(over)} over"
              f" the limit{where}", file=sys.stderr)
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit,
                          "ok": gap <= limit},
        "tokens_compared_at_least": {"value": compared, "limit": least,
                                     "ok": compared >= least},
        "bad_turns": {"value": len(bad_turns), "limit": 0,
                      "ok": not bad_turns},
    }
    return checks, {**control, **{f"program_{k}": v
                                  for k, v in readings.items()}}
