"""Serving substrate: KV pool invariants (hypothesis), workload Table-I
distributions, metrics, an end-to-end engine run per policy, and the
reactor-refactor regression guard (golden trace + oracle streams)."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from _serving_util import events_by_session, oracle_streams

from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.kvcache import KVCachePool
from repro.serving.metrics import SLOThresholds, collect_tpots
from repro.serving.policies import POLICIES
from repro.serving.reactor import EngineReactor, HandleStatus
from repro.serving.request import SessionState
from repro.serving.workload import make_workload, table1_statistics

GOLDEN = pathlib.Path(__file__).parent / "golden" / "serving_golden.json"

TINY = ModelConfig(name="tiny-serve", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=128, tie_embeddings=True, source="test")


# ---------------------------------------------------------------------------
# KV cache pool
# ---------------------------------------------------------------------------

def test_pool_alloc_free_cycle():
    pool = KVCachePool(TINY, 4, 64)
    slots = [pool.alloc() for _ in range(4)]
    assert len(set(slots)) == 4 and pool.free_slots == 0
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.free(slots[0])
    assert pool.alloc() == slots[0]


def test_prefix_snapshot_roundtrip():
    pool = KVCachePool(TINY, 4, 64)
    s = pool.alloc()
    toks = np.arange(10, dtype=np.int32)
    # write something recognisable into the slot
    pool.cache = jax.tree.map(lambda l: l.at[:, s].set(1.0), pool.cache)
    pool.lengths[s] = 10
    pool.register_prefix(s, toks)
    d = pool.alloc()
    entry = pool.lookup(toks)
    assert entry is not None and entry.length == 10
    pool.restore_prefix(d, entry)
    assert pool.lengths[d] == 10
    for leaf in jax.tree_util.tree_leaves(pool.cache):
        np.testing.assert_array_equal(np.asarray(leaf[:, d]),
                                      np.asarray(leaf[:, s]))
    assert pool.lookup(np.arange(11, dtype=np.int32)) is None


def test_prefix_eviction_is_lru():
    """Eviction must be least-recently-used, not min-refs: under
    min-refs an old hot prefix (many hits) can never be displaced and a
    fresh deployment's prompt is thrashed forever."""
    pool = KVCachePool(TINY, 4, 64, max_prefix_entries=2)
    slot = pool.alloc()
    a = np.arange(5, dtype=np.int32)
    b = np.arange(6, dtype=np.int32)
    c = np.arange(7, dtype=np.int32)

    def reg(tokens):
        pool.lengths[slot] = len(tokens)
        pool.register_prefix(slot, tokens)

    reg(a)
    for _ in range(3):                      # a: hot (3 hits) but stale
        assert pool.lookup(a) is not None
    reg(b)                                  # b: fresh, zero hits
    reg(c)                                  # at capacity -> evict LRU (a)
    assert pool.lookup(b) is not None       # fresh prefix survives
    assert pool.lookup(c) is not None
    assert pool.lookup(a) is None           # stale-hot one was evicted
    assert pool.stats["evictions"] == 1


def test_register_prefix_refresh_on_exact_key_hit():
    """Re-registering an already-cached prefix must not re-snapshot or
    evict another entry at capacity — it only refreshes recency (so the
    re-registered prefix is treated as just-used by LRU eviction)."""
    pool = KVCachePool(TINY, 4, 64, max_prefix_entries=2)
    slot = pool.alloc()
    a = np.arange(5, dtype=np.int32)
    b = np.arange(6, dtype=np.int32)
    c = np.arange(7, dtype=np.int32)

    def reg(tokens):
        pool.lengths[slot] = len(tokens)
        pool.register_prefix(slot, tokens)

    reg(a)
    reg(b)                                  # at capacity, no eviction yet
    reg(a)                                  # exact-key hit: refresh only
    assert pool.stats["prefix_refreshes"] == 1
    assert pool.stats["evictions"] == 0     # the old code evicted here
    reg(c)                                  # LRU is now b, not a
    assert pool.lookup(a) is not None
    assert pool.lookup(c) is not None
    assert pool.lookup(b) is None
    assert pool.stats["evictions"] == 1


@given(mask=st.lists(st.booleans(), min_size=4, max_size=4))
@settings(max_examples=20, deadline=None)
def test_commit_mask_protects_inactive(mask):
    """commit() must only update rows where mask is True — the property
    that keeps inactive sessions' SSM states untouched."""
    pool = KVCachePool(TINY, 4, 16)
    old = pool.cache
    new = jax.tree.map(lambda l: l + 1.0, old)
    pool.commit(new, np.asarray(mask))
    for leaf_new, leaf_cur in zip(jax.tree_util.tree_leaves(new),
                                  jax.tree_util.tree_leaves(pool.cache)):
        for b, m in enumerate(mask):
            expect = leaf_new[:, b] if m else leaf_new[:, b] * 0.0
            np.testing.assert_allclose(np.asarray(leaf_cur[:, b]),
                                       np.asarray(expect))


# ---------------------------------------------------------------------------
# workload (Table I)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload,res_rng,dec_rng", [
    ("react", (30, 127), (27, 127)),
    ("plan_execute", (125, 421), (33, 141)),
])
def test_table1_distributions(workload, res_rng, dec_rng):
    stats = table1_statistics(workload, n=100)
    assert 2500 <= stats["cold_prefill"]["min"]
    assert stats["cold_prefill"]["max"] <= 3500 + 3500 // 8
    assert res_rng[0] <= stats["resume_prefill"]["min"]
    assert stats["resume_prefill"]["max"] <= res_rng[1]
    assert dec_rng[0] <= stats["decode"]["min"]
    assert stats["decode"]["max"] <= dec_rng[1]


def test_workload_scaling_and_shared_prefix():
    ws = make_workload(4, vocab_size=128, token_scale=0.25,
                       num_system_prompts=1, seed=3)
    assert all(s.shared_prefix_len > 0 for s in ws)
    a, b = ws[0], ws[1]
    pa = a.turns[0].prefill_tokens[:min(a.shared_prefix_len,
                                        b.shared_prefix_len)]
    pb = b.turns[0].prefill_tokens[:len(pa)]
    np.testing.assert_array_equal(pa, pb)   # shared system prompt


# ---------------------------------------------------------------------------
# engine end-to-end (one per policy)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_parts():
    params = init_params(TINY, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=4, max_seq=512, cycle_budget=80,
                        granularity=8, b_min=8, b_max=128, b_init=32,
                        delta_b=8, control_interval_s=0.05, max_wall_s=60.0)
    return params, ecfg


@pytest.mark.parametrize("policy", ["agentserve", "pd_static", "chunked",
                                    "fcfs"])
def test_engine_end_to_end(tiny_engine_parts, policy):
    params, ecfg = tiny_engine_parts
    sessions = make_workload(3, workload="react", vocab_size=TINY.vocab_size,
                             token_scale=0.0625, num_system_prompts=1,
                             seed=0, stagger_s=0.05)
    eng = ServingEngine(TINY, params, POLICIES[policy], ecfg)
    rep = eng.run(sessions, SLOThresholds(ttft_s=5.0, tpot_s=1.0))
    assert all(s.state == SessionState.FINISHED for s in sessions)
    assert rep.total_output_tokens > 0
    assert rep.throughput_tok_s > 0
    assert np.isfinite(rep.ttft_p50_s) and np.isfinite(rep.tpot_p50_s)
    # every turn produced its full decode burst
    for s in sessions:
        assert s.output_tokens() == sum(t.decode_len for t in s.turns)


def test_agentserve_isolation_invariant(tiny_engine_parts):
    """Cold prefills never enter Q_D (checked via the admission log)."""
    params, ecfg = tiny_engine_parts
    sessions = make_workload(3, vocab_size=TINY.vocab_size,
                             token_scale=0.0625, seed=1)
    eng = ServingEngine(TINY, params, POLICIES["agentserve"], ecfg)
    eng.run(sessions)
    assert eng.slots.stats.rebinds >= 1
    assert eng.slots.stats.misses == 0      # everything pre-established


def test_no_green_pays_on_demand(tiny_engine_parts):
    params, ecfg = tiny_engine_parts
    sessions = make_workload(2, vocab_size=TINY.vocab_size,
                             token_scale=0.0625, seed=2)
    eng = ServingEngine(TINY, params, POLICIES["no_green"], ecfg)
    eng.run(sessions)
    assert eng.slots.stats.misses >= 1      # built inside the serving path


# ---------------------------------------------------------------------------
# reactor refactor regression guard (golden trace + oracle streams)
# ---------------------------------------------------------------------------

def _golden_workload_and_engine(params, record_events=True):
    g = json.loads(GOLDEN.read_text())
    w = g["workload"]
    sessions = make_workload(w["n"], workload=w["workload"],
                             vocab_size=w["vocab_size"],
                             token_scale=w["token_scale"],
                             num_system_prompts=w["num_system_prompts"],
                             seed=w["seed"], stagger_s=w["stagger_s"])
    ecfg = EngineConfig(**g["engine_cfg"], record_events=record_events)
    eng = ServingEngine(TINY, params, POLICIES["agentserve"], ecfg)
    return g, sessions, eng


def test_run_matches_pre_refactor_golden(tiny_engine_parts):
    """run() rebuilt on the reactor must reproduce the pre-refactor
    engine's golden trace: the deterministic ServingReport fields and
    per-session outcomes recorded from commit 8559b36, plus
    token-for-token identity of the emitted streams against the
    scheduling-independent oracle."""
    params, _ = tiny_engine_parts
    g, sessions, eng = _golden_workload_and_engine(params)
    rep = eng.run(sessions)

    assert rep.policy == g["policy"]
    assert rep.num_sessions == g["num_sessions"]
    assert rep.total_output_tokens == g["total_output_tokens"]
    assert eng.slots.stats.misses == g["slot_misses"]
    for s, gs in zip(sessions, g["per_session"]):
        assert s.session_id == gs["session_id"]
        assert s.output_tokens() == gs["output_tokens"]
        assert len(s.request_arrivals) == gs["num_requests"]
        assert len(s.first_token_s) == gs["num_first_tokens"]
        assert int(s.last_token) == gs["final_token"]
        assert [t.decode_len for t in s.turns] == gs["turn_decode_lens"]

    # token-for-token: the event stream run() recorded must equal the
    # isolated greedy reference for every session
    streams = events_by_session(eng.event_log)
    want = oracle_streams(TINY, params, sessions,
                          num_slots=eng.ecfg.num_slots,
                          max_seq=eng.ecfg.max_seq)
    for s in sessions:
        assert streams[s.session_id] == want[s.session_id]
        assert len(streams[s.session_id]) == s.output_tokens()


def test_reactor_manual_drive_matches_run(tiny_engine_parts):
    """Driving submit/step/poll by hand must produce the same streams
    and session outcomes as the packaged run() loop."""
    params, _ = tiny_engine_parts
    g, sessions, eng = _golden_workload_and_engine(params)
    reactor = EngineReactor(eng)
    handles = [reactor.submit(s, arrival_s=s.ready_s) for s in sessions]
    events = reactor.drain(max_wall_s=60.0)

    assert all(reactor.poll(h) is HandleStatus.DONE for h in handles)
    # poll-side delivery: every emitted event is also on its handle
    assert sum(len(reactor.take_events(h)) for h in handles) == len(events)
    streams = events_by_session(events)
    want = oracle_streams(TINY, params, sessions,
                          num_slots=eng.ecfg.num_slots,
                          max_seq=eng.ecfg.max_seq)
    for s, gs in zip(sessions, g["per_session"]):
        assert streams[s.session_id] == want[s.session_id]
        assert s.output_tokens() == gs["output_tokens"]
        assert int(s.last_token) == gs["final_token"]


def test_park_unpark_preserves_resume(tiny_engine_parts):
    """A TOOL_WAIT session whose KV slot is released under pressure must
    resume with a bit-identical stream: park snapshots the slot
    (attention KV + any SSM state), the slot serves another session,
    and unpark restores it losslessly."""
    params, ecfg = tiny_engine_parts
    sessions = make_workload(2, vocab_size=TINY.vocab_size,
                             token_scale=0.0625, seed=4, stagger_s=0.0)
    for s in sessions:
        s.external_tools = True         # gateway-style tool clock
    eng = ServingEngine(TINY, params, POLICIES["agentserve"], ecfg)
    reactor = EngineReactor(eng)
    handles = [reactor.submit(s) for s in sessions]
    events = []
    parked_once = False
    for _ in range(200_000):
        events.extend(reactor.step())
        for s in sessions:
            if s.state != SessionState.TOOL_WAIT:
                continue
            if not parked_once:
                # the hold default: the slot is still owned in TOOL_WAIT
                assert s.slot >= 0
                free_before = eng.pool.free_slots
                eng.park_session(s.session_id)
                assert s.slot == -1
                assert eng.pool.free_slots == free_before + 1
                parked_once = True
            eng.resume_session(s.session_id)   # tool done immediately
        if not reactor.pending():
            break
    else:
        raise AssertionError("sessions never finished")
    reactor.drain(max_wall_s=10.0)
    assert eng.hotpath_stats["parks"] == 1
    assert eng.hotpath_stats["unparks"] == 1

    streams = events_by_session(events)
    want = oracle_streams(TINY, params, sessions,
                          num_slots=ecfg.num_slots, max_seq=ecfg.max_seq)
    for s in sessions:
        assert streams[s.session_id] == want[s.session_id]
    assert all(reactor.poll(h) is HandleStatus.DONE for h in handles)


def test_device_mirrors_are_not_host_aliases():
    """``_sync_device_state`` uploads copies of the host mirrors.  On the
    CPU ``jnp.asarray`` may alias a small NumPy buffer, and the engine
    advances ``pool.lengths`` (and the spec history) in place right after
    dispatching the step that reads them: an aliased upload would hand
    that step the advanced lengths."""
    import types

    def aligned(shape):
        # XLA:CPU aliases host buffers aligned to 64 bytes
        buf = np.zeros(int(np.prod(shape)) + 64, np.int32)
        off = (-buf.ctypes.data % 64) // buf.itemsize
        return buf[off:off + int(np.prod(shape))].reshape(shape)
    lengths, history = aligned((4,)), aligned((4, 8))
    lengths[[0, 2]] = 7, 3
    eng = types.SimpleNamespace(
        _dev_dirty=True, _dev_ids=[], _window_steps=0, _spec_on=True,
        _hist_dirty=True, _history=history,
        ecfg=types.SimpleNamespace(num_slots=4),
        pool=types.SimpleNamespace(lengths=lengths))
    active = [types.SimpleNamespace(session_id=0, slot=0, last_token=5)]
    ServingEngine._sync_device_state(eng, active)
    lengths += 1000
    history[0, :3] = 9
    np.testing.assert_array_equal(np.asarray(eng._dev_lengths), [7, 0, 3, 0])
    assert not np.asarray(eng._dev_history).any()
