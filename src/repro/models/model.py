"""Model assembly: stacked repeating groups + ``lax.scan`` over them.

All architectures are expressed as ``num_groups`` repetitions of a
statically-described *group* of layers (``group_size`` =
lcm(hybrid_period, moe.every), e.g. Jamba: 9 groups x 8 layers).  Per
layer-slot parameters are stacked along a leading ``num_groups`` axis so
the whole depth compiles as a single scanned HLO body — this keeps the
80 dry-run compiles tractable and is also how remat is applied.

Public entry points:
  init_params / params_shape      — weights (or their ShapeDtypeStructs)
  init_cache  / cache_shape       — decode caches (KV + SSM state)
  forward_train                   — full causal (or encoder) forward
  forward_prefill                 — chunk prefill writing into a cache
  forward_decode                  — one token per active sequence
  forward_decode_fused            — decode + greedy sample + cache merge,
                                    fully device-resident (DESIGN.md §3)
  forward_decode_megastep         — K fused decode steps in one lax.scan
  forward_resume_batch            — M resume prefills packed in one call
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba2
from repro.models.blocks import (LayerSpec, apply_layer, init_layer,
                                 layer_specs_for_group)
from repro.models.common import embed_init, rms_norm, split_keys


def group_layout(cfg: ModelConfig) -> Tuple[int, int, Tuple[LayerSpec, ...]]:
    period = cfg.hybrid_period or 1
    every = cfg.moe.every if cfg.moe else 1
    group_size = math.lcm(period, every)
    assert cfg.num_layers % group_size == 0, (cfg.name, group_size)
    return cfg.num_layers // group_size, group_size, layer_specs_for_group(cfg, group_size)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    G, gs, specs = group_layout(cfg)
    k_embed, k_groups, k_head = split_keys(key, 3)

    def one_group(k):
        ks = split_keys(k, gs)
        return {f"l{j}": init_layer(ks[j], cfg, specs[j], dtype)
                for j in range(gs)}

    gkeys = split_keys(k_groups, G)
    groups = jax.tree.map(lambda *xs: jnp.stack(xs), *[one_group(k) for k in gkeys])
    params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "groups": groups,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, cfg.padded_vocab, cfg.d_model,
                                       dtype).T
    return params


def params_shape(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct pytree of the parameters (no allocation)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))


def num_kv_pages(cfg: ModelConfig, batch: int, max_seq: int) -> int:
    """Default usable page count for the paged layout: capacity parity
    with the slab layout (``batch`` full-length stripes)."""
    return batch * (-(-max_seq // cfg.kv_page_size))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.float32, kv_quant: bool = False,
               num_pages: int = 0) -> Dict[str, Any]:
    """Decode/serving cache for one model: stacked over groups.
    ``kv_quant``: int8 values + per-(position, head) scales (§Perf).

    With ``cfg.kv_layout == "paged"`` the *positional* leaves (attention
    K/V and quant scales) become a flat page arena
    ``[G, num_pages + 1, page_size, Hk, hd]`` addressed through
    per-session block tables (DESIGN.md §8); the extra last page is the
    write scratch page (never read).  SSM/stateful leaves stay per-slot
    point summaries — a recurrent state is a length-point snapshot, not
    a positional row (the Marconi argument), so paging it would buy
    nothing and break the COW sharing invariants."""
    G, gs, specs = group_layout(cfg)
    paged = cfg.kv_layout == "paged"
    if paged:
        assert max_seq % cfg.kv_page_size == 0, (max_seq, cfg.kv_page_size)
        if num_pages <= 0:
            num_pages = num_kv_pages(cfg, batch, max_seq)
    cache: Dict[str, Any] = {}
    for j, spec in enumerate(specs):
        if spec.kind == "attn":
            if paged:
                shape = (G, num_pages + 1, cfg.kv_page_size,
                         cfg.num_kv_heads, cfg.head_dim)
            else:
                shape = (G, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
            # k/v (and scale) leaves must be *distinct* buffers: donating
            # executables (fused decode, batched resume, fused prefix
            # restore) reject a pytree that donates one buffer twice
            if kv_quant:
                cache[f"l{j}"] = {"k": jnp.zeros(shape, jnp.int8),
                                  "v": jnp.zeros(shape, jnp.int8),
                                  "ks": jnp.zeros(shape[:-1] + (1,), dtype),
                                  "vs": jnp.zeros(shape[:-1] + (1,), dtype)}
                continue
            cache[f"l{j}"] = {"k": jnp.zeros(shape, dtype),
                              "v": jnp.zeros(shape, dtype)}
        else:
            st = mamba2.init_ssm_state(batch, cfg.d_model, cfg.ssm, dtype)
            cache[f"l{j}"] = {
                k: jnp.zeros((G,) + v.shape, v.dtype)
                for k, v in st._asdict().items()
            }
    return cache


def cache_shape(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.float32, kv_quant: bool = False,
                num_pages: int = 0):
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_seq, dtype, kv_quant, num_pages))


# ---------------------------------------------------------------------------
# forward core
# ---------------------------------------------------------------------------


def _sqrt_divisor(n: int) -> int:
    best = 1
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            best = d
    return best


def _scan_groups(params, x, cfg: ModelConfig, *, mode: str, positions,
                 lengths, cache, window: int, moe_mode: str,
                 remat: bool = False, block_size: int = 512,
                 moe_capacity: float = 1.25, moe_shards: int = 1,
                 seq_parallel=None, block_tables=None,
                 write_positions=None, ssm_valid=None):
    G, gs, specs = group_layout(cfg)
    from repro.distributed.context import current_spmd
    spmd = current_spmd()
    S = x.shape[1]
    constrain = (spmd is not None and spmd.shard_activations
                 and mode in ("train", "encode")
                 and S % spmd.tp_size == 0 and S > 1)

    def body(carry, xs):
        h, aux = carry
        gparams, gcache = xs
        new_gcache = {} if gcache is not None else None
        for j, spec in enumerate(specs):
            lc = gcache.get(f"l{j}") if gcache is not None else None
            if spec.kind == "ssm" and lc is None:
                # train/cold path still needs a zero state to scan from
                st = mamba2.init_ssm_state(h.shape[0], cfg.d_model, cfg.ssm,
                                           h.dtype)
                lc = st._asdict()

            def layer_fn(lp, h_in, lc_in, _spec=spec):
                return apply_layer(
                    lp, h_in, cfg, _spec, mode=mode,
                    positions=positions, lengths=lengths, layer_cache=lc_in,
                    window=window, moe_mode=moe_mode, block_size=block_size,
                    moe_capacity=moe_capacity, moe_shards=moe_shards,
                    seq_parallel=seq_parallel, block_tables=block_tables,
                    write_positions=write_positions, ssm_valid=ssm_valid)

            if remat and gs > 1:
                # per-layer remat within the group body: without this, a
                # multi-layer group (Jamba: 8) keeps every layer's
                # residuals live at once during the body's backward
                layer_fn = jax.checkpoint(layer_fn)
            h, lc, a = layer_fn(gparams[f"l{j}"], h, lc)
            aux = aux + a
            if new_gcache is not None:
                new_gcache[f"l{j}"] = lc
        if constrain:
            # Megatron-style sequence parallelism for the stored carry:
            # scan carries persist per iteration; sharding them over the
            # tensor axis divides that storage by tp_size (the re-gather
            # happens at the next group's attention anyway).
            from jax.sharding import NamedSharding, PartitionSpec as P
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(spmd.mesh,
                                 P(spmd.dp_axes, spmd.tp_axis, None)))
        return (h, aux), new_gcache

    if remat:
        body = jax.checkpoint(body)

    aux0 = jnp.zeros((), jnp.float32)
    G1 = _sqrt_divisor(G) if (remat and cache is None) else 1
    if G1 > 1:
        # 2-level (sqrt-depth) remat scan: peak carry storage drops from
        # G * |h| to (G1 + G/G1) * |h| at one extra forward recompute.
        G2 = G // G1
        xs2 = jax.tree.map(
            lambda a: a.reshape((G1, G2) + a.shape[1:]), params["groups"])

        def outer(carry, xs):
            return jax.lax.scan(body, carry, xs)

        (x, aux), _ = jax.lax.scan(jax.checkpoint(outer), (x, aux0),
                                   (xs2, None))
        return x, aux, None

    if cache is None:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), (params["groups"], None))
        return x, aux, None

    # the stacked cache rides in the carry and each group's slice is
    # written back in place: stacking new slices as scan outputs would
    # build a second whole cache per call (per step inside a megastep
    # scan), which at published widths exceeds a 16 GB chip
    def body_inplace(carry, xs):
        h, aux, full = carry
        gparams, g = xs
        gcache = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, g, 0, keepdims=False),
            full)
        (h, aux), new_g = body((h, aux), (gparams, gcache))
        full = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, g, 0),
            full, new_g)
        return (h, aux, full), None

    (x, aux, new_cache), _ = jax.lax.scan(
        body_inplace, (x, aux0, cache),
        (params["groups"], jnp.arange(G, dtype=jnp.int32)))
    return x, aux, new_cache


def _logits(params, cfg: ModelConfig, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["embed"].T if cfg.tie_embeddings
              else h @ params["lm_head"])
    if cfg.padded_vocab != cfg.vocab_size:
        logits = logits[..., : cfg.vocab_size]
    return logits


def _embed(params, cfg: ModelConfig, tokens=None, embeds=None):
    if embeds is not None:
        return embeds
    return params["embed"][tokens]


# ---------------------------------------------------------------------------
# public forwards
# ---------------------------------------------------------------------------


def forward_train(params, cfg: ModelConfig, tokens=None, *, embeds=None,
                  positions=None, moe_mode: str = "gmm", remat: bool = False,
                  window_override: Optional[int] = None,
                  block_size: int = 512, moe_capacity: float = 1.25,
                  moe_shards: int = 1, return_hidden: bool = False):
    """Full forward producing logits for every position.

    ``embeds`` (instead of ``tokens``) is the sanctioned modality-stub
    entry point for audio/VLM frontends."""
    x = _embed(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mode = "encode" if cfg.encoder_only else "train"
    window = cfg.sliding_window if window_override is None else window_override
    lengths = jnp.zeros((B,), jnp.int32)
    h, aux, _ = _scan_groups(params, x, cfg, mode=mode, positions=positions,
                             lengths=lengths, cache=None, window=window,
                             moe_mode=moe_mode, remat=remat,
                             block_size=block_size, moe_capacity=moe_capacity,
                             moe_shards=moe_shards)
    if return_hidden:
        return h, aux
    return _logits(params, cfg, h), aux


def forward_cold(params, cfg: ModelConfig, tokens=None, *, embeds=None,
                 moe_mode: str = "gmm", remat: bool = False,
                 window_override: Optional[int] = None,
                 block_size: int = 512, moe_shards: int = 1):
    """Cold prefill without a persistent cache: full causal (or encoder)
    forward returning ONLY the last-position logits [B, vocab] — the
    serving TTFT path, and the prefill_32k dry-run step (materialising
    [B, S, vocab] logits at 32k would not fit HBM)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    mode = "encode" if cfg.encoder_only else "train"
    window = cfg.sliding_window if window_override is None else window_override
    lengths = jnp.zeros((B,), jnp.int32)
    h, aux, _ = _scan_groups(params, x, cfg, mode=mode, positions=positions,
                             lengths=lengths, cache=None, window=window,
                             moe_mode=moe_mode, remat=remat,
                             block_size=block_size, moe_shards=moe_shards)
    return _logits(params, cfg, h[:, -1:, :])[:, 0]


def forward_prefill(params, cfg: ModelConfig, tokens, cache, lengths, *,
                    embeds=None, moe_mode: str = "gmm",
                    window_override: Optional[int] = None,
                    block_size: int = 512, moe_capacity: float = 1.25,
                    moe_shards: int = 1, logit_idx=None, block_tables=None):
    """Process a chunk (cold or resume prefill), writing into ``cache``.

    tokens: [B, S] appended at per-batch offsets ``lengths`` [B].
    ``logit_idx`` [B]: position within the chunk whose logits to return
    (defaults to the last — engines pass the last *unpadded* position).
    ``block_tables`` [B, P_max] selects the paged cache layout: chunk
    rows scatter into the page arena through the table instead of into
    per-slot stripes (DESIGN.md §8).
    Returns (logits [B, vocab], new_cache, new_lengths)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    window = cfg.sliding_window if window_override is None else window_override
    # logit_idx marks the last real token per row, so it also fences the
    # SSM state update against executable-shape padding (mamba2.py)
    ssm_valid = None if logit_idx is None else logit_idx + 1
    h, aux, new_cache = _scan_groups(
        params, x, cfg, mode="prefill", positions=positions, lengths=lengths,
        cache=cache, window=window, moe_mode=moe_mode,
        block_size=block_size, moe_capacity=moe_capacity,
        moe_shards=moe_shards, block_tables=block_tables,
        ssm_valid=ssm_valid)
    if logit_idx is None:
        h_last = h[:, -1:, :]
    else:
        h_last = jnp.take_along_axis(h, logit_idx[:, None, None], axis=1)
    logits = _logits(params, cfg, h_last)[:, 0]
    return logits, new_cache, lengths + S


def forward_decode(params, cfg: ModelConfig, tokens, cache, lengths, *,
                   moe_mode: str = "gmm",
                   window_override: Optional[int] = None,
                   moe_capacity: float = 1.25, moe_shards: int = 1,
                   seq_parallel=None, block_tables=None,
                   write_positions=None):
    """One decode step. tokens: [B] (last sampled token per sequence).

    ``write_positions`` [B] decouples where the new K/V row lands from
    the attention valid-length: the fused hot path redirects *inactive*
    lanes' writes to scratch while their attention extent stays
    O(real length) — without it ``lengths`` would have to be pinned to
    the scratch position for idle lanes (the DESIGN.md §3 follow-up).
    Defaults to ``lengths`` (the seed behaviour).
    Returns (logits [B, vocab], new_cache, new_lengths)."""
    x = _embed(params, cfg, tokens[:, None])
    B = x.shape[0]
    positions = lengths[:, None]
    window = cfg.sliding_window if window_override is None else window_override
    h, aux, new_cache = _scan_groups(
        params, x, cfg, mode="decode", positions=positions, lengths=lengths,
        cache=cache, window=window, moe_mode=moe_mode,
        moe_capacity=moe_capacity, moe_shards=moe_shards,
        seq_parallel=seq_parallel, block_tables=block_tables,
        write_positions=write_positions)
    logits = _logits(params, cfg, h[:, 0, :])
    return logits, new_cache, lengths + 1


# ---------------------------------------------------------------------------
# device-resident serving hot path (DESIGN.md §3)
# ---------------------------------------------------------------------------

# Cache leaves whose writes are *positional* (landing at sequence offsets
# derived from ``lengths``) as opposed to *stateful* (a full overwrite of
# a recurrent state every step).  Positional leaves never need a masked
# merge: a lane's write lands at its first invalid position, which is
# only ever read after a later prefill has overwritten it.
POSITIONAL_CACHE_KEYS = frozenset({"k", "v", "ks", "vs"})


def merge_decode_cache(new_cache, old_cache, active):
    """Merge a decode step's cache updates under an active-lane mask.

    Stateful (SSM) leaves are where-selected per batch lane so inactive
    sessions' recurrent states are not advanced by masked lanes; purely
    positional (attention KV) leaves pass through untouched — combined
    with the scratch-row write redirection in ``forward_decode_fused``
    this removes the O(full-cache) where-select the host-side
    ``KVCachePool.commit`` pays per token."""
    def merge_layer(new_l, old_l):
        if set(new_l) <= POSITIONAL_CACHE_KEYS:
            return new_l
        out = {}
        for k, n in new_l.items():
            shape = (1, n.shape[1]) + (1,) * (n.ndim - 2)
            out[k] = jnp.where(active.reshape(shape), n, old_l[k])
        return out
    return {name: merge_layer(layer, old_cache[name])
            for name, layer in new_cache.items()}


def _scratch_write_lengths(cache, lengths, active):
    """Redirect inactive lanes' positional writes to the cache's last
    sequence row (the scratch row — engines must keep real content out
    of it; see DESIGN.md §3).  Attention-free caches need no redirect."""
    for layer in cache.values():
        if "k" in layer:
            return jnp.where(active, lengths,
                             jnp.int32(layer["k"].shape[2] - 1))
    return lengths


def forward_decode_fused(params, cfg: ModelConfig, tokens, cache, lengths,
                         active, *, moe_mode: str = "gmm",
                         window_override: Optional[int] = None,
                         moe_capacity: float = 1.25, moe_shards: int = 1,
                         block_tables=None):
    """One decode step with greedy sampling, length increment and the
    active-lane cache merge folded in, so a serving engine can keep
    ``tokens``/``lengths``/``active`` as device arrays and never sync
    per token (DESIGN.md §3).

    tokens: [B] int32 (last token per lane; don't-care where inactive);
    active: [B] bool.  Returns (next_tokens [B], new_cache, new_lengths);
    inactive lanes keep their token and length unchanged, and their only
    cache writes land in the scratch row (slab) / scratch page (paged).
    Attention valid-length stays the *real* ``lengths`` for every lane —
    only the write position is redirected — so idle lanes cost O(real
    length), not O(max_seq), under a tile-skipping kernel."""
    if block_tables is not None:
        # paged: a negative write position redirects to the scratch page
        write_positions = jnp.where(active, lengths, -1)
    else:
        write_positions = _scratch_write_lengths(cache, lengths, active)
    logits, new_cache, _ = forward_decode(
        params, cfg, tokens, cache, lengths, moe_mode=moe_mode,
        window_override=window_override, moe_capacity=moe_capacity,
        moe_shards=moe_shards, block_tables=block_tables,
        write_positions=write_positions)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    next_tokens = jnp.where(active, next_tokens, tokens)
    merged = merge_decode_cache(new_cache, cache, active)
    return next_tokens, merged, lengths + active.astype(jnp.int32)


def forward_decode_megastep(params, cfg: ModelConfig, tokens, cache,
                            lengths, active, *, num_steps: int,
                            moe_mode: str = "gmm",
                            window_override: Optional[int] = None,
                            moe_capacity: float = 1.25, moe_shards: int = 1,
                            block_tables=None):
    """``num_steps`` fused decode iterations as one ``lax.scan``
    executable, amortising dispatch over K emitted tokens per lane.

    Paged callers must have grown each active lane's block table to
    cover ``lengths + num_steps`` before dispatch — the table is fixed
    for the whole scan (``KVCachePool`` does this in
    ``prepare_append``).

    Returns (tokens_seq [K, B], next_tokens [B], new_cache, new_lengths);
    ``tokens_seq[i]`` is the token emitted by step i (inactive lanes
    repeat their input token)."""
    def body(carry, _):
        t, l, c = carry
        nt, nc, nl = forward_decode_fused(
            params, cfg, t, c, l, active, moe_mode=moe_mode,
            window_override=window_override, moe_capacity=moe_capacity,
            moe_shards=moe_shards, block_tables=block_tables)
        return (nt, nl, nc), nt

    (t, l, c), toks = jax.lax.scan(body, (tokens, lengths, cache), None,
                                   length=num_steps)
    return toks, t, c, l


# ---------------------------------------------------------------------------
# speculative decode: n-gram draft + multi-query verify (DESIGN.md §12)
# ---------------------------------------------------------------------------


def _ngram_draft(history, tokens, lengths, *, num_draft: int, ngram: int):
    """Device-resident prompt-lookup proposer.

    history: [B, S] int32 — ``history[b, p]`` is the token *input* at
    position ``p`` (prompt tokens, then each accepted/emitted token as
    it becomes the next step's input); rows are valid for ``p <
    lengths[b]``.  tokens: [B] (the pending input t0, not yet in
    history); lengths: [B].

    Finds the most recent position ``j < lengths`` where the ``ngram``
    tokens ending at ``j`` equal the ``ngram`` tokens ending at the
    frontier (t0 inclusive), and proposes the ``num_draft - 1`` tokens
    that followed it.  Lanes with no match (or too-short history)
    propose repeated t0 — harmless, the verify rejects them.  Returns
    the verify input x [B, num_draft] = [t0, d_1 .. d_{k-1}]."""
    B, S = history.shape
    k = num_draft
    # frontier-inclusive sequence: place t0 at its position
    seq = history.at[jnp.arange(B), jnp.clip(lengths, 0, S - 1)].set(tokens)
    ar = jnp.arange(S, dtype=jnp.int32)[None, :]                    # j grid
    # pattern: the ngram ending at the frontier, per lane
    pat_idx = (lengths[:, None] - (ngram - 1)
               + jnp.arange(ngram, dtype=jnp.int32)[None, :])       # [B, n]
    pat = jnp.take_along_axis(seq, jnp.clip(pat_idx, 0, S - 1), axis=1)
    ok = jnp.ones((B, S), bool)
    for i in range(ngram):
        src = ar + (i - (ngram - 1))                 # pattern elt i at j
        g = jnp.take_along_axis(seq, jnp.clip(src, 0, S - 1), axis=1)
        ok &= (src >= 0) & (g == pat[:, i: i + 1])
    # j == lengths is the trivial self-match; require a full pattern,
    # and require the k-1 continuation tokens after j to lie within
    # known history (<= frontier, where seq holds t0) — a match right
    # at the frontier would "continue" into unwritten zeros
    ok &= (ar < lengths[:, None]) & (lengths[:, None] >= ngram - 1)
    ok &= ar <= lengths[:, None] - (k - 1)
    jstar = jnp.max(jnp.where(ok, ar, -1), axis=1)                  # [B]
    if k == 1:
        return tokens[:, None]
    src = jstar[:, None] + 1 + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    drafts = jnp.take_along_axis(seq, jnp.clip(src, 0, S - 1), axis=1)
    drafts = jnp.where(jstar[:, None] >= 0, drafts, tokens[:, None])
    return jnp.concatenate([tokens[:, None], drafts], axis=1)


def forward_spec_verify(params, cfg: ModelConfig, x, cache, lengths, active,
                        ssm_valid, *, moe_mode: str = "gmm",
                        window_override: Optional[int] = None,
                        block_size: int = 512, moe_capacity: float = 1.25,
                        moe_shards: int = 1, block_tables=None):
    """Multi-query verify forward: score ``x`` [B, k] at positions
    ``lengths .. lengths + k - 1`` through the small-Sq prefill
    attention path, writing the draft K/V rows as it goes (per-row
    scatter; inactive lanes redirect to scratch).  Returns
    (logits [B, k, vocab], new_cache) — *all* per-position logits, the
    accept rule consumes only rows below the accepted length."""
    emb = _embed(params, cfg, x)
    B, k, _ = emb.shape
    offs = jnp.arange(k, dtype=jnp.int32)[None, :]
    positions = lengths[:, None] + offs
    write_positions = jnp.where(active[:, None], positions, -1)
    window = cfg.sliding_window if window_override is None else window_override
    h, _, new_cache = _scan_groups(
        params, emb, cfg, mode="prefill", positions=positions,
        lengths=lengths, cache=cache, window=window, moe_mode=moe_mode,
        block_size=block_size, moe_capacity=moe_capacity,
        moe_shards=moe_shards, block_tables=block_tables,
        write_positions=write_positions, ssm_valid=ssm_valid)
    return _logits(params, cfg, h), new_cache


def forward_spec_step(params, cfg: ModelConfig, history, tokens, cache,
                      lengths, active, *, num_draft: int, ngram: int = 3,
                      moe_mode: str = "gmm",
                      window_override: Optional[int] = None,
                      block_size: int = 512, moe_capacity: float = 1.25,
                      moe_shards: int = 1, block_tables=None):
    """One speculative decode step: draft ``num_draft - 1`` tokens by
    prompt lookup, verify all ``num_draft`` positions in one forward,
    accept the longest greedy-matching prefix, and leave the cache
    consistent at the accepted frontier (DESIGN.md §12).

    Greedy identity by construction: position 0's argmax is exactly the
    non-speculative next token (same context), and draft position i is
    consumed only when the drafts at 1..i all matched the argmaxes at
    0..i-1 — i.e. only when its context prefix is exactly what the
    sequential greedy path would have fed.  Rejected rows' KV writes
    land beyond the new length (slab: fenced by ``lengths``; paged: the
    host truncates the block table), and rejected SSM advancement is
    undone by a fenced re-run from the pre-step state snapshot
    (hybrid only; the fence is exact — ``mamba2.apply_mamba2_scan``).

    Returns (toks [k, B], accepts [B], next_tokens [B], new_history,
    new_cache, new_lengths); ``toks[i, b]`` is valid for
    ``i < accepts[b]`` (padding repeats the lane's next token).
    Inactive lanes: accepts = 0, token/length/history/state unchanged."""
    k = num_draft
    B, S_hist = history.shape
    x = _ngram_draft(history, tokens, lengths, num_draft=k, ngram=ngram)
    kfull = jnp.where(active, jnp.int32(k), 0)
    logits, cache1 = forward_spec_verify(
        params, cfg, x, cache, lengths, active, kfull, moe_mode=moe_mode,
        window_override=window_override, block_size=block_size,
        moe_capacity=moe_capacity, moe_shards=moe_shards,
        block_tables=block_tables)
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)            # [B, k]
    if k > 1:
        matches = (x[:, 1:] == g[:, :-1]).astype(jnp.int32)
        acc = 1 + jnp.cumprod(matches, axis=1).sum(axis=1)       # [1, k]
    else:
        acc = jnp.ones((B,), jnp.int32)
    acc = jnp.where(active, acc, 0)

    next_tokens = jnp.take_along_axis(
        g, jnp.maximum(acc - 1, 0)[:, None], axis=1)[:, 0]
    next_tokens = jnp.where(active, next_tokens, tokens)
    new_lengths = lengths + acc

    # history gains this step's *inputs*: t0 at position L, accepted
    # drafts after it (the bonus token is next step's t0 — not ours to
    # write).  Unwanted rows redirect to the last history row, which
    # the engine's k-cap keeps clear of real content (scratch).
    offs = jnp.arange(k, dtype=jnp.int32)[None, :]
    keep = offs < acc[:, None]
    pos_h = jnp.where(keep, lengths[:, None] + offs, S_hist - 1)
    new_history = jax.vmap(lambda h, t, p: h.at[p].set(t))(history, x, pos_h)

    has_state = any(not set(layer) <= POSITIONAL_CACHE_KEYS
                    for layer in cache.values())
    if has_state:
        # rejected positions advanced the SSM states in the verify; re-run
        # from the pre-step states with the fence at the accepted length
        # (exact: fenced positions are state-identity, accepted positions
        # recompute bit-identically from the same inputs).  Skipped when
        # every active lane accepted everything — the common case on
        # repetitive stretches.
        def fixup(_):
            mix = {name: (cache1[name]
                          if set(layer) <= POSITIONAL_CACHE_KEYS else layer)
                   for name, layer in cache.items()}
            _, c2 = forward_spec_verify(
                params, cfg, x, mix, lengths, active, acc,
                moe_mode=moe_mode, window_override=window_override,
                block_size=block_size, moe_capacity=moe_capacity,
                moe_shards=moe_shards, block_tables=block_tables)
            return c2

        full = jnp.all(jnp.where(active, acc, k) == k)
        cache1 = jax.lax.cond(full, lambda _: cache1, fixup, None)
        cache1 = merge_decode_cache(cache1, cache, active)

    toks = jnp.where(keep, g, next_tokens[:, None]).T             # [k, B]
    return toks, acc, next_tokens, new_history, cache1, new_lengths


def forward_resume_batch(params, cfg: ModelConfig, tokens, cache, slot_idx,
                         lengths, logit_idx, *, moe_mode: str = "gmm",
                         window_override: Optional[int] = None,
                         block_size: int = 512, moe_capacity: float = 1.25,
                         moe_shards: int = 1, block_tables=None):
    """Batched resume prefill: M jobs packed as one [M, bucket] chunk.

    tokens: [M, S]; slot_idx: [M] int32 (distinct cache slots);
    lengths: [M] (cached tokens per slot); logit_idx: [M] (last unpadded
    position per row).  Gathers the M slot rows out of the stacked
    cache, runs one batch-M prefill, and scatters the rows back.

    Under the paged layout (``block_tables`` [B, P_max]) only the
    *stateful* (SSM) leaves are gathered/scattered by slot — positional
    leaves are the shared page arena, which the prefill addresses
    directly through the M gathered block-table rows.
    Returns (logits [M, vocab], new_cache)."""
    if block_tables is not None:
        sub = {name: (layer if set(layer) <= POSITIONAL_CACHE_KEYS else
                      {k: jnp.take(v, slot_idx, axis=1)
                       for k, v in layer.items()})
               for name, layer in cache.items()}
        logits, sub2, _ = forward_prefill(
            params, cfg, tokens, sub, lengths, moe_mode=moe_mode,
            window_override=window_override, block_size=block_size,
            moe_capacity=moe_capacity, moe_shards=moe_shards,
            logit_idx=logit_idx,
            block_tables=jnp.take(block_tables, slot_idx, axis=0))
        new_cache = {
            name: (sub2[name] if set(layer) <= POSITIONAL_CACHE_KEYS else
                   {k: v.at[:, slot_idx].set(sub2[name][k])
                    for k, v in layer.items()})
            for name, layer in cache.items()}
        return logits, new_cache
    sub = jax.tree.map(lambda leaf: jnp.take(leaf, slot_idx, axis=1), cache)
    logits, sub2, _ = forward_prefill(
        params, cfg, tokens, sub, lengths, moe_mode=moe_mode,
        window_override=window_override, block_size=block_size,
        moe_capacity=moe_capacity, moe_shards=moe_shards,
        logit_idx=logit_idx)
    new_cache = jax.tree.map(
        lambda full, rows: full.at[:, slot_idx].set(rows), cache, sub2)
    return logits, new_cache
