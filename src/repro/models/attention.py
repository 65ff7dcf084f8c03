"""Attention: blocked (flash-style) pure-JAX implementation.

This module is the XLA execution path used for training, the dry-run and
the serving engine.  It never materialises the full [Sq, Sk] score
matrix: scores are computed per KV block inside a ``lax.scan`` with an
online-softmax accumulator, so peak memory is O(Sq * block) — required
for the 32k prefill and 524k decode shapes to fit per-device HBM.

The backward pass is a hand-written ``custom_vjp`` implementing the
FlashAttention recompute algorithm: the forward saves only (q, k, v,
out, m, l) and the backward re-derives each block's probabilities.
This matters: ``lax.scan`` autodiff would otherwise checkpoint the
O(Sq x hd) accumulator carry per KV block — measured 13.7 GB/device for
one Mixtral-dims layer at train_4k, vs ~0.5 GB with this VJP.

The Pallas kernels in ``repro.kernels`` implement the same contract for
the TPU hot path; ``repro.kernels.ref`` holds the naive oracle both are
tested against.

Supports: causal masking with a per-batch query offset (resume prefill
against a cached context), per-batch valid-key lengths, sliding windows
(Mixtral SWA and the sanctioned long_500k dense variant), bidirectional
encoder attention (HuBERT), and GQA via grouped einsums (no KV head
repetition is materialised).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _pad_to_multiple(x, multiple: int, axis: int):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


def _block_mask(key_pos, q_pos, lengths, causal: bool, window: int):
    """valid: [B, Sq, blk] (causal) or [B, 1, blk] (padding-only)."""
    valid = key_pos[None, None, :] < lengths[:, None, None]
    if causal:
        valid = valid & (key_pos[None, None, :] <= q_pos[:, :, None])
        if window > 0:
            valid = valid & (key_pos[None, None, :] > q_pos[:, :, None] - window)
    return valid


def _flash_fwd_impl(q, k, v, q_offset, lengths, causal, window, block):
    B, Sq, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = 1.0 / (hd ** 0.5)
    nblocks = k.shape[1] // block

    qg = (q * scale).astype(jnp.float32).reshape(B, Sq, Hk, G, hd)
    q_pos = q_offset[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]

    m0 = jnp.full((B, Hk, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hk, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hk, G, Sq, hd), jnp.float32)

    kb = k.reshape(B, nblocks, block, Hk, hd).swapaxes(0, 1)
    vb = v.reshape(B, nblocks, block, Hk, hd).swapaxes(0, 1)

    def body(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, blk_idx = xs
        key_pos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        valid = _block_mask(key_pos, q_pos, lengths, causal, window)
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kb, vb, jnp.arange(nblocks, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B, Hk, G, Sq, hd] f32
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash(causal, window, block, q, k, v, q_offset, lengths):
    out, _, _ = _flash_fwd_impl(q, k, v, q_offset, lengths, causal, window,
                                block)
    B, Sq, H, hd = q.shape
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def _flash_fwd(causal, window, block, q, k, v, q_offset, lengths):
    out, m, l = _flash_fwd_impl(q, k, v, q_offset, lengths, causal, window,
                                block)
    B, Sq, H, hd = q.shape
    o = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    return o, (q, k, v, q_offset, lengths, out, m, l)


def _flash_bwd(causal, window, block, res, do):
    q, k, v, q_offset, lengths, out, m, l = res
    B, Sq, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = 1.0 / (hd ** 0.5)
    nblocks = k.shape[1] // block

    qg = (q * scale).astype(jnp.float32).reshape(B, Sq, Hk, G, hd)
    dog = do.astype(jnp.float32).reshape(B, Sq, Hk, G, hd) \
        .transpose(0, 2, 3, 1, 4)                      # [B,Hk,G,Sq,hd]
    q_pos = q_offset[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    l_safe = jnp.maximum(l, 1e-30)
    # D_i = sum_d dO_i * O_i  (out here is already normalised)
    D = jnp.sum(dog * out, axis=-1)                    # [B,Hk,G,Sq]

    kb = k.reshape(B, nblocks, block, Hk, hd).swapaxes(0, 1)
    vb = v.reshape(B, nblocks, block, Hk, hd).swapaxes(0, 1)

    def body(dq, xs):
        k_blk, v_blk, blk_idx = xs
        key_pos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        valid = _block_mask(key_pos, q_pos, lengths, causal, window)
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / l_safe[..., None]   # [B,Hk,G,Sq,blk]
        dv = jnp.einsum("bhgqk,bhgqd->bkhd", p, dog,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", dog, v_blk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D[..., None])                        # [B,Hk,G,Sq,blk]
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                             k_blk.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hk, G, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(nblocks, dtype=jnp.int32)))
    dq = (dq * scale).reshape(B, Sq, H, hd).astype(q.dtype)
    # dk needs no extra scale: qg in the einsum already carries 1/sqrt(hd)
    dk = dks.swapaxes(0, 1).reshape(B, -1, Hk, hd).astype(k.dtype)
    dv = dvs.swapaxes(0, 1).reshape(B, -1, Hk, hd).astype(v.dtype)
    zi = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return dq, dk, dv, zi(q_offset), zi(lengths)


_flash.defvjp(_flash_fwd, _flash_bwd)


def blocked_attention(
    q,                      # [B, Sq, H, hd]
    k,                      # [B, Sk, Hk, hd]
    v,                      # [B, Sk, Hk, hd]
    *,
    q_offset=None,          # [B] int32: absolute position of q[:, 0]
    lengths=None,           # [B] int32: number of valid keys (<= Sk)
    causal: bool = True,
    window: int = 0,        # 0 = unlimited
    block_size: int = 512,
):
    B, Sq, H, hd = q.shape
    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)
    if lengths is None:
        lengths = jnp.full((B,), k.shape[1], jnp.int32)
    block = min(block_size, k.shape[1])
    k, _ = _pad_to_multiple(k, block, 1)
    v, _ = _pad_to_multiple(v, block, 1)
    out = _flash(causal, window, block, q, k, v,
                 q_offset.astype(jnp.int32), lengths.astype(jnp.int32))
    return out


def blocked_attention_quant(
    q, k_q, k_s, v_q, v_s, *, q_offset=None, lengths=None,
    causal: bool = True, window: int = 0, block_size: int = 512,
):
    """Forward-only blocked attention over an int8-quantised KV cache.

    k_q/v_q: int8 [B, Sk, Hk, hd]; k_s/v_s: per-(position, head) scales
    [B, Sk, Hk, 1].  Dequantisation happens per KV tile inside the scan,
    so HBM traffic for the cache is halved (the §Perf memory-term
    optimization for the decode shapes); serving paths never
    differentiate through the cache, so no VJP is needed."""
    B, Sq, H, hd = q.shape
    Hk = k_q.shape[2]
    G = H // Hk
    scale = 1.0 / (hd ** 0.5)
    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)
    if lengths is None:
        lengths = jnp.full((B,), k_q.shape[1], jnp.int32)
    block = min(block_size, k_q.shape[1])
    k_q, _ = _pad_to_multiple(k_q, block, 1)
    v_q, _ = _pad_to_multiple(v_q, block, 1)
    k_s, _ = _pad_to_multiple(k_s, block, 1)
    v_s, _ = _pad_to_multiple(v_s, block, 1)
    nblocks = k_q.shape[1] // block

    qg = (q * scale).astype(jnp.float32).reshape(B, Sq, Hk, G, hd)
    q_pos = q_offset[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    m0 = jnp.full((B, Hk, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hk, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hk, G, Sq, hd), jnp.float32)

    def rb(x):
        return x.reshape(B, nblocks, block, *x.shape[2:]).swapaxes(0, 1)

    def body(carry, xs):
        m, l, acc = carry
        kq_b, ks_b, vq_b, vs_b, blk_idx = xs
        k_blk = kq_b.astype(jnp.float32) * ks_b.astype(jnp.float32)
        v_blk = vq_b.astype(jnp.float32) * vs_b.astype(jnp.float32)
        key_pos = blk_idx * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_blk,
                       preferred_element_type=jnp.float32)
        valid = _block_mask(key_pos, q_pos, lengths, causal, window)
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (rb(k_q), rb(k_s), rb(v_q), rb(v_s),
         jnp.arange(nblocks, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def prefill_attention(q, k_cache, v_cache, *, q_offset, lengths,
                      window: int = 0, block_size: int = 512,
                      backend: str = "xla"):
    """Serving prefill/resume attention: a [B, Sq] query chunk against
    the resident KV cache [B, S_max] (chunk rows already written).

    ``backend`` selects the execution path (``ModelConfig.prefill_kernel``):
    "xla" runs the reference ``blocked_attention`` scan, which streams
    every padded cache tile; "pallas" runs the cache-aware kernel whose
    scalar-prefetched ``q_offset``/``lengths`` prune causally-dead and
    never-written KV tiles from the DMA stream (DESIGN.md §4)."""
    if backend == "pallas":
        from repro.kernels.ops import flash_prefill
        return flash_prefill(q, k_cache, v_cache, q_offset, lengths,
                             causal=True, window=window)
    return blocked_attention(q, k_cache, v_cache, q_offset=q_offset,
                             lengths=lengths, causal=True, window=window,
                             block_size=block_size)


def prefill_attention_quant(q, k_q, k_s, v_q, v_s, *, q_offset, lengths,
                            window: int = 0, block_size: int = 512,
                            backend: str = "xla"):
    """int8-KV serving prefill attention; same dispatch contract as
    ``prefill_attention`` (the Pallas path dequantises per tile in VMEM
    and applies the same tile pruning)."""
    if backend == "pallas":
        from repro.kernels.ops import flash_prefill_quant
        return flash_prefill_quant(q, k_q, k_s, v_q, v_s, q_offset, lengths,
                                   causal=True, window=window)
    return blocked_attention_quant(q, k_q, k_s, v_q, v_s, q_offset=q_offset,
                                   lengths=lengths, causal=True,
                                   window=window, block_size=block_size)


def paged_gather(arena, block_tables):
    """Linearise a page arena through block tables: arena
    [P_phys, page, Hk, x], block_tables [B, P_max] (physical page ids;
    unallocated entries already point at the scratch page) ->
    [B, P_max * page, Hk, x].  Positions >= the session's valid length
    land on scratch/stale pages — exactly like the slab layout's
    never-written rows, and masked identically by ``lengths``."""
    g = jnp.take(arena, block_tables, axis=0)
    B, pm, ps = g.shape[:3]
    return g.reshape((B, pm * ps) + g.shape[3:])


def prefill_attention_paged(q, k_arena, v_arena, block_tables, *, q_offset,
                            lengths, window: int = 0, block_size: int = 512,
                            backend: str = "xla"):
    """Paged-layout serving prefill attention (DESIGN.md §8): the
    "xla" backend gathers the session's pages into a linear view and
    runs the reference scan (bit-identical to the slab path at valid
    positions); "pallas" streams pages directly via block-table index
    maps — no gather materialised."""
    if backend == "pallas":
        from repro.kernels.ops import flash_prefill_paged
        return flash_prefill_paged(q, k_arena, v_arena, q_offset, lengths,
                                   block_tables, causal=True, window=window)
    return blocked_attention(
        q, paged_gather(k_arena, block_tables),
        paged_gather(v_arena, block_tables), q_offset=q_offset,
        lengths=lengths, causal=True, window=window, block_size=block_size)


def prefill_attention_paged_quant(q, k_arena, ks_arena, v_arena, vs_arena,
                                  block_tables, *, q_offset, lengths,
                                  window: int = 0, block_size: int = 512,
                                  backend: str = "xla"):
    """int8-KV paged prefill attention; same dispatch contract as
    ``prefill_attention_paged`` (scale leaves ride the same tables)."""
    if backend == "pallas":
        from repro.kernels.ops import flash_prefill_paged_quant
        return flash_prefill_paged_quant(
            q, k_arena, ks_arena, v_arena, vs_arena, q_offset, lengths,
            block_tables, causal=True, window=window)
    bt = block_tables
    return blocked_attention_quant(
        q, paged_gather(k_arena, bt), paged_gather(ks_arena, bt),
        paged_gather(v_arena, bt), paged_gather(vs_arena, bt),
        q_offset=q_offset, lengths=lengths, causal=True, window=window,
        block_size=block_size)


def decode_attention_paged(q, k_arena, v_arena, block_tables, lengths, *,
                           window: int = 0, block_size: int = 2048,
                           k_scale=None, v_scale=None, backend: str = "xla"):
    """Paged-layout single-token decode.  "pallas" (full-attention,
    non-quant) maps the kernel's k-tile grid index through the
    scalar-prefetched block table; otherwise pages are gathered and the
    reference ``decode_attention`` runs on the linear view (identical
    numerics — the gather is position-preserving)."""
    if backend == "pallas" and window == 0 and k_scale is None:
        from repro.kernels.ops import flash_decode_paged
        return flash_decode_paged(q, k_arena, v_arena, lengths, block_tables)
    scales = {}
    if k_scale is not None:
        scales = dict(k_scale=paged_gather(k_scale, block_tables),
                      v_scale=paged_gather(v_scale, block_tables))
    return decode_attention(
        q, paged_gather(k_arena, block_tables),
        paged_gather(v_arena, block_tables), lengths, window=window,
        block_size=block_size, **scales)


def quantize_kv(x):
    """x: [..., hd] bf16 -> (int8 values, per-(...) scale [..., 1])."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(x.dtype)


def decode_attention_seqpar(q, k_new, v_new, k_cache, v_cache, lengths,
                            spmd, *, window: int = 0,
                            block_size: int = 2048,
                            k_scale=None, v_scale=None,
                            new_scales=None):
    """Sequence-parallel flash decode (shard_map over the data axes),
    INCLUDING the shard-local cache write.

    The KV cache sequence dim is sharded over dp.  The new token's K/V is
    written by exactly the shard whose range covers position
    ``lengths-1`` (a local dynamic-update-slice — a global one at a
    dynamic position makes XLA gather the whole sharded cache: measured
    8.6 GB/step of all-gather for phi4-mini x long_500k, §Perf iteration
    2a, hypothesis refuted->revised).  Each device then computes flash
    stats (m, l, acc) over its local chunk and one log-sum-exp merge
    combines them:

        m* = pmax(m);  l* = psum(l e^{m-m*});  acc* = psum(acc e^{m-m*})

    Collective traffic: O(B x H x hd) once per layer.
    Returns (out, new_k_cache, new_v_cache[, new_k_scale, new_v_scale])."""
    from jax.sharding import PartitionSpec as P

    _, _, H, hd = q.shape
    S = k_cache.shape[1]
    dp = spmd.dp_axes
    ba = tuple(getattr(spmd, "batch_axes", ()) or ())
    n_shards = spmd.dp_size
    S_loc = S // n_shards
    quant = k_scale is not None

    def _local_write(cache_l, new_row, pos, offset):
        """cache_l: [B, S_loc, Hk, x]; new_row: [B, 1, Hk, x]; pos [B]."""
        local_pos = jnp.clip(pos - offset, 0, S_loc - 1)
        in_range = (pos >= offset) & (pos < offset + S_loc)

        def one(c, row, p, ok):
            upd = jax.lax.dynamic_update_slice_in_dim(c, row, p, axis=0)
            return jnp.where(ok, upd, c)
        return jax.vmap(one)(cache_l, new_row, local_pos, in_range)

    def local(q_l, kn, vn, k_l, v_l, len_l, *scales):
        # global position of this shard's first cache row
        idx = jnp.zeros((), jnp.int32)
        for i, a in enumerate(dp):
            stride = int(np.prod([spmd.mesh.shape[b] for b in dp[i + 1:]],
                                 dtype=np.int64)) if i + 1 < len(dp) else 1
            idx = idx + jax.lax.axis_index(a) * stride
        offset = idx * S_loc
        pos = len_l - 1                                      # write position
        k_l = _local_write(k_l, kn, pos, offset)
        v_l = _local_write(v_l, vn, pos, offset)
        out_scales = ()
        if quant:
            ks, vs, kns, vns = scales
            ks = _local_write(ks, kns, pos, offset)
            vs = _local_write(vs, vns, pos, offset)
            out_scales = (ks, vs)
            kf = k_l.astype(jnp.float32) * ks.astype(jnp.float32)
            vf = v_l.astype(jnp.float32) * vs.astype(jnp.float32)
        else:
            kf = k_l.astype(jnp.float32)
            vf = v_l.astype(jnp.float32)
        B_loc = q_l.shape[0]
        qg = (q_l[:, 0] * (1.0 / hd ** 0.5)).astype(jnp.float32)  # [B,H,hd]
        Hk = k_l.shape[2]
        G = H // Hk
        qg = qg.reshape(B_loc, Hk, G, hd)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kf,
                       preferred_element_type=jnp.float32)  # [B,Hk,G,S_loc]
        key_pos = offset + jnp.arange(S_loc, dtype=jnp.int32)
        valid = key_pos[None, :] < len_l[:, None]            # [B, S_loc]
        if window > 0:   # sliding window on *global* positions
            valid = valid & (key_pos[None, :] >= len_l[:, None] - window)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)                                   # [B,Hk,G]
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        acc = jnp.einsum("bhgk,bkhd->bhgd", p, vf,
                         preferred_element_type=jnp.float32)
        # LSE merge across shards (the single collective round)
        m_g = jax.lax.pmax(m, dp)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, dp)
        acc_g = jax.lax.psum(acc * corr[..., None], dp)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return (out.reshape(B_loc, 1, H, hd).astype(q_l.dtype),
                k_l, v_l) + out_scales

    b_ax = ba if ba else None
    specs_kv = P(b_ax, dp, None, None)
    specs_q = P(b_ax, None, None, None)
    in_specs = [specs_q, specs_q, specs_q, specs_kv, specs_kv, P(b_ax)]
    args = [q, k_new, v_new, k_cache, v_cache, lengths]
    out_specs = (specs_q, specs_kv, specs_kv)
    if quant:
        in_specs += [specs_kv, specs_kv, specs_q, specs_q]
        args += [k_scale, v_scale] + list(new_scales)
        out_specs = out_specs + (specs_kv, specs_kv)
    fn = jax.shard_map(local, mesh=spmd.mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs)
    return fn(*args)


def decode_attention(
    q,                      # [B, 1, H, hd]
    k_cache,                # [B, S, Hk, hd]   (int8 when quantised)
    v_cache,                # [B, S, Hk, hd]
    lengths,                # [B] int32: tokens valid in cache (incl. current)
    *,
    window: int = 0,
    block_size: int = 2048,
    k_scale=None,           # [B, S, Hk, 1] when the cache is int8
    v_scale=None,
):
    """Single-token decode against a KV cache.

    With ``window > 0`` only the last ``window`` cache entries are read
    (per-batch dynamic slice) — this is what makes long_500k decode
    sub-quadratic-in-practice for SWA architectures: compute and bytes
    are O(window), not O(S)."""
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    quant = k_scale is not None
    if window > 0 and window < S:
        starts = jnp.maximum(lengths - window, 0)  # [B]

        def slice_one(c, s):
            return jax.lax.dynamic_slice_in_dim(c, s, window, axis=0)

        sl = lambda c: jax.vmap(slice_one)(c, starts)
        # positions of sliced keys are starts + arange(window); valid while
        # < lengths.  Re-express as lengths relative to the slice.
        rel_len = lengths - starts
        if quant:
            return blocked_attention_quant(
                q, sl(k_cache), sl(k_scale), sl(v_cache), sl(v_scale),
                q_offset=rel_len - 1, lengths=rel_len, causal=True,
                window=0, block_size=min(block_size, window))
        return blocked_attention(
            q, sl(k_cache), sl(v_cache), q_offset=rel_len - 1,
            lengths=rel_len, causal=True, window=0,
            block_size=min(block_size, window),
        )
    if quant:
        return blocked_attention_quant(
            q, k_cache, k_scale, v_cache, v_scale, q_offset=lengths - 1,
            lengths=lengths, causal=True, window=0, block_size=block_size)
    return blocked_attention(
        q, k_cache, v_cache, q_offset=lengths - 1, lengths=lengths,
        causal=True, window=0, block_size=block_size,
    )


def bidirectional_attention(q, k, v, lengths=None, block_size: int = 512):
    """Encoder attention (HuBERT): full bidirectional with padding mask."""
    return blocked_attention(
        q, k, v, lengths=lengths, causal=False, block_size=block_size,
    )
