"""Mixture-of-Experts layer (Mixtral / OLMoE / Jamba).

Two execution paths, selected by ``mode``:

* ``"gmm"``   — sort + capacity-bounded scatter into per-expert rows,
                grouped matmul ``[E, C, d] @ [E, d, f]``, gather back.
                Compute is proportional to *active* experts (top-k), which
                is what the roofline MODEL_FLOPS ratio expects.  Default
                for dry-run / production lowering.
* ``"dense"`` — every expert computes every token, outputs combined with
                the (zeroed outside top-k) router weights.  O(E) compute
                but trivially correct and shard-friendly; used as the
                oracle in tests and for tiny smoke configs.

Router: softmax over expert logits, top-k, weights renormalised over the
selected experts (Mixtral convention).  The Switch-style load-balance
auxiliary loss is returned for the training path.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, split_keys
from repro.configs.base import MoEConfig


def init_moe(key, d_model: int, d_ff: int, moe: MoEConfig, act: str, dtype):
    kr, kg, ku, kd = split_keys(key, 4)
    E = moe.num_experts
    params = {
        "router": dense_init(kr, d_model, E, dtype),
        "w_up": jnp.stack([dense_init(k, d_model, d_ff, dtype)
                           for k in split_keys(ku, E)]),
        "w_down": jnp.stack([dense_init(k, d_ff, d_model, dtype)
                             for k in split_keys(kd, E)]),
    }
    if act == "swiglu":
        params["w_gate"] = jnp.stack([dense_init(k, d_model, d_ff, dtype)
                                      for k in split_keys(kg, E)])
    return params


def _expert_ffn(params, h, act: str = "swiglu"):
    """h: [E, C, d] -> [E, C, d] through each expert's FFN."""
    up = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    if act == "swiglu":
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, params["w_gate"]))
        mid = gate * up
    else:
        mid = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", mid, params["w_down"])


def _router(params, x2d, moe: MoEConfig):
    logits = (x2d @ params["router"]).astype(jnp.float32)      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, moe.top_k)             # [T, k]
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return probs, weights, idx


def _aux_loss(probs, idx, moe: MoEConfig):
    """Switch-transformer load-balance loss: E * sum_e f_e * P_e."""
    E = moe.num_experts
    hits = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)    # [T, E]
    f = hits.mean(0) / moe.top_k
    P = probs.mean(0)
    return E * jnp.sum(f * P)


def _gmm_dispatch_one(params, x2d, weights, idx, *, moe: MoEConfig,
                      act: str, C: int, expert_ffn=None):
    """Capacity-bounded sort/scatter grouped matmul for ONE token shard.
    x2d: [T, d]; weights/idx: [T, k].  Kept shard-local (vmapped over the
    data-sharded leading axis by the caller) so the sort and scatter
    never leave the device — the global variant would force XLA SPMD to
    all-gather the full token array."""
    T, d = x2d.shape
    k, E = moe.top_k, moe.num_experts
    e_flat = idx.reshape(-1)                                    # [T*k]
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = weights.reshape(-1)

    order = jnp.argsort(e_flat)                                 # stable
    se, st, sw = e_flat[order], t_flat[order], w_flat[order]
    counts = jnp.bincount(e_flat, length=E)                     # [E]
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    dropped = pos_in_e >= C
    slot = jnp.where(dropped, E * C, se * C + pos_in_e)         # overflow row

    buf = jnp.zeros((E * C + 1, d), x2d.dtype).at[slot].set(x2d[st])
    ffn = expert_ffn if expert_ffn is not None else functools.partial(
        _expert_ffn, params, act=act)
    h = ffn(buf[: E * C].reshape(E, C, d))
    h = h.reshape(E * C, d)
    contrib = jnp.where(
        dropped[:, None], 0.0, h[jnp.where(dropped, 0, slot)] * sw[:, None]
    ).astype(x2d.dtype)
    return jnp.zeros((T, d), x2d.dtype).at[st].add(contrib)


def apply_moe(params, x, moe: MoEConfig, act: str, *,
              mode: str = "gmm", capacity_factor: float = 1.25,
              data_shards: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar).

    ``data_shards``: number of data-parallel shards of the token stream;
    the gmm dispatch runs independently per shard (local sort/scatter,
    per-shard capacity) — the production lowering path."""
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    probs, weights, idx = _router(params, x2d, moe)
    aux = _aux_loss(probs, idx, moe)
    k, E = moe.top_k, moe.num_experts

    if mode == "dense":
        # combine weights over all experts, zero outside top-k
        comb = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], idx].set(weights)
        outs = _expert_ffn(params, jnp.broadcast_to(x2d, (E, T, d)), act)
        out = jnp.einsum("te,etd->td", comb.astype(x.dtype), outs)
        return out.reshape(B, S, d), aux

    # ---- gmm path ------------------------------------------------------
    from repro.distributed.context import current_spmd
    spmd = current_spmd()
    if spmd is not None and T % spmd.dp_size == 0:
        out = _gmm_shard_map(params, x2d, weights, idx, moe=moe, act=act,
                             capacity_factor=capacity_factor, spmd=spmd)
        return out.reshape(B, S, d), aux

    R = data_shards if T % data_shards == 0 else 1
    T_loc = T // R
    C = int(max(1, -(-T_loc * k // E) * capacity_factor))
    disp = functools.partial(_gmm_dispatch_one, params, moe=moe, act=act,
                             C=C)
    out = jax.vmap(disp)(x2d.reshape(R, T_loc, d),
                         weights.reshape(R, T_loc, k),
                         idx.reshape(R, T_loc, k))
    return out.reshape(B, S, d), aux


def _gmm_shard_map(params, x2d, weights, idx, *, moe: MoEConfig, act: str,
                   capacity_factor: float, spmd):
    """Mesh-aware gmm dispatch: ``shard_map`` runs the sort/scatter
    per-device on the token-parallel axes (XLA SPMD would otherwise
    replicate the whole token stream to partition the sort), with the
    expert FFN tensor-parallel over ``tp_axis`` (d_ff sharded; one psum
    reduces the down-projection partials, same collective as a dense TP
    MLP)."""
    from jax.sharding import PartitionSpec as P

    T, d = x2d.shape
    k, E = moe.top_k, moe.num_experts
    T_loc = T // spmd.dp_size
    # process the local tokens in bounded chunks: the dispatch buffer is
    # [E*C, d] with C ~ chunk*k/E — chunking caps the transient at a few
    # hundred MB regardless of sequence length (FLOPs unchanged).
    chunk = T_loc
    for cand in (8192, 4096, 2048, 1024):
        if T_loc % cand == 0:
            chunk = cand
            break
    n_chunks = T_loc // chunk
    C = int(max(1, -(-chunk * k // E) * capacity_factor))
    dp, tp = spmd.dp_axes, spmd.tp_axis

    has_gate = "w_gate" in params
    ffn_params = {"w_up": params["w_up"], "w_down": params["w_down"]}
    if has_gate:
        ffn_params["w_gate"] = params["w_gate"]
    # fsdp: keep the expert weights' d dim sharded over dp INSIDE the
    # shard_map and gather one expert at a time (rematted) — gathering the
    # whole [E, d, f] stack at once leaves E x 3 full-size f32 weight
    # gradients live simultaneously in the backward (measured 91 GB/device
    # for Jamba train_4k).
    fsdp = spmd.fsdp
    dspec = dp if fsdp else None
    ffn_specs = {"w_up": P(None, dspec, tp), "w_down": P(None, tp, dspec)}
    if has_gate:
        ffn_specs["w_gate"] = P(None, dspec, tp)

    def local(p_local, x_l, w_l, i_l):
        if fsdp:
            def gather(w, axis):
                return jax.lax.all_gather(w, dp, axis=axis, tiled=True)

            def expert_ffn(h):          # h: [E, C, d] -> [E, C, d]
                @jax.checkpoint
                def one_e(args):
                    he = args[0]
                    wu = gather(args[1], 0)          # [d, f_loc]
                    wd = gather(args[2], 1)          # [f_loc, d]
                    up = he @ wu
                    if has_gate:
                        gate = jax.nn.silu(he @ gather(args[3], 0))
                        mid = gate * up
                    else:
                        mid = jax.nn.gelu(up)
                    return mid @ wd
                args = (h, p_local["w_up"], p_local["w_down"])
                if has_gate:
                    args = args + (p_local["w_gate"],)
                return jax.lax.map(one_e, args)
        else:
            expert_ffn = functools.partial(_expert_ffn, p_local, act=act)

        @jax.checkpoint
        def one(args):
            # rematted: the [E*C, d] dispatch buffers are recomputed in the
            # backward pass instead of being saved per chunk
            xc, wc, ic = args
            return _gmm_dispatch_one(p_local, xc, wc, ic, moe=moe, act=act,
                                     C=C, expert_ffn=expert_ffn)
        if n_chunks > 1:
            out = jax.lax.map(one, (x_l.reshape(n_chunks, chunk, d),
                                    w_l.reshape(n_chunks, chunk, k),
                                    i_l.reshape(n_chunks, chunk, k)))
            out = out.reshape(T_loc, d)
        else:
            out = one((x_l, w_l, i_l))
        return jax.lax.psum(out, tp)

    fn = jax.shard_map(local, mesh=spmd.mesh,
                       in_specs=(ffn_specs, P(dp, None), P(dp, None),
                                 P(dp, None)),
                       out_specs=P(dp, None))
    return fn(ffn_params, x2d, weights, idx)
