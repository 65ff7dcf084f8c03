"""Chip smoke test: SmolLM-360M at its published widths, served through
the gateway on one TPU.

    python chip_smoke.py

One process, four phases; each prints its result on its own line and a
failed phase exits non-zero:

  device   ``jax.devices()`` must be a TPU — there is no CPU fallback.
  kernels  the Pallas attention kernels (``flash_prefill``,
           ``flash_prefill_paged``, ``flash_decode_paged``) compiled for
           the chip (``interpret=False``) at SmolLM widths: each lowered
           program must hold a ``tpu_custom_call`` and agree with
           ``kernels/ref.py`` within ``KERNEL_TOL``.
  prefill  a ~3k-token prompt through the engine's cached prefill
           executable, in the engine's chunk size, against ``forward_cold``
           at ``highest`` matmul precision: the last-position logits
           must agree within ``PREFILL_REL_TOL`` of the reference's
           largest logit.  Argmax tokens are not compared: they flip on
           near-ties between executables.
  serve    ``repro.launch.serve --serve-smoke`` in-process on an
           ephemeral port: 4 ReAct agents at Table-I lengths
           (``--token-scale 1.0``), Poisson arrivals at 1 req/s.  Every
           request must resolve, every admitted session must finish with
           all its tokens, with no KV capacity overrun and no swallowed
           engine error.

The last line of standard output is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Weights are random, drawn from a fixed seed, at the published shapes.
"""
from __future__ import annotations

import asyncio
import functools
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "smollm-360m"
AGENTS = 4
RATE_RPS = 1.0
PROMPT_LEN = 3000
# Pallas kernels vs the f32 reference on O(1) activations: room for one
# bf16 MXU pass over f32 operands (relative rounding 2^-9 per product)
KERNEL_TOL = 1e-2
# engine executable (default matmul precision: on TPU, bf16 passes over
# f32 operands) vs the reference at "highest", through 32 layers:
# max |dlogit| <= PREFILL_REL_TOL * max |reference logit|
PREFILL_REL_TOL = 5e-2
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def serve_args(arch: str = ARCH, token_scale: float = 1.0,
               rate: float = RATE_RPS) -> List[str]:
    return ["--arch", arch, "--agents", str(AGENTS), "--workload", "react",
            "--token-scale", str(token_scale), "--rate", str(rate),
            "--serve-smoke"]


def check_device():
    """The first device, which must be a TPU; exits otherwise."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind}); no CPU fallback")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    return d


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_cases(*, batch: int = 8, heads: int = 15, kv_heads: int = 5,
                 head_dim: int = 64, page: int = 64, seq: int = 6144,
                 chunk: int = 128, interpret: bool = False, seed: int = 0,
                 ) -> List[Tuple[str, Callable, tuple, object]]:
    """(name, jitted kernel, args, reference output) for the three
    main-path attention kernels at SmolLM widths.  The paged cases hold
    the same keys/values as the contiguous one, scattered over a
    shuffled page arena, so one reference serves both."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.ref import naive_attention, naive_decode_attention

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, chunk, heads, head_dim))
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, head_dim))
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, head_dim))
    q1 = jax.random.normal(ks[3], (batch, 1, heads, head_dim))
    q_off = jnp.asarray(rng.integers(0, seq - chunk + 1, batch), jnp.int32)
    lens = q_off + chunk
    dec_lens = jnp.asarray(rng.integers(1, seq + 1, batch), jnp.int32)

    n_pages = batch * seq // page
    perm = rng.permutation(n_pages)
    bt = jnp.asarray(perm.reshape(batch, seq // page), jnp.int32)

    def arena(x):                       # + one scratch page, never read
        flat = x.reshape(n_pages, page, kv_heads, head_dim)
        return jnp.zeros((n_pages + 1, page, kv_heads, head_dim),
                         x.dtype).at[perm].set(flat)

    k_ar, v_ar = arena(k), arena(v)
    with jax.default_matmul_precision("highest"):
        ref_pre = naive_attention(q, k, v, causal=True, q_offset=q_off,
                                  lengths=lens)
        ref_dec = naive_decode_attention(q1, k, v, dec_lens)
    kw = dict(interpret=interpret)
    return [
        ("flash_prefill", functools.partial(ops.flash_prefill, **kw),
         (q, k, v, q_off, lens), ref_pre),
        ("flash_prefill_paged",
         functools.partial(ops.flash_prefill_paged, **kw),
         (q, k_ar, v_ar, q_off, lens, bt), ref_pre),
        ("flash_decode_paged",
         functools.partial(ops.flash_decode_paged, **kw),
         (q1, k_ar, v_ar, dec_lens, bt), ref_dec),
    ]


def phase_kernels() -> Dict[str, float]:
    import jax
    import numpy as np
    errs = {}
    for name, fn, args, ref in kernel_cases():
        lowered = jax.jit(fn).lower(*args)
        if "tpu_custom_call" not in lowered.as_text():
            raise SystemExit(f"kernels: {name} lowered without a "
                             f"tpu_custom_call (not a Mosaic kernel)")
        out = lowered.compile()(*args)
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
        errs[name] = err
        print(f"kernels: {name} tpu_custom_call=yes max|err|={err:.3e} "
              f"(tol {KERNEL_TOL:g})", flush=True)
        if not err <= KERNEL_TOL:
            raise SystemExit(f"kernels: {name} max|err| {err:.3e} > "
                             f"{KERNEL_TOL:g}")
    return errs


# ---------------------------------------------------------------------------
# prefill correctness
# ---------------------------------------------------------------------------

def phase_prefill(arch: str = ARCH, prompt_len: int = PROMPT_LEN,
                  seed: int = 0) -> float:
    """Relative max |dlogit| of the engine's chunked prefill against a
    cache-free forward of the whole prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import forward_cold, init_params
    from repro.serving.engine import get_executables
    from repro.serving.kvcache import make_pool

    args = serve.build_parser().parse_args(serve_args(arch))
    cfg = serve.model_config(args)
    ecfg = serve.engine_config(args, cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))   # the server's seed
    ex = get_executables(cfg, ecfg.num_slots, ecfg.max_seq, ecfg.moe_mode)
    pool = make_pool(cfg, ecfg.num_slots, ecfg.max_seq, jnp.float32,
                     num_pages=ecfg.kv_pages, prefix_cache=ecfg.prefix_cache)
    slot = pool.alloc()
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, prompt_len, dtype=np.int32)
    chunk = ecfg.cycle_budget - ecfg.granularity   # the largest slot chunk
    t0 = time.perf_counter()
    for start in range(0, prompt_len, chunk):
        take = min(chunk, prompt_len - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = tokens[start:start + take]
        bt = ()
        if cfg.kv_layout == "paged":
            pool.prepare_append(slot, start, take)
            bt = (pool.block_tables_device(),)
        logits, pool.cache = ex.prefill(
            params, pool.cache, jnp.asarray(toks), jnp.int32(slot),
            jnp.int32(start), jnp.int32(take - 1), *bt)
        # one chunk in flight: this executable does not donate, so each
        # queued chunk would hold another whole cache and the phase's
        # peak would mask the serve phase's
        jax.block_until_ready(logits)
    got = np.asarray(logits)
    t_engine = time.perf_counter() - t0
    del pool
    cold = jax.jit(lambda p, t: forward_cold(p, cfg, t,
                                             moe_mode=ecfg.moe_mode))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(cold(params, jnp.asarray(tokens[None]))[0])
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    rel = err / scale
    print(f"prefill: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} prompt={prompt_len} chunk={chunk} "
          f"chunks={-(-prompt_len // chunk)} engine_s={t_engine:.2f} "
          f"max|dlogit|={err:.4e} max|logit|={scale:.4e} rel={rel:.3e} "
          f"(tol {PREFILL_REL_TOL:g}) argmax_equal={got.argmax() == ref.argmax()}",
          flush=True)
    if not rel <= PREFILL_REL_TOL:
        raise SystemExit(f"prefill: relative logit error {rel:.3e} > "
                         f"{PREFILL_REL_TOL:g}")
    return rel


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

class _CompileLog:
    """Wall-clock times of executable builds (compiled, or loaded from
    the persistent cache) and persistent-cache hits."""

    def __init__(self):
        self.builds: List[float] = []
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.builds.append(time.time())

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def phase_serve(arch: str = ARCH, token_scale: float = 1.0,
                rate: float = RATE_RPS) -> Dict:
    import jax
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        serve_args(arch, token_scale, rate))
    log = _CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    try:
        res = asyncio.run(serve.serve_smoke(args))
    finally:
        jax.monitoring.unregister_event_duration_listener(log.on_duration)
        jax.monitoring.unregister_event_listener(log.on_event)
    in_window = sum(t > res["warmup_end"] for t in log.builds)
    res["executables_warmup"] = len(log.builds) - in_window
    res["executables_serving"] = in_window
    res["cache_hits"] = log.cache_hits
    print(f"serve: {res['model']} layers={res['num_layers']} "
          f"d_model={res['d_model']} agents={AGENTS} token_scale={token_scale} "
          f"kv_layout={res['kv_layout']} max_seq={res['max_seq']} "
          f"warmup_s={res['warmup_s']:.2f} "
          f"executables_warmup={res['executables_warmup']} "
          f"executables_serving={in_window} cache_hits={log.cache_hits} "
          f"overlap={res['overlap']} tokens_streamed={res['tokens_streamed']} "
          f"output_tokens={res['output_tokens']} "
          f"capacity_overruns={res['capacity_overruns']} "
          f"engine_errors={res['engine_errors']} wall_s={res['wall_s']:.2f}",
          flush=True)
    return res


def main() -> int:
    cache_dir = enable_compile_cache()
    dev = check_device()
    import jax
    print(f"compile cache: {cache_dir}", flush=True)
    phase_kernels()
    print(f"memory: peak_bytes_in_use={peak_bytes(dev)} after kernels",
          flush=True)
    phase_prefill()
    print(f"memory: peak_bytes_in_use={peak_bytes(dev)} after prefill",
          flush=True)
    phase_serve()
    print(f"memory: peak_bytes_in_use={peak_bytes(dev)} after serve",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
