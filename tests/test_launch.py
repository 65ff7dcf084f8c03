"""Entry points: registry names, launcher shapes, the serve smoke's
failure reporting, and where the compile cache lives."""
import asyncio
import os
import pathlib
import subprocess
import sys
import uuid

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, get_smoke_config, reduced
from repro.launch import compile_cache, serve
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.gateway import AgentGateway, GatewayConfig
from repro.serving.policies import POLICIES
from repro.serving.workload import (SPECS, make_session, make_workload,
                                    max_session_tokens)

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_registry_resolves_published_and_smoke_names():
    full = get_config("smollm-360m")
    assert (full.num_layers, full.d_model, full.vocab_size) == \
        (32, 960, 49152)
    smoke = get_config("smollm-360m-smoke")
    assert smoke == reduced(full) == get_smoke_config("smollm-360m")
    assert smoke.name == "smollm-360m-smoke"
    with pytest.raises(KeyError):
        get_config("no-such-arch-smoke")


@pytest.mark.parametrize("workload", sorted(SPECS))
@pytest.mark.parametrize("scale", [1.0, 0.125, 0.04])
def test_max_session_tokens_bounds_generated_sessions(workload, scale):
    bound = max_session_tokens(workload, scale)
    sessions = make_workload(300, workload=workload, token_scale=scale,
                             num_system_prompts=1, seed=3)
    rng = np.random.default_rng(4)
    sessions += [make_session(i, SPECS[workload], rng, 512,
                              token_scale=scale) for i in range(300)]
    longest = max(sum(len(t.prefill_tokens) + t.decode_len for t in s.turns)
                  for s in sessions)
    assert longest <= bound
    assert longest > 0.6 * bound        # a bound, not a guess


def _args(*extra):
    return serve.build_parser().parse_args(list(extra))


def test_engine_config_sizes_slots_from_the_workload():
    args = _args("--arch", "smollm-360m", "--token-scale", "1.0")
    cfg = serve.model_config(args)
    ecfg = serve.engine_config(args, cfg)
    assert ecfg.max_seq % cfg.kv_page_size == 0
    assert ecfg.max_seq > max_session_tokens("react", 1.0)
    assert ecfg.max_seq - cfg.kv_page_size <= max_session_tokens("react", 1.0)
    small = serve.engine_config(_args("--token-scale", "0.04"), cfg)
    assert small.max_seq >= small.b_max    # warm-up writes a whole bucket


def test_session_spec_longer_than_a_slot_is_refused():
    cfg = get_config("smollm-360m-smoke")
    with pytest.raises(ValueError, match="outgrow"):
        serve._session_from_spec({"token_scale": 1.0}, cfg, 0.125,
                                 max_seq=1024)
    sess = serve._session_from_spec({}, cfg, 0.125, max_seq=1024)
    assert sum(len(t.prefill_tokens) + t.decode_len
               for t in sess.turns) < 1024


def test_serve_smoke_fails_on_engine_errors_and_shows_them(monkeypatch):
    """A swallowed engine exception fails --serve-smoke, and the failure
    carries the exception's traceback."""
    cfg = get_config("smollm-360m-smoke")
    args = _args("--arch", "smollm-360m-smoke", "--agents", "2",
                 "--rate", "8", "--token-scale", "0.04", "--serve-smoke")

    def build_gateway(_):
        ecfg = EngineConfig(num_slots=4, max_seq=512, cycle_budget=80,
                            granularity=8, b_min=8, b_max=128, b_init=32,
                            delta_b=8, control_interval_s=0.05,
                            max_wall_s=float("inf"))
        eng = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                            POLICIES["agentserve"], ecfg)
        gw = AgentGateway(eng, GatewayConfig(high_watermark=32))
        step, calls = gw.reactor.step, []

        def flaky_step():
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("injected device fault")
            return step()

        gw.reactor.step = flaky_step
        return gw, cfg

    monkeypatch.setattr(serve, "build_gateway", build_gateway)
    with pytest.raises(serve.SmokeFailed) as exc:
        asyncio.run(serve.serve_smoke(args))
    assert "1 engine loop iterations failed" in str(exc.value)
    assert "injected device fault" in str(exc.value)
    assert "Traceback" in str(exc.value)


def _run_compile(env):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            f"f = jax.jit(lambda x: x * {uuid.uuid4().int % 10**6} + 1)\n"
            "f(jnp.ones(4)).block_until_ready()\n")
    env = {**env, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def _entries(path: pathlib.Path):
    return set(os.listdir(path)) if path.is_dir() else set()


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["HOME"] = str(tmp_path)
    outside = tmp_path / "cache"
    before = _entries(compile_cache.CHECKOUT_CACHE_DIR)
    used = _run_compile({**env, compile_cache.ENV_VAR: str(outside)})
    assert used == str(outside) and _entries(outside)
    assert _entries(compile_cache.CHECKOUT_CACHE_DIR) == before

    used = _run_compile(env)
    assert pathlib.Path(used) == compile_cache.CHECKOUT_CACHE_DIR
    assert compile_cache.CHECKOUT_CACHE_DIR.parent == ROOT
    new = _entries(compile_cache.CHECKOUT_CACHE_DIR) - before
    assert new
    for name in new:                       # leave the checkout as found
        (compile_cache.CHECKOUT_CACHE_DIR / name).unlink()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
