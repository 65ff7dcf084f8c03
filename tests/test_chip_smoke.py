"""``chip_smoke.py`` on the CPU: its prefill-correctness and serve
phases at the ``-smoke`` size, the kernel cases in interpret mode, and
its refusal to run without a TPU."""
import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

SMOKE_ARCH = "smollm-360m-smoke"


def test_main_refuses_cpu_and_prints_no_result(monkeypatch, tmp_path,
                                               capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    msg = str(exc.value.code)
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in msg and "cpu" in msg
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_cases_match_reference_in_interpret_mode():
    for name, fn, args, ref in chip_smoke.kernel_cases(
            batch=2, seq=256, chunk=64, interpret=True):
        out = jax.jit(fn)(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5, err_msg=name)


def test_prefill_phase_matches_cold_forward_at_smoke_size():
    # f32 on the CPU: far inside the chip's stated tolerance
    rel = chip_smoke.phase_prefill(SMOKE_ARCH)
    assert rel < 1e-4


def test_serve_phase_at_smoke_size():
    res = chip_smoke.phase_serve(SMOKE_ARCH, token_scale=0.1, rate=4.0)
    assert res["streams_ok"] == chip_smoke.AGENTS and res["shed"] == 0
    assert res["capacity_overruns"] == 0 and res["engine_errors"] == 0
    assert res["tokens_streamed"] == res["output_tokens"] > 0
    assert res["executables_warmup"] > 0
    assert res["overlap"] is False          # CPU dispatch is synchronous
