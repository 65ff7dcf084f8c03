"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine and gateway (``BENCHMARK.json`` names the
configuration and the traffic mix), serves the mix through the HTTP/SSE
front, measures a window of ``--seconds`` and checks what was served
against the plain reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device``, when traced ``breakdown``, the gap
``readings`` of the correctness check, and last of all ``checks``, each
compared number beside its limit.  Needs a TPU:
without one it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu would otherwise keep its logs at a fixed path outside the run
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

METRICS_DIR = ROOT / "bench" / "metrics"


class NoChip(SystemExit):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also write the trace's plain events here")
    return ap.parse_args(argv)


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"bench: needs {chips} TPU chip(s), JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def enable_cache() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, comes from the cache on
    # the next run: set-up then does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def metric_reader(name: str, metrics_dir: Path = METRICS_DIR):
    """``read`` of ``<metrics_dir>/<name>.py``."""
    path = Path(metrics_dir) / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} in {metrics_dir}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(args, *, skip_device_check: bool = False, dtype=None,
        cell=None, fault=None, metrics_dir: Path = METRICS_DIR,
        reference_control: bool = False) -> dict:
    """One run; returns the result object (the last stdout line)."""
    import asyncio

    import jax

    from bench import correctness, harness, traffic as tr
    from bench.cost import peaks as peak_table
    from bench.record import Record, reduce_trace

    cell = cell or harness.load_cell(args.workload)
    devs = jax.devices() if skip_device_check else check_device(cell.chips)
    dev = devs[0]
    schedule = tr.make_schedule(cell.mix, args.seconds, args.seed)
    plans = tr.session_plans(cell.mix, schedule,
                             cell.config["vocab_size"])
    srv = harness.Server(cell, args.seed, dtype=dtype,
                         annotate=bool(args.trace))
    if fault is not None:
        fault(srv)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
        if args.trace else None
    setup = {}
    obs = asyncio.run(harness.drive(
        srv, schedule, plans, args.seconds, trace_dir=trace_dir,
        on_open=lambda: setup.setdefault(
            "s", time.perf_counter() - T_PROCESS)))
    stats = dev.memory_stats() or {}
    mem = {"peak": int(stats.get("peak_bytes_in_use", 0)),
           "limit": int(stats.get("bytes_limit", 0))}
    num_slots = srv.engine.ecfg.num_slots
    pad_to = srv.engine.ecfg.max_seq
    srv.free()
    del srv
    gc.collect()

    reqs, refused, bad = harness.requests_in_window(obs, plans)
    attempted = len(reqs) + refused
    failed = refused + sum(r.failed for r in reqs)
    late = [st.sent_s - d for st, d in zip(obs.streams, obs.due_cold)
            if st.sent_s]
    cold = sum(r.turn == 0 for r in reqs)
    print(f"bench: {cell.name} seed {args.seed}: {len(obs.streams)} "
          f"sessions, {len(reqs)} requests in the window ({cold} "
          f"cold), {refused} refused, {failed} failed; {len(obs.cycles)} "
          f"cycles; drain {obs.end_s - obs.close_s:.2f} s; generator "
          f"late by at most {max(late, default=0.0) * 1e3:.1f} ms",
          file=sys.stderr)

    checks, readings = correctness.check(
        cell, obs, plans, args.seed, pad_to, bad,
        reference_control=reference_control)
    correct = all(c["ok"] for c in checks.values())
    checks = {k: {"value": c["value"], "limit": c["limit"]}
              for k, c in checks.items()}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem["peak"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    if args.trace:
        trace = reduce_trace(obs, keep=args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        pk = {} if trace is None else peak_table(dev.device_kind)
        rec = Record(cfg=cell.config, peaks=pk,
                     obs=obs, reqs=reqs, plans=plans, trace=trace,
                     memory=mem, num_slots=num_slots)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], metrics_dir)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["top_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
    else:
        e2e = harness.end_to_end(obs, reqs)
        e2e["setup_s"] = setup.get("s")
        print(f"bench: window statistics {json.dumps(e2e)}",
              file=sys.stderr)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    result["readings"] = readings
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        check_device(1)
        enable_cache()
        result = run(args)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
