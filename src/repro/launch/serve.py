"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Two modes:

  * closed-loop (default): boots an AgentServe engine for the selected
    architecture and serves a multi-agent ToolBench-like workload,
    printing the per-policy report (the paper's Fig-5-style output);
  * online (``--serve``): boots the asyncio gateway (DESIGN.md §6) and
    exposes a minimal stdlib HTTP/SSE front —

        GET  /healthz      liveness
        GET  /stats        unified telemetry registry snapshot (JSON)
        GET  /metrics      the same registry, Prometheus text format
        POST /v1/session   submit an agent session; streams one
                           ``data: {...}`` SSE line per token, a final
                           ``event: done`` record, or HTTP 429 when the
                           admission watermark sheds the request.

    ``--serve-smoke`` boots the same server on an ephemeral port,
    drives it with an in-process asyncio client at an open-loop Poisson
    rate, prints the open-loop report row, and exits — the CI gateway
    smoke path.

``--arch <id>`` builds the published config (random weights from a
seed); ``--arch <id>-smoke`` builds its reduced CPU-test variant.  Each
slot holds ``max_seq`` KV rows, sized from ``--workload`` at
``--token-scale`` so no session runs past its slot.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

import jax

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.gateway import (AgentGateway, GatewayConfig,
                                   GatewayStopped, Rejected)
from repro.serving.metrics import (OpenLoopReport, ServingReport,
                                   SLOThresholds, build_open_loop_report)
from repro.serving.policies import PLANNERS, POLICIES
from repro.serving.telemetry import (parse_prometheus_text,
                                     reconstruct_latency,
                                     validate_trace_events)
from repro.serving.workload import (SPECS, make_session, make_workload,
                                    max_session_tokens, poisson_arrivals)


# ---------------------------------------------------------------------------
# HTTP/SSE front (stdlib asyncio only — no extra deps)
# ---------------------------------------------------------------------------

def _http_resp(status: int, body: bytes, ctype: str = "application/json",
               ) -> bytes:
    reason = {200: "OK", 404: "Not Found", 429: "Too Many Requests",
              400: "Bad Request",
              503: "Service Unavailable"}.get(status, "OK")
    return (f"HTTP/1.1 {status} {reason}\r\nContent-Type: {ctype}\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
            ).encode() + body


def _json_resp(status: int, obj) -> bytes:
    return _http_resp(status, json.dumps(obj).encode())


def _session_from_spec(spec: Dict, mcfg, default_token_scale: float,
                       max_seq: int):
    """Build a scripted agent session from a client JSON spec:
    ``{"workload": "react", "seed": 7, "token_scale": 0.1,
    "slo_class": "interactive", "deadline_s": 30.0}``.  The session_id
    is assigned by the gateway at admission; ``slo_class`` matters under
    ``--policy priority`` (interactive requests preempt batch cold
    prefills); ``deadline_s`` (relative seconds, optional) arms an
    engine-enforced SLO deadline — past it the session is aborted and
    its stream ends with an ``event: aborted`` record.  A spec whose
    longest possible session would not fit a ``max_seq`` slot is
    refused."""
    workload = spec.get("workload", "react")
    if workload not in SPECS:
        raise ValueError(f"unknown workload {workload!r}")
    slo_class = spec.get("slo_class", "batch")
    if slo_class not in ("interactive", "batch"):
        raise ValueError(f"unknown slo_class {slo_class!r}")
    seed = int(spec.get("seed", 0))
    scale = float(spec.get("token_scale", default_token_scale))
    if max_session_tokens(workload, scale) >= max_seq:
        raise ValueError(f"{workload} sessions at token_scale {scale} can "
                         f"outgrow the server's {max_seq}-token slots")
    rng = np.random.default_rng(seed)
    sess = make_session(-1, SPECS[workload], rng, mcfg.vocab_size,
                        token_scale=scale)
    sess.slo_class = slo_class
    return sess


async def _read_request(reader) -> Tuple[str, str, Dict[str, str], bytes]:
    line = await reader.readline()
    if not line:
        raise ConnectionError("empty request")
    parts = line.decode("latin1").split()
    if len(parts) < 2:
        raise ValueError(f"bad request line {line!r}")
    method, path = parts[0], parts[1]
    headers: Dict[str, str] = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        k, _, v = h.decode("latin1").partition(":")
        headers[k.strip().lower()] = v.strip()
    body = b""
    n = int(headers.get("content-length", "0") or 0)
    if n:
        body = await reader.readexactly(n)
    return method, path, headers, body


async def handle_connection(gateway: AgentGateway, mcfg,
                            default_token_scale: float,
                            reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter) -> None:
    try:
        try:
            method, path, _, body = await _read_request(reader)
        except (ValueError, ConnectionError, asyncio.IncompleteReadError):
            return
        if method == "GET" and path == "/healthz":
            writer.write(_json_resp(200, {"ok": True}))
        elif method == "GET" and path == "/stats":
            writer.write(_json_resp(200, gateway.stats()))
        elif method == "GET" and path == "/metrics":
            text = gateway.engine.telemetry.registry.prometheus_text()
            writer.write(_http_resp(200, text.encode(),
                                    "text/plain; version=0.0.4"))
        elif method == "POST" and path == "/v1/session":
            try:
                spec = json.loads(body or b"{}")
                if not isinstance(spec, dict):
                    raise ValueError("request body must be a JSON object")
                sess = _session_from_spec(spec, mcfg, default_token_scale,
                                          gateway.engine.ecfg.max_seq)
                deadline = spec.get("deadline_s")
                deadline = None if deadline is None else float(deadline)
            except (ValueError, KeyError, TypeError) as e:
                writer.write(_json_resp(400, {"error": str(e)}))
                await writer.drain()
                return
            try:
                res = await gateway.submit(sess, deadline_s=deadline)
            except GatewayStopped as e:
                writer.write(_json_resp(503, {"error": str(e)}))
                await writer.drain()
                return
            if isinstance(res, Rejected):
                writer.write(_json_resp(429, {
                    "error": res.reason, "occupancy": res.occupancy}))
                await writer.drain()
                return
            writer.write(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Type: text/event-stream\r\n"
                         b"Cache-Control: no-cache\r\n"
                         b"Connection: close\r\n\r\n")
            await writer.drain()
            # disconnect watcher: the request body is fully consumed, so
            # any further read completing means the peer closed its end
            # of the connection — cancel the session so the engine
            # reclaims its slot/pages promptly (DESIGN.md §10)
            watcher = asyncio.get_running_loop().create_task(reader.read())
            aborted_ev = None
            try:
                async for ev in res.events():
                    if watcher.done():
                        res.cancel()     # client went away mid-stream
                    if ev.error:
                        aborted_ev = ev
                    writer.write(b"data: "
                                 + json.dumps(dataclasses.asdict(ev)).encode()
                                 + b"\n\n")
                    await writer.drain()
            except (ConnectionResetError, BrokenPipeError):
                res.cancel()             # write side saw the disconnect
                raise
            finally:
                watcher.cancel()
            if aborted_ev is not None:
                writer.write(b"event: aborted\ndata: "
                             + json.dumps({
                                 "session_id": res.session_id,
                                 "reason": aborted_ev.abort_reason,
                                 "tokens": len(res.received) - 1}).encode()
                             + b"\n\n")
            else:
                writer.write(b"event: done\ndata: "
                             + json.dumps({
                                 "session_id": res.session_id,
                                 "tokens": len(res.received)}).encode()
                             + b"\n\n")
        else:
            writer.write(_json_resp(404, {"error": f"no route {path}"}))
        await writer.drain()
    except (ConnectionResetError, BrokenPipeError):
        pass                             # client went away mid-stream
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass


# ---------------------------------------------------------------------------
# asyncio SSE client (smoke driver + tests + benchmarks)
# ---------------------------------------------------------------------------

async def sse_submit(host: str, port: int, spec: Dict,
                     ) -> Tuple[int, List[Dict]]:
    """POST one session spec and consume its SSE stream.  Returns
    (http_status, token event dicts)."""
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps(spec).encode()
    writer.write((f"POST /v1/session HTTP/1.1\r\nHost: {host}\r\n"
                  f"Content-Type: application/json\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    status_line = await reader.readline()
    status = int(status_line.split()[1])
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass                             # skip response headers
    events: List[Dict] = []
    if status == 200:
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if line in (b"event: done", b"event: aborted"):
                await reader.readline()  # the terminal data record
                break
            if line.startswith(b"data: "):
                events.append(json.loads(line[len(b"data: "):]))
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionResetError, BrokenPipeError):
        pass
    return status, events


async def sse_get(host: str, port: int, path: str) -> Tuple[int, Dict]:
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n\r\n".encode())
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    n = 0
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        if h.lower().startswith(b"content-length:"):
            n = int(h.split(b":")[1])
    body = json.loads(await reader.readexactly(n)) if n else {}
    writer.close()
    await writer.wait_closed()
    return status, body


async def http_get_text(host: str, port: int, path: str,
                        ) -> Tuple[int, str]:
    """Raw-text GET (the ``/metrics`` scrape — Prometheus text is not
    JSON, so ``sse_get`` cannot fetch it)."""
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n\r\n".encode())
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    n = 0
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        if h.lower().startswith(b"content-length:"):
            n = int(h.split(b":")[1])
    body = (await reader.readexactly(n)).decode() if n else ""
    writer.close()
    await writer.wait_closed()
    return status, body


def _export_trace(engine, path: str) -> None:
    """Dump the run's span timeline as Chrome/Perfetto trace_event JSON
    (``--trace-out``), re-validated on the way out."""
    if not path:
        return
    n = engine.telemetry.export_trace(path)
    with open(path) as f:
        validate_trace_events(json.load(f))
    print(f"trace: {n} events -> {path} (open in ui.perfetto.dev)",
          flush=True)


# ---------------------------------------------------------------------------
# gateway boot
# ---------------------------------------------------------------------------

def model_config(args):
    """The served model: the published config of ``--arch`` (or its
    reduced variant for ``<arch>-smoke``) in the requested KV layout."""
    cfg = get_config(args.arch)
    if args.kv_layout != "slab":
        cfg = dataclasses.replace(cfg, kv_layout=args.kv_layout,
                                  kv_page_size=args.kv_page_size)
    return cfg


def engine_config(args, cfg, *, max_wall_s: float = 300.0) -> EngineConfig:
    """Engine shapes for the CLI.  ``max_seq`` holds the longest session
    ``--workload`` can produce at ``--token-scale`` plus the scratch row
    (DESIGN.md §3) and at least one largest resume bucket (warm-up
    writes a whole bucket at offset 0), rounded up to the KV page."""
    ecfg = EngineConfig(num_slots=max(args.agents + 2, 6),
                        cycle_budget=160, granularity=16,
                        control_interval_s=0.1, max_wall_s=max_wall_s,
                        prefix_cache=args.prefix_cache,
                        spec_k=args.spec_k,
                        overlap_dispatch=(
                            False if args.no_overlap
                            else True if args.overlap else None))
    need = max(max_session_tokens(args.workload, args.token_scale) + 1,
               ecfg.b_max)
    ecfg.max_seq = -(-need // cfg.kv_page_size) * cfg.kv_page_size
    return ecfg


def _build_engine(args, *, max_wall_s: float = 300.0,
                  ) -> Tuple[ServingEngine, object]:
    """One engine construction for both the closed-loop and online
    paths — they must not silently diverge in shapes/budget."""
    cfg = model_config(args)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ecfg = engine_config(args, cfg, max_wall_s=max_wall_s)
    return ServingEngine(cfg, params, PLANNERS[args.policy], ecfg), cfg


def build_gateway(args) -> Tuple[AgentGateway, object]:
    engine, cfg = _build_engine(args, max_wall_s=float("inf"))
    gcfg = GatewayConfig(high_watermark=args.high_watermark,
                         admission=args.admission,
                         tool_policy=args.tool_policy)
    return AgentGateway(engine, gcfg), cfg


async def _serve(args) -> int:
    gateway, mcfg = build_gateway(args)
    await gateway.start()
    server = await asyncio.start_server(
        lambda r, w: handle_connection(gateway, mcfg, args.token_scale,
                                       r, w),
        args.host, args.port)
    port = server.sockets[0].getsockname()[1]
    print(f"gateway serving on http://{args.host}:{port} "
          f"(policy={args.policy}, watermark={args.high_watermark})",
          flush=True)
    try:
        async with server:
            await server.serve_forever()
    except asyncio.CancelledError:
        pass
    finally:
        await gateway.stop(timeout_s=5.0)
        _export_trace(gateway.engine, args.trace_out)
    return 0


class SmokeFailed(RuntimeError):
    """A ``--serve-smoke`` check failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailed(msg)


async def serve_smoke(args) -> Dict:
    """Boot the SSE server on an ephemeral port, drive it with an
    asyncio client cohort at an open-loop Poisson rate, and print the
    open-loop report — end-to-end over real sockets.  Raises
    ``SmokeFailed`` when a check fails; returns the run's summary."""
    t_boot = time.perf_counter()
    gateway, mcfg = build_gateway(args)
    engine = gateway.engine
    warmup_s = time.perf_counter() - t_boot
    warmup_end = time.time()
    print(f"engine: {mcfg.name} layers={mcfg.num_layers} "
          f"d_model={mcfg.d_model} heads={mcfg.num_heads}/"
          f"{mcfg.num_kv_heads} vocab={mcfg.vocab_size} "
          f"kv_layout={mcfg.kv_layout} slots={engine.ecfg.num_slots} "
          f"max_seq={engine.ecfg.max_seq} overlap={engine._overlap} "
          f"warmup_s={warmup_s:.2f}", flush=True)
    await gateway.start()
    server = await asyncio.start_server(
        lambda r, w: handle_connection(gateway, mcfg, args.token_scale,
                                       r, w),
        args.host, 0)
    port = server.sockets[0].getsockname()[1]
    print(f"smoke server on {args.host}:{port}", flush=True)

    arrivals = poisson_arrivals(args.rate, args.agents, seed=args.seed)
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    statuses: List[int] = []
    all_events: List[Tuple[float, Dict]] = []

    async def one(i: int, at: float) -> None:
        delay = at - (loop.time() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        status, events = await sse_submit(
            args.host, port, {"workload": args.workload, "seed": args.seed + i,
                              "token_scale": args.token_scale})
        statuses.append(status)
        all_events.extend((loop.time() - t0, e) for e in events)

    await asyncio.gather(*(one(i, a) for i, a in enumerate(arrivals)))
    wall = loop.time() - t0

    # telemetry surfaces, checked over the live socket (DESIGN.md §11):
    # /metrics parses as Prometheus text and the three stats views —
    # engine, gateway, HTTP — expose identical key sets
    m_status, m_text = await http_get_text(args.host, port, "/metrics")
    s_status, http_stats = await sse_get(args.host, port, "/stats")
    await gateway.stop(timeout_s=30.0)
    server.close()
    await server.wait_closed()

    # an exception the gateway loop swallowed explains every later
    # symptom (failed streams, missing tokens): report it first
    errors = int(gateway.counters["engine_errors"])
    _check(errors == 0, f"{errors} engine loop iterations failed; last:\n"
                        f"{gateway.last_engine_error}")
    _check(m_status == 200, f"/metrics returned {m_status}")
    samples = parse_prometheus_text(m_text)
    _check(bool(samples), "/metrics served no samples")
    _check(s_status == 200, f"/stats returned {s_status}")
    _check(set(http_stats) == set(gateway.stats()) == set(engine.stats()),
           "stats key drift")
    print(f"/metrics: {len(samples)} samples, "
          f"/stats: {len(http_stats)} keys (views agree)", flush=True)

    ok = statuses.count(200)
    shed = statuses.count(429)
    sids = {e["session_id"] for _, e in all_events}
    print(f"agents={args.agents} rate={args.rate}/s wall={wall:.2f}s "
          f"streams_ok={ok} shed_429={shed} "
          f"tokens={len(all_events)} sessions_streamed={len(sids)}",
          flush=True)
    done = list(gateway.completed_sessions)
    rep = build_open_loop_report(
        args.policy, done, wall, args.rate, rejected=shed,
        thresholds=SLOThresholds(ttft_s=10.0, tpot_s=2.0),
        aborted_sessions=list(gateway.failed_sessions))
    rep.tokens_per_model_call = engine.tokens_per_model_call()
    print(OpenLoopReport.HEADER)
    print(rep.row(), flush=True)
    _check(ok + shed == args.agents,
           f"every request must resolve: statuses {sorted(statuses)}")
    _check(ok > 0 and len(all_events) > 0, "no tokens streamed")
    _check(len(done) == ok, f"{ok} sessions admitted, {len(done)} finished")
    short = [s.session_id for s in done
             if s.output_tokens() != sum(t.decode_len for t in s.turns)]
    _check(not short, f"sessions {short} emitted a wrong token count")
    overruns = int(engine.hotpath_stats["capacity_overruns"])
    _check(overruns == 0, f"{overruns} KV capacity overruns "
                          f"(max_seq={engine.ecfg.max_seq})")

    # timeline export + the acceptance cross-check: per-session spans
    # must reconstruct TTFT/TPOT within 1% of metrics.py's values
    tracer = engine.telemetry.tracer
    if tracer is not None and done:
        from repro.serving.metrics import collect_tpots, collect_ttfts
        span_ttfts, span_tpot = reconstruct_latency(tracer.spans)
        m_ttfts = collect_ttfts(done)
        m_tpots = collect_tpots(done)
        if m_ttfts:
            a, b = float(np.mean(span_ttfts)), float(np.mean(m_ttfts))
            _check(abs(a - b) <= 0.01 * b, f"span TTFT {a} vs {b}")
        if m_tpots:
            a, b = span_tpot, float(np.mean(m_tpots))
            _check(abs(a - b) <= 0.01 * b, f"span TPOT {a} vs {b}")
        _check(tracer.open_span_count() == 0,
               f"leaked spans: {tracer.open_spans()}")
        print(f"span reconstruction OK: {len(span_ttfts)} TTFTs, "
              f"mean TPOT {span_tpot * 1e3:.2f}ms within 1% of metrics",
              flush=True)
    _export_trace(engine, args.trace_out)
    return {"model": mcfg.name, "num_layers": mcfg.num_layers,
            "d_model": mcfg.d_model, "kv_layout": mcfg.kv_layout,
            "max_seq": engine.ecfg.max_seq, "overlap": engine._overlap,
            "warmup_s": warmup_s, "warmup_end": warmup_end,
            "wall_s": wall, "streams_ok": ok, "shed": shed,
            "tokens_streamed": len(all_events),
            "output_tokens": sum(s.output_tokens() for s in done),
            "capacity_overruns": overruns, "engine_errors": errors}


# ---------------------------------------------------------------------------
# closed-loop mode (unchanged Fig-5 path)
# ---------------------------------------------------------------------------

def _closed_loop(args) -> int:
    policies = sorted(POLICIES) if args.compare else [args.policy]
    print(ServingReport.HEADER)
    for policy in policies:
        eng, cfg = _build_engine(
            argparse.Namespace(**{**vars(args), "policy": policy}))
        sessions = make_workload(
            args.agents, workload=args.workload,
            vocab_size=cfg.vocab_size, token_scale=args.token_scale,
            num_system_prompts=1, seed=args.seed)
        rep = eng.run(sessions)
        print(rep.row(), flush=True)
        # --compare reruns per policy; the trace captures the last run
        _export_trace(eng, args.trace_out)
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    help="registry id: the published config; append "
                         "-smoke for the reduced CPU-test variant")
    ap.add_argument("--policy", default="agentserve",
                    choices=sorted(PLANNERS))
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--workload", default="react",
                    choices=["react", "plan_execute"])
    ap.add_argument("--token-scale", type=float, default=0.125)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare", action="store_true",
                    help="run every policy on the same workload")
    # online gateway mode
    ap.add_argument("--serve", action="store_true",
                    help="boot the online HTTP/SSE gateway")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="boot the gateway and drive it with an in-process "
                         "open-loop client cohort, then exit")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8077)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--high-watermark", type=int, default=8)
    ap.add_argument("--admission", default="reject",
                    choices=["reject", "queue"])
    ap.add_argument("--tool-policy", default="hold",
                    choices=["hold", "release"])
    ap.add_argument("--kv-layout", default="slab",
                    choices=["slab", "paged"],
                    help="KV cache layout (DESIGN.md §8): paged enables "
                         "zero-copy prefix sharing and park/unpark")
    ap.add_argument("--kv-page-size", type=int, default=64)
    ap.add_argument("--prefix-cache", default="radix",
                    choices=["radix", "exact"],
                    help="paged prefix index (DESIGN.md §13): radix = "
                         "longest-match trie over token IDs (partial hits, "
                         "tree-aware eviction); exact = legacy whole-key "
                         "store (the slab-parity oracle path)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode draft length (DESIGN.md §12): "
                         "0 disables; >=2 enables n-gram draft-and-verify "
                         "megasteps (greedy-identical by construction)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable double-buffered async dispatch — block on "
                         "each decode window before submitting the next "
                         "(DESIGN.md §12); default is auto (on for "
                         "async-dispatch backends, off on CPU)")
    ap.add_argument("--overlap", action="store_true",
                    help="force double-buffered dispatch on even where "
                         "auto would disable it (CPU executes dispatches "
                         "synchronously, so overlap only delays token "
                         "observation there)")
    ap.add_argument("--trace-out", default="",
                    help="write the run's span timeline as Chrome/"
                         "Perfetto trace_event JSON to this path "
                         "(load in ui.perfetto.dev or chrome://tracing)")
    return ap


def main(argv=None) -> int:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    if args.serve_smoke:
        asyncio.run(serve_smoke(args))
        return 0
    if args.serve:
        return asyncio.run(_serve(args))
    return _closed_loop(args)


if __name__ == "__main__":
    sys.exit(main())
