"""Router front-door ceiling: requests/s through the cluster router with
NO model behind it.

Every other serving benchmark measures decode; this one isolates the
FRONT DOOR — the cost ROADMAP item 3 calls the wall at production QPS:
readline + json loads/dumps per message at client, router, and replica.
The fleet is :class:`~distkeras_tpu.serving.cluster.replicas.EchoServer`
(protocol-complete, engine-free: each request is answered with
``--echo-tokens`` token events and a done line), so wall time is pure
wire + router cost and the measured number is the router's requests/s
CEILING, not a decode throughput.

Methodology: the router runs ALONE in this process; echo replicas and
load-generating clients are separate OS processes (fork), so the
router's single event loop is the measured resource — in-process
clients would bill their own wire cost to the router's core and mask
the ceiling. Client processes warm up (connect + negotiate + a few
round trips), meet at a barrier, then drive the timed run.

Two wire modes, measured in one invocation:

- ``jsonl`` — the BEFORE number: a ``wire='jsonl'`` router (binary
  upgrade disabled in BOTH directions, i.e. the pre-bin1 code path:
  exclusive pooled backend connections, one readline + json round per
  message) under sequential-per-connection JSONL clients;
- ``bin1`` — the AFTER number: a ``wire='auto'`` router with the
  negotiated binary front door — multiplexed per-replica backend
  connections, pipelined client streams, batched frame reads, and
  coalesced token writes.

``--record-history`` writes ``serving/router_echo/...`` rows
(requests/s per wire + the bin1/jsonl ``speedup_x``) under the same
strict ``--only serving/`` CI gate as every serving row, and
``--min-speedup X`` turns the ratio into a hard assertion — the
acceptance run uses ``--min-speedup 5``.

Run (no jax/accelerator needed — pure asyncio + the native wire core):

    python benchmarks/router_bench.py --requests 20000 --replicas 2 \
        --client-procs 4 --pipeline 64 --min-speedup 5 --record-history
"""

from __future__ import annotations

import argparse
import asyncio
import json
import multiprocessing as mp
import os
import sys
import time

# Self-contained even without `pip install -e .`: nothing here needs
# jax, so this bench must run anywhere the checkout exists.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


# -- child processes --------------------------------------------------------
def _echo_proc(conn, echo_tokens: int, kv_block_tokens: int = 4) -> None:
    """One echo replica in its own process: bind, report the port, serve
    until killed. ``kv_block_tokens`` sizes the emulated KV blocks so a
    roles run's short bench prompts still export/adopt chains."""
    from distkeras_tpu.serving.cluster.replicas import EchoServer

    async def run():
        server = EchoServer(echo_tokens=echo_tokens,
                            kv_block_tokens=kv_block_tokens)
        await server.start()
        conn.send(("127.0.0.1", server.port))
        await asyncio.Event().wait()  # until SIGTERM

    try:
        asyncio.run(run())
    except (KeyboardInterrupt, SystemExit):
        pass


def _client_proc(conn, barrier, port: int, wire_name: str, requests: int,
                 conns: int, pipeline: int, prompt_len: int) -> None:
    """One load-generator process: warm up its connections, wait at the
    barrier with every other client, drive its share of the load, and
    report (wall_s, completed, latency samples)."""

    async def run():
        from distkeras_tpu.serving import ServingClient

        all_prompts = [[(i % 250) + 1] * prompt_len
                       for i in range(requests)]
        latencies: list[float] = []
        completed = 0

        async def worker_jsonl(c, prompts):
            nonlocal completed
            for p in prompts:
                t0 = time.monotonic()
                await c.generate(p, 1)
                latencies.append(time.monotonic() - t0)
                completed += 1

        async def worker_bin1(c, prompts):
            # Waves of `pipeline` requests per connection: one buffered
            # write of REQ frames, futures resolved by the demux loop —
            # the batched-admission client shape. Latency here is
            # time-to-complete for a request inside its wave.
            nonlocal completed
            for i in range(0, len(prompts), pipeline):
                wave = prompts[i:i + pipeline]
                t0 = time.monotonic()
                dones = await c.generate_batch(wave, 1)
                dt = time.monotonic() - t0
                ok = sum(1 for d in dones if isinstance(d, dict))
                completed += ok
                latencies.extend([dt] * ok)

        clients = []
        share = len(all_prompts) // conns or 1
        for i in range(conns):
            c = ServingClient("127.0.0.1", port,
                              wire_mode="bin1" if wire_name == "bin1"
                              else "jsonl")
            await c.connect()
            await c.generate([1, 2], 1)  # warm the route
            clients.append((c, all_prompts[i * share:(i + 1) * share]
                            if i < conns - 1
                            else all_prompts[i * share:]))
        barrier.wait(timeout=60)
        t0 = time.monotonic()
        worker = worker_bin1 if wire_name == "bin1" else worker_jsonl
        await asyncio.gather(*(worker(c, ps) for c, ps in clients))
        wall = time.monotonic() - t0
        for c, _ in clients:
            await c.aclose()
        # Ship a bounded latency sample (the parent computes percentiles
        # over the union; full lists would be MBs at high request counts).
        step = max(1, len(latencies) // 2000)
        conn.send((wall, completed, latencies[::step]))

    asyncio.run(run())


class _ProcEchoReplica:
    """ReplicaHandle over an out-of-process EchoServer (fork + pipe
    port handshake) — the router's event loop never shares a core with
    the replicas it routes to."""

    def __init__(self, echo_tokens: int = 1):
        self._parent, child = mp.Pipe()
        self.proc = mp.Process(target=_echo_proc,
                               args=(child, echo_tokens), daemon=True)
        self.proc.start()

    async def start(self):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self._parent.recv)

    @property
    def alive(self) -> bool:
        return self.proc.is_alive()

    async def kill(self) -> None:
        if self.proc.is_alive():
            self.proc.terminate()

    async def terminate(self) -> None:
        await self.kill()


# -- measurement ------------------------------------------------------------
async def _measure(args, wire_name: str) -> dict:
    """One wire mode's ceiling: fresh router (policy per mode: the
    jsonl BEFORE router refuses the upgrade everywhere, recreating the
    pre-bin1 path exactly), fresh client processes, timed between the
    barrier release and the last client's completion."""
    from distkeras_tpu.serving.cluster.router import Router
    from distkeras_tpu.serving.cluster.supervisor import ReplicaSupervisor
    from distkeras_tpu.serving.metrics import percentile
    from distkeras_tpu.telemetry import MetricsRegistry

    roles = getattr(args, "_roles", None)  # parsed once in main()
    supervisor = ReplicaSupervisor(
        lambda i: _ProcEchoReplica(args.echo_tokens),
        args.replicas, health_interval_s=5.0, roles=roles)
    await supervisor.start()
    registry = MetricsRegistry() if roles else None
    router = Router(supervisor, port=0, registry=registry,
                    trace_capacity=512 if args.trace else 0,
                    wire_mode="jsonl" if wire_name == "jsonl" else "auto",
                    # Bench prompts are short; hand off anything with
                    # at least one emulated block.
                    min_handoff_tokens=4)
    await router.start()
    procs, conns = [], []
    n_procs = args.client_procs
    share = args.requests // n_procs
    barrier = mp.Barrier(n_procs + 1)
    try:
        for _ in range(n_procs):
            parent, child = mp.Pipe()
            p = mp.Process(
                target=_client_proc,
                args=(child, barrier, router.port, wire_name, share,
                      args.conns_per_proc, args.pipeline,
                      args.prompt_len),
                daemon=True)
            p.start()
            procs.append(p)
            conns.append(parent)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, barrier.wait, 120)
        t0 = time.monotonic()
        results = await asyncio.gather(*(
            loop.run_in_executor(None, c.recv) for c in conns))
        wall = time.monotonic() - t0
        completed = sum(r[1] for r in results)
        lats = [x for r in results for x in r[2]]
        sec = {
            "requests": completed,
            "wall_s": round(wall, 4),
            "requests_per_sec": round(completed / wall, 1),
            "client_procs": n_procs,
            "conns_per_proc": args.conns_per_proc,
        }
        if wire_name == "bin1":
            sec["pipeline"] = args.pipeline
        if lats:
            sec["latency_p50_s"] = round(percentile(lats, 50), 6)
            sec["latency_p99_s"] = round(percentile(lats, 99), 6)
        sec["backend_wire"] = {
            rid: info.wire_proto
            for rid, info in supervisor.replicas.items()}
        if roles:
            snap = registry.snapshot()
            sec["roles"] = {"prefill": roles.count("prefill"),
                            "decode": roles.count("decode")}
            sec["kv_handoffs"] = snap.get(
                "router_kv_handoffs_total", {}).get("value", 0)
            sec["kv_handoff_fallbacks"] = snap.get(
                "router_kv_handoff_fallbacks_total", {}).get("value", 0)
        return sec
    finally:
        for p in procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        await router.stop()
        await supervisor.stop()


# History rows: requests_per_sec and speedup_x regress by DROPPING
# (higher-is-better, the checker's default); latency_* rows by rising.
_ROW_METRICS = ("requests_per_sec", "latency_p50_s", "latency_p99_s")


def _record_history(args, report: dict) -> None:
    import time as _time

    import bench  # stdlib-only shared history helpers (repo root)

    path = os.path.join(_ROOT, "bench_history.json")
    hist = bench.load_history(path)
    when = _time.strftime("%Y-%m-%dT%H:%M:%SZ", _time.gmtime())
    base = (f"serving/router_echo/replicas{args.replicas}"
            f"/procs{args.client_procs}x{args.conns_per_proc}")
    for wire_name in ("jsonl", "bin1"):
        sec = report.get(wire_name)
        if not isinstance(sec, dict):
            continue
        tag = (f"{wire_name}/pipeline{args.pipeline}"
               if wire_name == "bin1" else wire_name)
        for metric in _ROW_METRICS:
            v = sec.get(metric)
            if isinstance(v, (int, float)) and v > 0:
                key = f"{base}/{tag}/{metric}"
                hist[key] = bench.history_entry(hist.get(key), float(v),
                                                when)
    speedup = report.get("speedup_x")
    if isinstance(speedup, (int, float)) and speedup > 0:
        key = f"{base}/pipeline{args.pipeline}/speedup_x"
        hist[key] = bench.history_entry(hist.get(key), float(speedup),
                                        when)
    bench.write_history(path, hist)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=20000,
                    help="generation requests per wire mode (split "
                         "across client processes)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="echo replica processes behind the router")
    ap.add_argument("--client-procs", type=int, default=4,
                    help="load-generator processes")
    ap.add_argument("--conns-per-proc", type=int, default=4,
                    help="connections per client process")
    ap.add_argument("--pipeline", type=int, default=64,
                    help="bin1: concurrent multiplexed streams per "
                         "connection (jsonl is pinned to 1 by its own "
                         "protocol)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="tokens per request prompt")
    ap.add_argument("--roles", default=None, metavar="prefill=N,decode=M",
                    help="disaggregated echo fleet: the router prefills "
                         "each prompt on an (emulated) prefill replica "
                         "and decode replicas run the REAL KVBLK pull "
                         "before echoing — measures the handoff path's "
                         "router cost jax-free (overrides --replicas; "
                         "disables the zero-task fast path by design)")
    ap.add_argument("--echo-tokens", type=int, default=1,
                    help="token events per echoed request")
    ap.add_argument("--wire", default="both",
                    choices=["jsonl", "bin1", "both"],
                    help="which front door(s) to measure")
    ap.add_argument("--trace", action="store_true",
                    help="keep the router's per-request timeline store ON "
                         "(measures the observability tax; default off "
                         "for the pure ceiling)")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="with --wire both: assert bin1 requests/s >= "
                         "this multiple of jsonl's (the acceptance run "
                         "uses 5)")
    ap.add_argument("--record-history", action="store_true",
                    help="append serving/router_* rows to "
                         "bench_history.json for the strict CI gate")
    args = ap.parse_args()
    args._roles = None
    if args.roles:
        from distkeras_tpu.serving.cluster import parse_roles

        try:
            args._roles = parse_roles(args.roles)
        except ValueError as e:
            raise SystemExit(f"--roles: {e}") from None
        args.replicas = len(args._roles)

    report: dict = {"config": {
        "requests": args.requests, "replicas": args.replicas,
        "client_procs": args.client_procs,
        "conns_per_proc": args.conns_per_proc,
        "pipeline": args.pipeline, "prompt_len": args.prompt_len,
        "echo_tokens": args.echo_tokens, "trace": bool(args.trace),
        "roles": args.roles,
    }}
    for wire_name in (("jsonl", "bin1") if args.wire == "both"
                      else (args.wire,)):
        report[wire_name] = asyncio.run(_measure(args, wire_name))
    if "jsonl" in report and "bin1" in report:
        report["speedup_x"] = round(
            report["bin1"]["requests_per_sec"]
            / report["jsonl"]["requests_per_sec"], 2)
    if args.record_history:
        _record_history(args, report)
    print(json.dumps(report, indent=1))
    if args.min_speedup > 0:
        speedup = report.get("speedup_x", 0.0)
        assert speedup >= args.min_speedup, (
            f"bin1 front door is only {speedup}x the jsonl ceiling "
            f"(required >= {args.min_speedup}x)")


if __name__ == "__main__":
    main()
