"""A serving cell: the server is a child process started through the
program's ``serve`` entry; this process is only its clients, over TCP, on
``ServingClient`` — front door, wire, scheduler, engine and model are all
inside the window. This process initializes no JAX backend until the
server has exited; then it takes the chip for the plain reference.

Load is offered as the traffic file says and never searched for: ``closed``
keeps a fixed number of clients busy, ``open`` sends each request when it
is DUE (Poisson arrivals at the file's rate) and times it from then.
"""

from __future__ import annotations

import asyncio
import bisect
import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

from . import cell as cell_mod, host_probe, traffic as traffic_mod
from .program import memory_peak_bytes

DRAIN_S = 60.0  # an answer may come this long after the window closed


def split_cpus(cpus) -> tuple[set, set] | None:
    """(the load generator's, the server's) of the CPUs this process may
    run on: disjoint, the server the larger share (three quarters). None
    where there are too few to part."""
    cpus = sorted(cpus)
    if len(cpus) < 4:
        return None
    k = len(cpus) // 4
    return set(cpus[:k]), set(cpus[k:])


@contextlib.contextmanager
def own_cpus(cpus):
    """This process kept to ``cpus`` (all it has, where None), and back on
    the CPUs it was found on afterwards, also when the body raises."""
    found = os.sched_getaffinity(0)
    if cpus:
        os.sched_setaffinity(0, cpus)
    try:
        yield
    finally:
        os.sched_setaffinity(0, found)


class Record:
    """One request as its client saw it (host clock, seconds)."""

    __slots__ = ("req", "due", "sent", "token_times", "tokens", "error",
                 "done")

    def __init__(self, req):
        self.req = req
        self.due = self.sent = None
        self.token_times, self.tokens, self.error = [], [], None
        self.done = False  # the stream ended by itself (not cut at the close)


class Server:
    """The child and its two pipes."""

    def __init__(self, cell, seed: int, require_platform: str | None,
                 cpus=None):
        here = os.path.dirname(os.path.abspath(__file__))
        # started from ``cpus``: the child and every thread it starts
        # inherit the set, with no moment at which it runs elsewhere
        with own_cpus(cpus):
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(here, "serve_child.py"),
                 cell.config_path, str(seed), require_platform or "-",
                 cell.root],
                cwd=cell.root, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True)
        self.banner = None

    def read_json(self, key: str, timeout_s: float) -> dict:
        """Next stdout line that is a JSON object holding ``key``."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                raise SystemExit(f"chipbench: the server ended (rc="
                                 f"{self.proc.wait()}) before {key!r}")
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and key in rec:
                return rec
        raise SystemExit(f"chipbench: no {key!r} from the server in "
                         f"{timeout_s:.0f}s")

    def control(self, command: str, timeout_s: float = 120.0) -> dict:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        return self.read_json("chipbench", timeout_s)["chipbench"]

    def stop(self) -> dict:
        """SIGTERM, the drained summary line, and the exit code."""
        self.proc.send_signal(signal.SIGTERM)
        tail = self.proc.stdout.read()
        rc = self.proc.wait(timeout=120)
        summary = {}
        for line in reversed(tail.strip().splitlines()):
            try:
                summary = json.loads(line)
                break
            except ValueError:
                continue
        return {"rc": rc, "summary": summary}

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


async def _stream(host, port, rec: Record) -> None:
    """One request over its own connection; stamps every token."""
    from distkeras_tpu.serving.client import ServingClient, ServingError

    rec.sent = time.perf_counter()
    try:
        async with ServingClient(host, port, max_retries=0) as client:
            async for tok in client.stream(list(rec.req.prompt),
                                           rec.req.max_new_tokens):
                rec.token_times.append(time.perf_counter())
                rec.tokens.append(int(tok))
        rec.done = True
    except (ServingError, OSError, asyncio.TimeoutError) as e:
        rec.error = f"{type(e).__name__}: {e}"


async def _counters(host, port, since_wall: float | None = None) -> dict:
    """The program's own counters, over its control verbs."""
    from distkeras_tpu.serving.client import ServingClient

    out = {}
    async with ServingClient(host, port) as c:
        metrics = await c.metricsz()
        out["metricsz"] = {
            k: v["value"] for k, v in metrics.items()
            if isinstance(v, dict) and isinstance(v.get("value"), (int, float))}
        if since_wall is not None:
            q = await c.queryz(
                where=[f"t_done>={since_wall:.3f}"],
                aggs=["count", "p95:queue_wait_s", "sum:prefix_hit_tokens",
                      "sum:prompt_tokens", "sum:output_tokens"])
            groups = q.get("groups") or [{}]
            out["queryz"] = {
                k: (v.get("value") if isinstance(v, dict) else v)
                for k, v in (groups[0].get("aggs") or {}).items()}
    return out


async def _drive(cell, server: Server, schedule, seed: int, seconds: float,
                 trace_dir: str | None, marks: dict) -> list:
    tr = cell.traffic
    host, port = server.banner["host"], server.banner["port"]
    vocab = cell.config["model"]["config"]["vocab_size"]
    loop = asyncio.get_running_loop()

    # Set-up: compile every prefill shape the traffic reaches, one prompt
    # after another, and with them admit and decode.
    for prompt in traffic_mod.warmup_prompts(
            tr, vocab, seed, cell.config["min_prefill_bucket"]):
        warm = Record(traffic_mod.Request(-1, prompt, 4, None, 0))
        await _stream(host, port, warm)
        if warm.error or len(warm.tokens) != 4:
            raise SystemExit(f"chipbench: warm-up request failed: {warm.error}")

    records = [Record(r) for r in schedule]
    tasks = []
    closing = asyncio.Event()

    if tr["kind"] == "closed":
        pending = iter(records)

        async def client():
            while not closing.is_set():
                rec = next(pending, None)
                if rec is None:
                    raise SystemExit("chipbench: the request pool ran dry "
                                     "before the window closed")
                await _stream(host, port, rec)

        # Set-up: every client sends its first request, and the window
        # opens once each has its SECOND token: all slots decoding. (The
        # engine admits the whole first wave, one prefill a tick, before it
        # decodes at all: a first token is up to 14 s ahead of its second.)
        n = int(tr["clients"])
        tasks = [asyncio.create_task(client()) for _ in range(n)]
        deadline = time.perf_counter() + 300.0
        while sum(1 for r in records[:n]
                  if len(r.token_times) >= 2 or r.error or r.done) < n:
            if time.perf_counter() > deadline:
                raise SystemExit("chipbench: the clients' first requests were "
                                 "not all decoding after 300 s")
            await asyncio.sleep(0.05)

    if tr["kind"] == "open":
        # Set-up: arrivals at the cell's own rate for ramp_s, so that the
        # window opens on slots and a prefix cache already in steady state.
        start = time.perf_counter()

        async def fire(rec):
            rec.due = start + rec.req.due_s
            await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
            await _stream(host, port, rec)

        tasks = [asyncio.create_task(fire(rec)) for rec in records]
        await asyncio.sleep(tr["ramp_s"])

    before = await _counters(host, port)
    with host_probe.GcWatch() as marks["collections"]:
        marks["t0"] = t0 = time.perf_counter()
        marks["wall0"] = time.time()

        async def heartbeat(period_s: float = 0.02):
            """How late this process's own loop woke in the window, at
            worst and each time it was over 10 ms: a starved load generator
            must not read as a stalled server."""
            marks["loadgen_stall_s"], marks["loadgen_stalls"] = 0.0, []
            while not closing.is_set():
                asked = time.perf_counter()
                await asyncio.sleep(period_s)
                late = time.perf_counter() - asked - period_s
                marks["loadgen_stall_s"] = max(marks["loadgen_stall_s"], late)
                if late > 0.01:  # (seconds into the window, seconds late)
                    marks["loadgen_stalls"].append((asked - t0, late))

        beat = asyncio.create_task(heartbeat())

        if trace_dir:
            await asyncio.sleep(tr["trace_offset_s"])
            await loop.run_in_executor(
                None, server.control, f"trace_start {trace_dir}")
            marks["trace_t0"] = time.perf_counter()
            await asyncio.sleep(tr["trace_seconds"])
            marks["traced_s"] = (await loop.run_in_executor(
                None, server.control, "trace_stop"))["traced_s"]

        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        marks["t_end"] = time.perf_counter()
    closing.set()
    await beat
    at_close = await _counters(host, port)
    marks["counters_read_s"] = time.perf_counter() - marks["t_end"]
    t_end = marks["t_end"]
    if tr["kind"] == "open":
        # Wait for every answer that is due: late is late, not wrong.
        await asyncio.wait(tasks, timeout=DRAIN_S)
    else:
        # A closed loop is cut at the close, once the token that closes the
        # window has come (window_metrics); only a request sent inside the
        # window that has no first token yet is waited for longer.
        deadline = t_end + DRAIN_S
        while time.perf_counter() < deadline and (any(
                r.sent is not None and r.sent < t_end and not r.token_times
                and not r.error and not r.done for r in records) or not any(
                r.token_times and r.token_times[-1] >= t_end for r in records)):
            await asyncio.sleep(0.02)
    for t in tasks:
        t.cancel()
    for t, result in zip(tasks, await asyncio.gather(
            *tasks, return_exceptions=True)):
        if isinstance(result, BaseException) and not isinstance(
                result, asyncio.CancelledError):
            raise result
    after = await _counters(host, port, since_wall=marks["wall0"])
    marks["before"], marks["after"], marks["at_close"] = before, after, at_close
    return records


def window_metrics(records: list, t0: float, t_end: float):
    """The window's end-to-end numbers from what the clients saw.

    The window's edges are token arrivals: it opens at the first token
    that arrives at or after ``t0`` and closes at the first that arrives at
    or after ``t_end``, neither counted. An engine hands out a token to
    every slot at once, each tick: with edges at fixed times, whether a
    whole tick's tokens fall inside or outside turns on a few milliseconds
    (one tick of 64 is 0.7 % of a 30 s window at a 218 ms tick).

    The requests of the window are those sent inside it; each is timed
    from when it was DUE (open loop) or sent (closed loop) to its first
    token, and one that failed or never answered misses by the whole
    drain. A request cut at the close, or still streaming when the drain
    ends, is late and not failed; only an error, no first token, or a
    stream that ended by itself with another count of tokens is. Gaps and
    tokens count where they fall inside the window, whichever request they
    belong to. Returns ``(mine, failed, e2e, spans, (t0, t_end))`` with the
    edges as they were taken."""
    arrivals = sorted(t for r in records for t in r.token_times)
    snapped = [arrivals[i] if i < len(arrivals) else t for t, i in
               ((t, bisect.bisect_left(arrivals, t)) for t in (t0, t_end))]
    if snapped[0] < snapped[1]:
        t0, t_end = snapped
    mine = [r for r in records if r.sent is not None and t0 <= r.sent < t_end]
    failed = [r for r in mine if r.error or not r.token_times
              or (r.done and len(r.tokens) != r.req.max_new_tokens)]
    ttft = [(r.token_times[0] - (r.due if r.due is not None else r.sent))
            if r.token_times else DRAIN_S for r in mine]
    gaps = [b - a for r in records
            for a, b in zip(r.token_times, r.token_times[1:]) if t0 < b < t_end]
    events = [(t, len(r.req.prompt) + i) for r in records
              for i, t in enumerate(r.token_times) if t0 < t < t_end]
    e2e = {"serve_tokens_per_s": len(events) / (t_end - t0)}
    if ttft:
        e2e["ttft_p95_ms"] = 1e3 * traffic_mod.percentile(ttft, 95)
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * traffic_mod.percentile(gaps, 95)
        e2e["itl_p99_ms"] = 1e3 * traffic_mod.percentile(gaps, 99)
    spans = {
        "ttft_s": ttft, "token_gap_s": gaps,
        "loadgen_lag_s": [r.sent - r.due for r in mine if r.due is not None],
        # (arrival time, keys its query saw) of every token of the window
        "token_events": events,
    }
    return mine, failed, e2e, spans, (t0, t_end)


def run(cell, seed: int, seconds: float, trace_dir: str | None,
        t_process_start: float, require_platform: str | None = "tpu",
        control: bool = False) -> dict:
    """One run of a serving cell. Returns the pieces of the result line.
    ``control=True`` (chipbench/limits.py) also reads the control."""
    tr, config = cell.traffic, cell.config
    sizes = config["model"]["config"]
    schedule = traffic_mod.request_schedule(
        tr, sizes["vocab_size"], seed, seconds + tr.get("ramp_s", 0.0))
    # The generator and the server on disjoint CPUs, parted from whatever
    # this machine gives the process (PERF.md section 6, PR 28: runs whose
    # server is ~5 % slow from start to end are rarer so, not gone).
    parted = split_cpus(os.sched_getaffinity(0))
    server = Server(cell, seed, require_platform, parted and parted[1])
    marks: dict = {"cpus": parted and [sorted(part) for part in parted]}
    try:
        server.banner = server.read_json("serving", 1100.0)
        device = {"platform": server.banner["platform"],
                  "kind": server.banner["device_kind"],
                  "count": server.banner["device_count"]}
        with own_cpus(parted and parted[0]):
            records = asyncio.run(_drive(cell, server, schedule, seed,
                                         seconds, trace_dir, marks))
        memory = server.control("memstats")["memory_stats"]
        stopped = server.stop()
    finally:
        server.kill()
    mine, failed, e2e, spans, (t0, t_end) = window_metrics(
        records, marks["t0"], marks["t_end"])
    window_s = t_end - t0
    streamed = [r for r in records
                if r.token_times and r.token_times[-1] >= t0 and not r.error]
    numbers, reference_s = check_served(cell, seed, streamed, control)
    audit = {k: {"calls": v.get("calls"), "compiles": v.get("compiles")}
             for k, v in stopped["summary"].get("recompile_audit", {}).items()}
    before, after = marks["before"]["metricsz"], marks["after"]["metricsz"]
    delta = {k: after[k] - before.get(k, 0) for k in after}
    at_close = marks["at_close"]["metricsz"]
    ticks = sum(v - before.get(k, 0) for k, v in at_close.items()
                if "decode_iterations_total" in k)
    gaps = sorted(spans["token_gap_s"])
    print(f"chipbench: window {window_s:.3f} s, {len(spans['token_events'])} "
          f"tokens, {ticks:.0f} server ticks, {len(mine)} requests sent; "
          f"gaps over 1 s: {sum(g > 1.0 for g in gaps)} of {len(gaps)}, longest "
          f"{1e3 * gaps[-1] if gaps else 0:.0f} ms; load generator woke "
          f"{1e3 * marks['loadgen_stall_s']:.1f} ms late at worst",
          file=sys.stderr, flush=True)
    return {
        "attempted": len(mine), "failed": len(failed), "device": device,
        "numbers": numbers, "controls": numbers.pop("controls", {}),
        "window_s": window_s,
        "split": split_by_side(records, marks, e2e, spans, window_s, ticks),
        "setup_s": t0 - t_process_start, "e2e": e2e,
        "memory_peak_bytes": max(memory_peak_bytes(m) for m in memory),
        "traced_s": marks.get("traced_s"),
        "counters": {
            "requests": len(mine), "tokens_in_window": len(spans["token_events"]),
            "server_ticks_in_window": ticks,
            "metricsz_delta": delta, "queryz": marks["after"].get("queryz", {}),
            "recompile_audit": audit, "kv_pool": stopped["summary"].get("kv_pool"),
            "server_rc": stopped["rc"], "reference_s": reference_s,
            # prompt lengths of the requests whose first token came in the
            # window: the prefills the window paid for
            "first_token_prompts": [
                len(r.req.prompt) for r in records
                if r.token_times and t0 <= r.token_times[0] < t_end],
        },
        # host clock (shared by both processes) when the trace began
        "spans": {**spans, "trace_t0": marks.get("trace_t0"),
                  "loadgen_stall_s": marks["loadgen_stall_s"]},
    }


def split_by_side(records, marks, e2e, spans, window_s, ticks):
    """One run's numbers by where they were taken (chipbench/spread.py
    reads them): the clients' count against the server's own over the same
    window, each half of the window reduced like the whole, the gaps
    between tokens by percentile (a run whose ticks are all slow against
    one with a few long stalls), and each time the generator's loop woke
    over 10 ms late with the tokens stamped in the 30 ms after: a backlog
    that the server built meanwhile (several ticks' worth: the generator
    alone was held) or no more than the usual (the server was held too)."""
    t0, t_end = marks["t0"], marks["t_end"]
    mid = 0.5 * (t0 + t_end)
    halves = [window_metrics(records, a, b)[2] for a, b in
              ((t0, mid), (mid, t_end))]
    server_window_s = t_end - t0 + marks["counters_read_s"]
    before = marks["before"]["metricsz"]
    slot_ticks = sum(v - before.get(k, 0)
                     for k, v in marks["at_close"]["metricsz"].items()
                     if "slot_ticks_total" in k)
    arrivals = sorted(t for t, _ in spans["token_events"])
    gaps = spans["token_gap_s"]

    def burst(at_s, late_s, span_s=0.03):
        end = t0 + at_s + 0.02 + late_s  # when the late timer fired
        return bisect.bisect_left(arrivals, end + span_s) - \
            bisect.bisect_left(arrivals, end)

    return {
        "clients": {"window_s": window_s, **e2e},
        "halves": halves,
        "server": {"window_s": server_window_s, "ticks": ticks,
                   "slot_ticks": slot_ticks,
                   "ticks_per_s": ticks / server_window_s,
                   "slot_ticks_per_s": slot_ticks / server_window_s},
        "gaps_ms": {f"p{q}": 1e3 * traffic_mod.percentile(gaps, q)
                    for q in (10, 50, 75, 90, 99)} if gaps else {},
        "loadgen": {"stall_max_ms": 1e3 * marks["loadgen_stall_s"],
                    "stalls": [[round(at, 3), round(1e3 * late, 1),
                                burst(at, late)]
                               for at, late in marks["loadgen_stalls"][:16]],
                    "cpus": marks["cpus"],
                    **marks["collections"].summary()},
    }


def check_served(cell, seed: int, finished: list, control: bool = False):
    """``correct`` for a served model: a sample, drawn from the seed, of
    the requests that streamed tokens in the window (finished, or cut at
    its close), with the longest in it; the reference
    runs once over each prompt with its served tokens, and the number
    compared is the widest gap by which a served (greedy) token's reference
    logit lies below the reference's best."""
    from distkeras_tpu.utils.platform import use_compile_cache

    use_compile_cache()  # the server has exited: the chip is free
    reference = cell_mod.ModelCode(cell.config, cell.root).reference

    t = time.perf_counter()
    tr = cell.traffic
    sizes = cell.config["model"]["config"]
    if not finished:
        return {}, 0.0
    rng = np.random.default_rng([seed % traffic_mod.SEED_MOD, 5])
    longest = max(finished, key=lambda r: len(r.req.prompt) + len(r.tokens))
    others = [r for r in finished if r is not longest]
    picks = [longest] + [others[i] for i in rng.permutation(len(others))[
        :int(tr["check_requests"]) - 1]]
    weights = reference.make_weights(sizes, seed)
    widest, compared = 0.0, 0
    controls = {"fp8": 0.0, "bf16": 0.0}
    for r in picks:
        logits = reference.position_logits(
            weights, sizes, r.req.prompt, r.tokens, "f32",
            sizes["max_seq_len"])
        gaps = reference.gaps_below_best(logits, r.tokens)
        widest = max(widest, float(gaps.max()))
        compared += len(gaps)
        for kind in controls if control else ():
            # the control need not decode: at each position of the same
            # prompt and tokens, the gap of the token IT puts first
            first = reference.position_logits(
                weights, sizes, r.req.prompt, r.tokens, kind,
                sizes["max_seq_len"]).argmax(-1)
            controls[kind] = max(controls[kind], float(
                reference.gaps_below_best(logits, first).max()))
    numbers = {"served_logit_gap": widest, "tokens_compared": compared}
    if control:
        numbers["controls"] = controls
    return numbers, time.perf_counter() - t
