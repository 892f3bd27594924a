#!/usr/bin/env python
"""Trace-shaped async load harness for the API front door (ISSUE 15).

Drives an ``ApiServer`` (inference/api_server.py) over real sockets
with the arrival shapes production traces actually have, and reports
what the CLIENT measured — the numbers the server cannot see:

- **closed loop** (``--mode closed``): ``--concurrency`` workers, each
  issuing its next request the moment the previous one finishes — the
  classic saturation probe;
- **open loop** (``--mode open``): arrivals on a Poisson process at
  ``--rate`` req/s with periodic BURSTS (``--burst-every`` /
  ``--burst-size``) layered on top — the trace shape that exposes
  queueing behavior closed loops hide;
- **shared-prefix mix**: a fraction of requests share one long prompt
  prefix (exercises the radix-tree prefix cache across the wire);
- **tenant/priority mix**: weighted tenants + priorities mapped onto
  the ``X-Tenant``/``X-Priority`` headers (per-tenant SLO accounting);
- **failure injection**: a configurable fraction of streams disconnect
  mid-stream after the first token (the cancel/reclaim path) and/or
  time out client-side;
- **JSON report**: goodput, client-measured p50/p99 TTFT and
  inter-token latency, delivered tok/s, bytes, and an error classes
  (HTTP status x typed SSE error), written to ``--report`` and echoed
  on stdout.

Stdlib-only (asyncio sockets + json) — the harness must not need more
than the server it drives. bench.py's ``cb_http`` section imports
:func:`run_load` directly; the CLI wraps the same entry point::

    python tools/load_harness.py --url http://127.0.0.1:8000 \
        --requests 128 --concurrency 64 --mode open --rate 200 \
        --prefix-frac 0.5 --report /tmp/http_load.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import random
import sys
import time
import zlib


# ---- one request over a raw socket ---------------------------------------

async def _read_headers(reader):
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("empty response")
    parts = status_line.decode("latin-1").split(None, 2)
    status = int(parts[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        headers[name.strip().lower()] = value.strip()
    return status, headers


async def _read_body(reader, headers):
    n = int(headers.get("content-length", "0") or "0")
    if n:
        return await reader.readexactly(n)
    return await reader.read()


async def do_request(host, port, payload, headers=None, stream=False,
                     disconnect_after_tokens=None, timeout_s=120.0):
    """One ``POST /v1/completions`` over a fresh connection. Returns a
    result dict: ok, status, text, finish_reason, error (class
    key), ttft_s, itl samples, bytes, trace_id."""
    t_send = time.perf_counter()
    res = {"ok": False, "status": 0, "text": "", "finish_reason": None,
           "error": None, "ttft_s": None, "itls_s": [], "bytes": 0,
           "trace_id": None}
    body = json.dumps(payload).encode("utf-8")
    head = ["POST /v1/completions HTTP/1.1",
            f"Host: {host}:{port}",
            "Content-Type: application/json",
            f"Content-Length: {len(body)}",
            "Connection: close"]
    for k, v in (headers or {}).items():
        head.append(f"{k}: {v}")
    raw = ("\r\n".join(head) + "\r\n\r\n").encode("latin-1") + body

    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout_s)
    except (OSError, asyncio.TimeoutError):
        res["error"] = "connect_error"
        return res
    try:
        writer.write(raw)
        await writer.drain()
        status, rheaders = await asyncio.wait_for(
            _read_headers(reader), timeout_s)
        res["status"] = status
        res["trace_id"] = rheaders.get("x-trace-id")
        if not stream or status != 200:
            data = await asyncio.wait_for(_read_body(reader, rheaders),
                                          timeout_s)
            res["bytes"] = len(data)
            doc = json.loads(data.decode("utf-8")) if data else {}
            if status == 200:
                choice = (doc.get("choices") or [{}])[0]
                res["text"] = choice.get("text", "")
                res["finish_reason"] = choice.get("finish_reason")
                res["ttft_s"] = time.perf_counter() - t_send
                res["ok"] = True
            else:
                err = doc.get("error") or {}
                res["error"] = f"http_{status}:" \
                               f"{err.get('type', 'unknown')}"
            return res
        # SSE: read data: lines, measure TTFT on the first chunk with
        # content, ITL between subsequent content chunks
        n_tokens_seen = 0
        last_t = None
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                res["error"] = res["error"] or "truncated_stream"
                return res
            res["bytes"] += len(line)
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                res["ok"] = res["error"] is None
                return res
            doc = json.loads(data.decode("utf-8"))
            if "error" in doc:
                err = doc["error"]
                res["error"] = f"sse:{err.get('type', 'unknown')}"
            choice = (doc.get("choices") or [{}])[0]
            delta = choice.get("text")
            if delta is None:
                delta = (choice.get("delta") or {}).get("content", "")
            if choice.get("finish_reason"):
                res["finish_reason"] = choice["finish_reason"]
            if delta:
                now = time.perf_counter()
                if res["ttft_s"] is None:
                    res["ttft_s"] = now - t_send
                elif last_t is not None:
                    res["itls_s"].append(now - last_t)
                last_t = now
                res["text"] += delta
                n_tokens_seen += len(delta.split())
                if disconnect_after_tokens is not None \
                        and n_tokens_seen >= disconnect_after_tokens:
                    res["error"] = "injected_disconnect"
                    return res
    except asyncio.TimeoutError:
        res["error"] = "client_timeout"
        return res
    except (ConnectionError, OSError, asyncio.IncompleteReadError,
            ValueError) as exc:
        res["error"] = f"transport:{type(exc).__name__}"
        return res
    finally:
        try:
            writer.close()
        except Exception:  # noqa: BLE001
            pass


# ---- workload synthesis --------------------------------------------------

#: named, seeded trace mixes — ONE workload definition shared by the
#: disagg A/B bench (bench.py cb-disagg), chaos suites and any future
#: scenario harness: every consumer of (name, n, vocab, seed) gets the
#: SAME request sequence. ``long_prompt_flood`` is the ROADMAP-item-1
#: shape: a minority of long prompts with real decode budgets flooding
#: in between short chat turns — the mix where colocated replicas
#: stall short-chat TTFT behind long prefills and disaggregation pays.
TRACE_MIXES = {
    "long_prompt_flood": dict(
        long_frac=0.35,
        long_prompt_len=(24, 40), long_max_new=(16, 24),
        short_prompt_len=(3, 8), short_max_new=(2, 6)),
    # the ISSUE-18 small-batch interactive shape: short chat prompts
    # with LONG generations at low concurrency — decode-bound, one
    # compiled program per token on the plain engine, so this is the
    # mix where speculative decoding pays (bench.py cb-spec goodput
    # leg drives it at concurrency 1-2)
    "short_chat_batch1": dict(
        long_frac=0.75,
        long_prompt_len=(4, 10), long_max_new=(24, 40),
        short_prompt_len=(3, 6), short_max_new=(12, 20)),
    # the ISSUE-20 capacity shape: EVERY request carries a real prompt
    # and decode budget, so page demand (not arrival cadence) is the
    # binding constraint — the mix where the int8-KV engine's ~2x page
    # budget at equal pool bytes shows up as peak concurrent slots
    # (bench.py cb-quant drives it on both A/B legs)
    "capacity_probe": dict(
        long_frac=1.0,
        long_prompt_len=(10, 14), long_max_new=(12, 20),
        short_prompt_len=(3, 8), short_max_new=(2, 6)),
}


def build_trace_mix(name, n_requests, *, vocab, seed=0):
    """A named mix as engine-level items: ``{"kind": "long"|"short",
    "prompt": [token ids], "max_new": int}``. Deterministic in
    (name, n_requests, vocab, seed)."""
    params = TRACE_MIXES[name]
    rng = random.Random(seed)
    out = []
    for _ in range(n_requests):
        kind = "long" if rng.random() < params["long_frac"] \
            else "short"
        plen = rng.randint(*params[f"{kind}_prompt_len"])
        out.append({
            "kind": kind,
            "prompt": [rng.randrange(vocab) for _ in range(plen)],
            "max_new": rng.randint(*params[f"{kind}_max_new"])})
    return out


def trace_mix_workload(mix, *, stream=True, tenants=("default",),
                       priorities=(0,)):
    """The HTTP form of a named mix — (payload, headers, disconnect)
    tuples for :func:`run_load`."""
    out = []
    for i, item in enumerate(mix):
        payload = {"prompt": list(item["prompt"]),
                   "max_tokens": int(item["max_new"]),
                   "stream": bool(stream)}
        headers = {"X-Tenant": tenants[i % len(tenants)],
                   "X-Priority": str(priorities[i % len(priorities)])}
        out.append((payload, headers, None))
    return out


def build_workload(n_requests, *, vocab, seed=0, prompt_len=(4, 12),
                   max_new=(2, 8), prefix_frac=0.0, prefix_len=8,
                   tenants=("default",), priorities=(0,),
                   disconnect_frac=0.0, stream=True,
                   ttft_deadline_ms=None, deadline_ms=None):
    """The request mix: each item is (payload, headers,
    disconnect_after_tokens). Prompts are integer-token-id lists in
    [0, vocab); a ``prefix_frac`` share of them open with one SHARED
    prefix of ``prefix_len`` tokens (the prefix-cache storm shape)."""
    rng = random.Random(seed)
    shared = [rng.randrange(vocab) for _ in range(prefix_len)]
    out = []
    for i in range(n_requests):
        plen = rng.randint(*prompt_len)
        if prefix_frac > 0 and rng.random() < prefix_frac:
            prompt = shared + [rng.randrange(vocab)
                               for _ in range(max(1, plen))]
        else:
            prompt = [rng.randrange(vocab) for _ in range(plen)]
        payload = {"prompt": prompt,
                   "max_tokens": rng.randint(*max_new),
                   "stream": bool(stream)}
        if ttft_deadline_ms is not None:
            payload["ttft_deadline_ms"] = ttft_deadline_ms
        if deadline_ms is not None:
            payload["deadline_ms"] = deadline_ms
        headers = {"X-Tenant": tenants[i % len(tenants)],
                   "X-Priority": str(priorities[i % len(priorities)])}
        disconnect = None
        if disconnect_frac > 0 and rng.random() < disconnect_frac:
            disconnect = 1     # hang up after the first token lands
        out.append((payload, headers, disconnect))
    return out


def arrival_times(n, *, mode="closed", rate=50.0, burst_every=0.0,
                  burst_size=0, seed=0):
    """Open-loop arrival offsets (seconds from start): Poisson at
    ``rate`` with ``burst_size`` extra simultaneous arrivals every
    ``burst_every`` seconds. Closed loop returns None (workers pace
    themselves)."""
    if mode == "closed":
        return None
    rng = random.Random(seed + 1)
    times, t, burst_t = [], 0.0, burst_every
    while len(times) < n:
        t += rng.expovariate(rate)
        if burst_every > 0 and t >= burst_t:
            for _ in range(burst_size):
                if len(times) < n:
                    times.append(burst_t)
            burst_t += burst_every
            continue
        times.append(t)
    return sorted(times[:n])


# ---- production scenario suite (ISSUE 19) ---------------------------------
#
# Named, seeded, gate-runnable scenarios for the fleet + autoscaler
# control loop. A scenario is a TICK-INDEXED arrival schedule (who
# submits what, when) plus the SLO rules it must be judged by and the
# attainment bar it must clear — the acceptance criteria live WITH the
# workload, not in the test that happens to run it. Everything here is
# deterministic in (name, vocab, seed) and stdlib-only; the runner is
# duck-typed over the fleet/autoscaler surfaces (submit/step/has_work,
# tick/actions) so this module still imports without the package.

SCENARIOS = {
    # a day of traffic in ~40 ticks: load swells to a peak and falls
    # back — the autoscaler should ride the curve (grow into the
    # swell, drain after it) instead of provisioning for the peak
    "diurnal": dict(
        describe="sinusoidal load curve peak->trough; capacity "
                 "should follow it",
        ticks=40, shape="diurnal", base=2, amp=2, period=32,
        prompt_len=(3, 8), max_new=(2, 5),
        tenants=("web", "api"),
        slo_rules=[dict(name="ttft", kind="ttft", threshold_ms=2000.0,
                        target=0.7, window_s=120.0, min_events=5)],
        attainment_bar=0.70),
    # one tenant goes hot while the background stays flat — burn-rate
    # pressure concentrated in a single label
    "tenant_hotspot": dict(
        describe="tenant 'hot' ramps 5x over a flat background",
        ticks=36, shape="hotspot", base=1, hot=4, window=(8, 24),
        prompt_len=(3, 8), max_new=(2, 5),
        tenants=("web",), hot_tenant="hot",
        slo_rules=[dict(name="ttft", kind="ttft", threshold_ms=2000.0,
                        target=0.7, window_s=120.0, min_events=5)],
        attainment_bar=0.70),
    # a flash crowd piles onto ONE shared prefix: queue depth spikes
    # fast, and prefix-affinity routing concentrates it — the gate
    # asserts a scale-up fires within a handful of ticks of onset
    "flash_crowd": dict(
        describe="6x crowd on one shared prefix for 10 ticks, quiet "
                 "before and after",
        ticks=40, shape="flash", base=1, crowd=6, window=(8, 18),
        prefix_len=8, prompt_len=(3, 6), max_new=(2, 5),
        tenants=("web",), crowd_tenant="crowd",
        slo_rules=[dict(name="ttft", kind="ttft", threshold_ms=3000.0,
                        target=0.7, window_s=120.0, min_events=5)],
        attainment_bar=0.70),
    # adversarial long-prompt flood between short chats — the mix that
    # starves short-chat TTFT and, on a disagg fleet, pressures the
    # prefill role specifically
    "long_prompt_flood": dict(
        describe="long prompts with real decode budgets flooding "
                 "between short chats",
        ticks=36, shape="flood", base=2, floods=2, window=(6, 26),
        long_prompt_len=(24, 40), long_max_new=(8, 12),
        prompt_len=(3, 6), max_new=(2, 4),
        tenants=("web",), flood_tenant="bulk",
        slo_rules=[dict(name="ttft", kind="ttft", threshold_ms=4000.0,
                        target=0.6, window_s=120.0, min_events=5)],
        attainment_bar=0.60),
    # a rolling upgrade drains replicas out from under steady load —
    # the operator acts, the autoscaler restores capacity
    "rolling_upgrade": dict(
        describe="operator drains a replica at ticks 10 and 22 under "
                 "steady load; the controller backfills",
        ticks=40, shape="steady", base=2,
        prompt_len=(3, 8), max_new=(2, 5),
        tenants=("web", "api"),
        events={10: "drain_oldest", 22: "drain_oldest"},
        slo_rules=[dict(name="ttft", kind="ttft", threshold_ms=3000.0,
                        target=0.6, window_s=120.0, min_events=5)],
        attainment_bar=0.60),
}


def _scenario_rng(name, seed):
    # crc32, not hash(): hash() is salt-randomized per process and
    # would silently unseed every scenario
    return random.Random(zlib.crc32(name.encode("utf-8")) ^ seed)


def build_scenario(name, *, vocab, seed=0):
    """The tick-indexed schedule for a named scenario: a list (one
    entry per tick) of arrival lists, each arrival ``{"prompt":
    [ids], "max_new": n, "tenant": t}``. Deterministic in
    (name, vocab, seed)."""
    sc = SCENARIOS[name]
    rng = _scenario_rng(name, seed)

    def req(plen_key="prompt_len", new_key="max_new", tenant=None,
            prefix=None):
        plen = rng.randint(*sc[plen_key])
        prompt = list(prefix or []) + [rng.randrange(vocab)
                                       for _ in range(plen)]
        return {"prompt": prompt, "max_new": rng.randint(*sc[new_key]),
                "tenant": tenant}

    shared = [rng.randrange(vocab) for _ in range(sc.get("prefix_len",
                                                         0))]
    schedule = []
    for t in range(sc["ticks"]):
        tick = []
        shape = sc["shape"]
        if shape == "diurnal":
            n = max(0, round(sc["base"] + sc["amp"]
                             * math.sin(2 * math.pi * t
                                        / sc["period"])))
            for i in range(n):
                tick.append(req(tenant=sc["tenants"][i
                                                     % len(sc["tenants"])]))
        elif shape == "hotspot":
            for _ in range(sc["base"]):
                tick.append(req(tenant=sc["tenants"][0]))
            lo, hi = sc["window"]
            if lo <= t < hi:
                for _ in range(sc["hot"]):
                    tick.append(req(tenant=sc["hot_tenant"]))
        elif shape == "flash":
            for _ in range(sc["base"]):
                tick.append(req(tenant=sc["tenants"][0]))
            lo, hi = sc["window"]
            if lo <= t < hi:
                for _ in range(sc["crowd"]):
                    tick.append(req(tenant=sc["crowd_tenant"],
                                    prefix=shared))
        elif shape == "flood":
            for _ in range(sc["base"]):
                tick.append(req(tenant=sc["tenants"][0]))
            lo, hi = sc["window"]
            if lo <= t < hi:
                for _ in range(sc["floods"]):
                    tick.append(req("long_prompt_len", "long_max_new",
                                    tenant=sc["flood_tenant"]))
        elif shape == "steady":
            for i in range(sc["base"]):
                tick.append(req(tenant=sc["tenants"][i
                                                     % len(sc["tenants"])]))
        else:
            raise ValueError(f"unknown scenario shape {shape!r}")
        schedule.append(tick)
    return schedule


def run_fleet_scenario(fleet, schedule, *, autoscaler=None,
                       clock=None, events=None, steps_per_tick=4,
                       drain_tick_limit=400, shed_exc=None):
    """Drive one scenario through a fleet: per tick, submit the
    tick's arrivals (a shed — ``shed_exc``, typically ``Overloaded``
    — is counted, never retried: goodput pays for it), run
    ``steps_per_tick`` fleet turns, fire the scenario's operator
    event if one lands on this tick, then give the autoscaler its
    control-loop tick (and advance the injected ``clock``, when the
    caller paces hysteresis on virtual time). After the schedule the
    loop keeps ticking — load off, controller still on — until all
    work and drains complete, which is where the scale-down half of
    the story happens. Returns the scenario report."""
    events = events or {}
    all_done = []
    submitted = shed = 0
    peak_ready = min_ready = sum(
        1 for r in fleet.replicas.values() if r.takes_weight())
    t0 = time.perf_counter()

    def one_tick(arrivals, tick_no):
        nonlocal submitted, shed, peak_ready, min_ready
        for item in arrivals:
            submitted += 1
            try:
                fleet.submit(item["prompt"], item["max_new"],
                             tenant=item.get("tenant"))
            except Exception as exc:  # noqa: BLE001 — only the typed
                if shed_exc is not None and isinstance(exc, shed_exc):
                    shed += 1         # overload is countable, anything
                else:                 # else is a real failure
                    raise
        ev = events.get(tick_no)
        if ev == "drain_oldest":
            ready = [r for r in fleet.replicas.values()
                     if r.state == "ready"]
            if ready:
                fleet.scale_down(
                    replica_id=min(ready, key=lambda r: r.id).id)
        elif ev is not None:
            raise ValueError(f"unknown scenario event {ev!r}")
        for _ in range(steps_per_tick):
            all_done.extend(fleet.step())
        if autoscaler is not None:
            autoscaler.tick()
        if clock is not None:
            clock.advance()
        ready = sum(1 for r in fleet.replicas.values()
                    if r.takes_weight())
        peak_ready = max(peak_ready, ready)
        min_ready = min(min_ready, ready)

    for tick_no, arrivals in enumerate(schedule):
        one_tick(arrivals, tick_no)
    # the cool-down tail: drains must complete and the controller must
    # get enough quiet ticks to give capacity back
    tick_no = len(schedule)
    while tick_no < len(schedule) + drain_tick_limit:
        draining = any(r.state == "draining"
                       for r in fleet.replicas.values())
        if not fleet.has_work() and not draining:
            break
        one_tick([], tick_no)
        tick_no += 1

    ok = [r for r in all_done if r.error is None]
    ttfts = sorted((r.t_first - r.t_arrive) * 1e3 for r in ok
                   if r.t_first and r.t_arrive)
    report = {
        "submitted": submitted,
        "accepted": submitted - shed,
        "shed": shed,
        "completed_ok": len(ok),
        "failed": len(all_done) - len(ok),
        "goodput_frac": round(len(ok) / max(1, submitted), 4),
        "ttft_ms_p50": round(_pct(ttfts, 0.50), 2),
        "ttft_ms_p99": round(_pct(ttfts, 0.99), 2),
        "ticks": tick_no,
        "wall_s": round(time.perf_counter() - t0, 3),
        "peak_ready": peak_ready,
        "min_ready": min_ready,
    }
    slo = getattr(fleet, "slo", None)
    if slo is not None:
        report["slo"] = slo.summary()
    if autoscaler is not None:
        report["decisions"] = list(autoscaler.decisions)
        report["actions"] = autoscaler.actions()
        report["chip_seconds"] = round(autoscaler.chip_seconds, 4)
    return report


class TickClock:
    """A virtual clock for deterministic hysteresis: the scenario
    runner advances it one ``dt`` per tick, and an autoscaler built
    with ``now_fn=clock`` paces its cooldowns on TICKS instead of
    host wall time (a loaded CI box cannot flake the quiet-period
    assertions)."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = float(dt)

    def __call__(self):
        return self.t

    def advance(self):
        self.t += self.dt


# ---- the driver ----------------------------------------------------------

async def _run_async(host, port, workload, *, mode="closed",
                     concurrency=8, arrivals=None, timeout_s=120.0):
    results = [None] * len(workload)
    t0 = time.perf_counter()

    async def one(i):
        payload, headers, disconnect = workload[i]
        results[i] = await do_request(
            host, port, payload, headers,
            stream=bool(payload.get("stream")),
            disconnect_after_tokens=disconnect, timeout_s=timeout_s)

    if mode == "closed":
        queue = list(range(len(workload)))

        async def worker():
            while queue:
                await one(queue.pop(0))
        await asyncio.gather(*[worker() for _ in range(concurrency)])
    else:
        async def timed(i):
            delay = arrivals[i] - (time.perf_counter() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            await one(i)
        await asyncio.gather(*[timed(i) for i in range(len(workload))])
    wall = time.perf_counter() - t0
    return results, wall


def _pct(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def summarize(results, wall_s):
    """The JSON report: goodput + client-measured latency + error
    classification. ``goodput_frac`` counts streams that completed clean
    over streams that were supposed to (injected disconnects are the
    CLIENT's fault and excluded from the denominator)."""
    ok = [r for r in results if r and r["ok"]]
    injected = [r for r in results
                if r and r["error"] == "injected_disconnect"]
    failed = [r for r in results if r and not r["ok"]
              and r["error"] != "injected_disconnect"]
    err_classes = {}
    for r in failed:
        key = r["error"] or f"http_{r['status']}"
        err_classes[key] = err_classes.get(key, 0) + 1
    ttfts = [r["ttft_s"] * 1e3 for r in ok if r["ttft_s"] is not None]
    itls = [v * 1e3 for r in ok for v in r["itls_s"]]
    toks = sum(len(r["text"].split()) for r in ok)
    denom = max(1, len(results) - len(injected))
    return {
        "requests": len(results),
        "completed_ok": len(ok),
        "injected_disconnects": len(injected),
        "failed": len(failed),
        "goodput_frac": round(len(ok) / denom, 4),
        "tok_s": round(toks / max(wall_s, 1e-9), 2),
        "tokens_delivered": toks,
        "wall_s": round(wall_s, 3),
        "ttft_ms_p50": round(_pct(ttfts, 0.50), 2),
        "ttft_ms_p99": round(_pct(ttfts, 0.99), 2),
        "itl_ms_p50": round(_pct(itls, 0.50), 3),
        "itl_ms_p99": round(_pct(itls, 0.99), 3),
        "bytes": sum(r["bytes"] for r in results if r),
        "errors": err_classes,
    }


def run_load(url, workload, *, mode="closed", concurrency=8,
             rate=50.0, burst_every=0.0, burst_size=0, seed=0,
             timeout_s=120.0):
    """Synchronous entry point (bench.py + tests): drive ``workload``
    against ``url`` and return (report, results)."""
    host, _, rest = url.partition("://")[2].partition(":")
    port = int(rest.split("/", 1)[0])
    arrivals = arrival_times(len(workload), mode=mode, rate=rate,
                             burst_every=burst_every,
                             burst_size=burst_size, seed=seed)
    results, wall = asyncio.run(_run_async(
        host, port, workload, mode=mode, concurrency=concurrency,
        arrivals=arrivals, timeout_s=timeout_s))
    return summarize(results, wall), results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="trace-shaped load generator for the paddle_tpu "
                    "API front door")
    ap.add_argument("--url", required=True,
                    help="server base url, e.g. http://127.0.0.1:8000")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=16,
                    help="closed-loop worker count")
    ap.add_argument("--mode", choices=("closed", "open"),
                    default="closed")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--burst-every", type=float, default=0.0,
                    help="seconds between arrival bursts (open loop)")
    ap.add_argument("--burst-size", type=int, default=0,
                    help="extra simultaneous arrivals per burst")
    ap.add_argument("--vocab", type=int, default=1000,
                    help="token ids drawn from [0, vocab)")
    ap.add_argument("--prompt-len", type=int, nargs=2,
                    default=(4, 12), metavar=("LO", "HI"))
    ap.add_argument("--max-new", type=int, nargs=2, default=(2, 8),
                    metavar=("LO", "HI"))
    ap.add_argument("--prefix-frac", type=float, default=0.0,
                    help="fraction of requests sharing one prefix")
    ap.add_argument("--prefix-len", type=int, default=8)
    ap.add_argument("--tenants", default="default",
                    help="comma-separated tenant mix")
    ap.add_argument("--priorities", default="0",
                    help="comma-separated priority mix")
    ap.add_argument("--disconnect-frac", type=float, default=0.0,
                    help="fraction of streams hung up after the first "
                         "token (exercises cancel/reclaim)")
    ap.add_argument("--ttft-deadline-ms", type=float, default=None)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--trace-mix", default=None,
                    choices=sorted(TRACE_MIXES),
                    help="use a named trace mix instead of the "
                         "--prompt-len/--max-new knobs (same "
                         "deterministic sequence every consumer of "
                         "(mix, requests, vocab, seed) gets)")
    ap.add_argument("--no-stream", action="store_true",
                    help="non-streaming JSON instead of SSE")
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default=None,
                    help="write the JSON report here")
    args = ap.parse_args(argv)

    if args.trace_mix:
        mix = build_trace_mix(args.trace_mix, args.requests,
                              vocab=args.vocab, seed=args.seed)
        workload = trace_mix_workload(
            mix, stream=not args.no_stream,
            tenants=tuple(args.tenants.split(",")),
            priorities=tuple(int(p)
                             for p in args.priorities.split(",")))
    else:
        workload = build_workload(
            args.requests, vocab=args.vocab, seed=args.seed,
            prompt_len=tuple(args.prompt_len),
            max_new=tuple(args.max_new), prefix_frac=args.prefix_frac,
            prefix_len=args.prefix_len,
            tenants=tuple(args.tenants.split(",")),
            priorities=tuple(int(p) for p in args.priorities.split(",")),
            disconnect_frac=args.disconnect_frac,
            stream=not args.no_stream,
            ttft_deadline_ms=args.ttft_deadline_ms,
            deadline_ms=args.deadline_ms)
    report, _ = run_load(
        args.url, workload, mode=args.mode,
        concurrency=args.concurrency, rate=args.rate,
        burst_every=args.burst_every, burst_size=args.burst_size,
        seed=args.seed, timeout_s=args.timeout_s)
    doc = json.dumps(report, indent=2, sort_keys=True)
    print(doc)
    if args.report:
        with open(args.report, "w", encoding="utf-8") as f:
            f.write(doc + "\n")
    return 0 if report["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
