"""Driving ``ContinuousBatchingEngine`` the way ``ApiServer`` and fleet
replicas do — ``add_request`` + ``step()`` from one thread — under a closed
or an open loop, stamping every token when the host first sees it."""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from . import traffic as T


@dataclass
class Rec:
    """One request as the harness saw it."""
    rid: int
    due: float                      # when it was due (closed loop: submit)
    sent: float                     # when add_request returned
    prompt: np.ndarray
    max_new: int
    handle: object                  # the engine's ServedRequest
    client: int = -1
    seen: int = 0
    times: list = field(default_factory=list)   # host time of each token
    done: bool = False
    error: object = None


@dataclass
class Window:
    t_start: float = 0.0
    t_end: float = 0.0              # end of the last step / drain
    t_close: float = 0.0            # when arrivals stopped (open loop)
    recs: list = field(default_factory=list)
    turns: list = field(default_factory=list)   # (t0, t1) of each step()
    late: list = field(default_factory=list)    # generator lateness, s
    gauges: dict = None


def build_engine(cfg, model):
    from paddle_tpu.inference import ContinuousBatchingEngine
    return ContinuousBatchingEngine(model, **cfg["engine"])


class Pump:
    """step() with the harness's own stamps around it."""

    def __init__(self, eng, win, annotate):
        self.eng, self.win, self.annotate = eng, win, annotate
        self.live = {}

    def submit(self, prompt, max_new, due, client=-1):
        with self.annotate("bench/add_request"):
            rid = self.eng.add_request(prompt, max_new)
        rec = Rec(rid, due, time.perf_counter(), prompt, max_new,
                  self.eng.request(rid), client)
        self.live[rid] = rec
        self.win.recs.append(rec)
        return rec

    def step(self):
        t0 = time.perf_counter()
        with self.annotate("bench/engine.step"):
            done = self.eng.step()
        t1 = time.perf_counter()
        self.win.turns.append((t0, t1))
        with self.annotate("bench/stamp"):
            for rec in self.live.values():
                n = len(rec.handle.tokens)
                if n > rec.seen:
                    rec.times += [t1] * (n - rec.seen)
                    rec.seen = n
            out = []
            for r in done:
                rec = self.live.pop(r.request_id, None)
                if rec is None:
                    continue            # a warm-up request
                rec.done, rec.error = True, r.error
                out.append(rec)
        return out


def warm_up(eng, cfg, traffic, say):
    """One admission wave and its turns through the ONE step program: the
    first turn is the program's eager discovery, the second compiles (or
    fetches). Short prompts and outputs of their own, not the window's."""
    w = traffic["warmup"]
    rng = np.random.default_rng(12345)
    vocab = cfg["sizes"]["vocab_size"]
    t0 = time.perf_counter()
    for _ in range(int(w["requests"])):
        eng.add_request(rng.integers(0, vocab, int(w["prompt"])).astype(
            np.int32), int(w["output"]))
    turns = []
    while eng.has_work():
        t = time.perf_counter()
        done = eng.step()
        turns.append(round(time.perf_counter() - t, 2))
        for r in done:
            if r.error is not None:
                raise SystemExit(f"perfbench: warm-up request failed: "
                                 f"{r.error!r}")
    eng.reset_gauges()
    say("warmup", turns_s=turns, total_s=round(time.perf_counter() - t0, 1))


def run_window(eng, cfg, traffic, seed, seconds, tracer, annotate):
    """The measured window. Returns a Window."""
    win = Window()
    pump = Pump(eng, win, annotate)
    vocab = cfg["sizes"]["vocab_size"]
    stream = T.request_stream(traffic, vocab, seed)
    trace_s = float(traffic["trace_seconds"]) if tracer else 0.0

    win.t_start = t_start = time.perf_counter()
    t_end = t_start + seconds

    def maybe_trace(now):
        if tracer and not tracer.started and now >= t_end - trace_s:
            tracer.start()

    if traffic["kind"] == "closed":
        for c in range(int(traffic["clients"])):
            p, k = next(stream)
            pump.submit(p, k, time.perf_counter(), c)
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            maybe_trace(now)
            for rec in pump.step():
                p, k = next(stream)
                pump.submit(p, k, time.perf_counter(), rec.client)
        win.t_close = win.t_end = win.turns[-1][1]
    else:
        due = [t_start + a for a in T.arrival_times(traffic, seconds, seed)]
        i = 0
        while True:
            now = time.perf_counter()
            while i < len(due) and due[i] <= now:
                p, k = next(stream)
                pump.submit(p, k, due[i])
                win.late.append(time.perf_counter() - due[i])
                i += 1
            if now >= t_end and i >= len(due):
                break
            maybe_trace(now)
            if eng.has_work():
                pump.step()
            else:
                nxt = min(due[i] if i < len(due) else t_end, t_end)
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))
        win.t_close = time.perf_counter()
        if tracer and tracer.started:
            tracer.stop()
        # no new arrivals: pump until every request that was due has its
        # first token (a TTFT is a wait, however long), at most `grace`
        grace = win.t_close + float(traffic["drain_grace_s"])
        while eng.has_work() and time.perf_counter() < grace and any(
                not r.times and r.error is None for r in win.recs):
            pump.step()
        win.t_end = time.perf_counter()
    if tracer and tracer.started:
        tracer.stop()
    win.gauges = eng.gauges()
    return win


def clear_engine(eng, win):
    """Between two windows of one engine (the tools' sweeps; a run has one
    window): cancel what ``win`` left unfinished, pump it out, and forget
    cached prefixes and counts."""
    for r in win.recs:
        if not r.done:
            eng.cancel(r.rid)
    while eng.has_work():
        eng.step()
    eng.reset_prefix_cache()
    eng.reset_gauges()


# ---- what the window says ---------------------------------------------------

def counts(win):
    failed = sum(1 for r in win.recs if r.error is not None)
    return len(win.recs), failed


def tokens_in(win, t0, t1):
    return sum(1 for r in win.recs for t in r.times if t0 <= t <= t1)


def ttfts(win):
    """Seconds from due to first token, for ALL requests due in the window;
    one that failed or never got a token counts as the window's length."""
    span = win.t_close - win.t_start
    return [(r.times[0] - r.due) if r.times and r.error is None else span
            for r in win.recs]


def gaps(win):
    """Every gap between consecutive tokens of every request, seconds."""
    out = []
    for r in win.recs:
        out += [b - a for a, b in zip(r.times, r.times[1:])]
    return out


def queue_waits(win):
    return [r.handle.t_admit - r.due for r in win.recs if r.handle.t_admit]


def turn_of(win, t):
    """Index of the step() during which host time t fell (or -1)."""
    lo, hi = 0, len(win.turns) - 1
    while lo <= hi:
        mid = (lo + hi) // 2
        t0, t1 = win.turns[mid]
        if t < t0:
            hi = mid - 1
        elif t > t1:
            lo = mid + 1
        else:
            return mid
    return -1


def work_items(win, chunk, t0, t1):
    """What the engine's passes in [t0, t1] had to attend, rebuilt from what
    the harness saw: (prompt_spans, sampled_ctx, attention_calls).

    A request admitted in turn k streams prompt chunk j (``chunk`` tokens)
    in turn k + j; its first token rides the last chunk; output token i >= 2
    is one single-token pass over L + i - 2 cached tokens, in the turn where
    the host saw it. Holds while no prompt is served from the prefix cache
    (the gauges say if one was)."""
    spans, sampled, calls = [], [], []
    for r in win.recs:
        L = len(r.prompt)
        k = turn_of(win, r.handle.t_admit) if r.handle.t_admit else -1
        if k >= 0:
            for j in range(-(-L // chunk)):
                if k + j >= len(win.turns):
                    break
                a, b = win.turns[k + j]
                if t0 <= b <= t1:
                    n = min(chunk, L - j * chunk)
                    spans.append((j * chunk, n))
                    calls.append((j * chunk, n))
        for i, t in enumerate(r.times, start=1):
            if not (t0 <= t <= t1):
                continue
            if i == 1:
                sampled.append(None)
            else:
                sampled.append(L + i - 1)
                calls.append((L + i - 2, 1))
    return spans, sampled, calls


# ---- correct ----------------------------------------------------------------

def pick_sample(win, seed, k):
    """k finished requests: the longest, and the rest drawn from the seed."""
    fin = [r for r in win.recs if r.done and r.error is None
           and len(r.handle.tokens) == r.max_new]
    if not fin:
        return []
    fin.sort(key=lambda r: r.rid)
    longest = max(fin, key=lambda r: len(r.prompt) + r.max_new)
    rest = [r for r in fin if r is not longest]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 99])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def pack_sample(sample, traffic, k):
    """Fixed shapes whatever the seed drew: ids [k, T], pos/tok/mask
    [k, R] — T and R from the traffic file's clips, so every run of a cell
    compiles the same reference program."""
    p_max, o_max = int(traffic["prompt"]["max"]), int(traffic["output"]["max"])
    T_ = -(-(p_max + o_max) // 128) * 128
    ids = np.zeros((k, T_), np.int32)
    pos = np.zeros((k, o_max), np.int32)
    tok = np.zeros((k, o_max), np.int32)
    mask = np.zeros((k, o_max), bool)
    for i, r in enumerate(sample):
        toks = np.asarray(r.handle.tokens, np.int32)
        L, n = len(r.prompt), len(toks)
        ids[i, :L] = r.prompt
        ids[i, L:L + n] = toks
        pos[i, :n] = L - 1 + np.arange(n)
        tok[i, :n] = toks
        mask[i, :n] = True
    return ids, pos, tok, mask


def served_gaps(cfg, seed, ids, pos, tok, mm=None):
    """Run the plain reference once over prompts + served tokens: per row,
    (reference's best logit - reference's logit of ``tok``), the
    reference's argmax."""
    import jax
    import jax.numpy as jnp

    from . import spec, weights
    from .model import jnp_dtype
    ref = spec.reference_module(cfg)
    m = cfg["sizes"]
    specs = ref.param_specs(m)
    mm = mm or ref.mm_f32

    def fn(words, ids, pos, tok):
        src = weights.LeafSource(specs, words, cfg["init_std"],
                                 jnp_dtype(cfg["dtype"]), ref.LAYER_PATTERN)
        best, chosen, arg = ref.next_token_rows(m, src, ids, pos, tok, mm)
        return best - chosen, arg

    gap, arg = jax.jit(fn)(weights.seed_words(seed), jnp.asarray(ids),
                           jnp.asarray(pos), jnp.asarray(tok))
    return np.asarray(gap), np.asarray(arg)


def judge(cfg, seed, failed, ids, pos, tok, mask):
    """The numbers compared, each beside its limit, for tokens ``tok``
    served at positions ``pos`` of ``ids`` (``mask``: which are real); and
    the reference's own first choice at each. The window's tokens come
    here, and so do a control's: both are judged by this one function."""
    lim = cfg["check"]
    out = {"failed_requests": {"value": int(failed), "limit": 0}}
    n_tok = int(mask.sum())
    if not n_tok:
        out["served_gap_max"] = {"value": None,
                                 "limit": lim["served_gap_max"]}
        return False, out, None
    gap, arg = served_gaps(cfg, seed, ids, pos, tok)
    worst = float(np.max(gap[mask]))
    out["served_gap_max"] = {"value": worst, "limit": lim["served_gap_max"]}
    need = int(lim["min_tokens"])
    out["served_tokens_compared"] = {"value": n_tok, "limit_min": need}
    ok = (failed == 0 and np.isfinite(worst)
          and worst <= lim["served_gap_max"] and n_tok >= need)
    return bool(ok), out, arg


def check(cfg, traffic, seed, win, say):
    """A sample of the window's finished requests, judged."""
    k = int(cfg["check"]["sample_requests"])
    sample = pick_sample(win, seed, k)
    t0 = time.perf_counter()
    ids, pos, tok, mask = pack_sample(sample, traffic, k)
    ok, out, arg = judge(cfg, seed, counts(win)[1], ids, pos, tok, mask)
    if sample:
        say("check", sampled_requests=len(sample),
            served_tokens=int(mask.sum()),
            longest=len(sample[0].prompt) + sample[0].max_new,
            argmax_agreement=round(float(np.mean((arg == tok)[mask])), 4),
            reference_s=round(time.perf_counter() - t0, 1))
    return ok, out
