"""Work counters and readers for training a decoder whose attention layers
are of two kinds (``layer_types``: ``sliding_attention`` over a window of
``sliding_window`` keys, ``full_attention`` over every earlier key) and
whose every MLP holds a SHARE of its routed experts (``num_experts`` of
``router_num_experts``), each a SwiGLU of three matrices, under a
``to_static`` AdamW step. Like ``flops.py`` this counts what the ALGORITHM
needs from the configuration file's sizes and the traffic's shapes: a
window query attends at most ``sliding_window`` keys whatever the kernel
walks, and the experts compute the MEASURED local pairs. For the step's
share of the peak recomputation is not work; a kernel's roofline counts
every forward its events show (a recomputed layer runs its forward twice),
so a step-level choice does not move a kernel's yardstick.

The expert counters are the program's own: the model sums its expert
layers' statistics on the device and ``paddle_tpu.ops.moe
.book_train_stats`` books them, once, on the process registry
(``moe/train_tokens``, ``moe/train_local_pairs``,
``moe/train_busiest_pairs``). They run from the first step, the warm-up's
included, so a reader takes their ratios. Readers return None where they
find nothing to read (a program without the counters, a trace without the
kernel): the harness then leaves the metric out of the line.
"""

from __future__ import annotations

from . import flops, xplane
from .readers import _kernel_seconds

ACT_BYTES = flops.ACT_BYTES
COUNTERS = ("moe/train_tokens", "moe/train_local_pairs",
            "moe/train_busiest_pairs")


def kinds(m):
    return list(m["layer_types"][:m["num_hidden_layers"]])


def expert_params(m):
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_matmul_params(m):
    """Weights every token meets, but the experts': attention and the
    router of every layer, and the head over the held slice."""
    hid, h, kvh, d = (m["hidden_size"], m["num_attention_heads"],
                      m["num_key_value_heads"], m["head_dim"])
    attn = hid * h * d + 2 * hid * kvh * d + h * d * hid
    return m["num_hidden_layers"] * (attn + hid * m["router_num_experts"]) \
        + hid * m["vocab_size"]


def expected_local_pairs(m):
    """Pairs per token and layer whose expert is held here, under uniform
    routing."""
    return m["num_experts_per_tok"] * m["num_experts"] \
        / m["router_num_experts"]


def keys_seen(s, window=None):
    """Keys attended by the s queries of one causal sequence, each at most
    ``window`` of them (its own among them)."""
    if window is None or window >= s:
        return s * (s + 1) / 2.0
    return window * (window + 1) / 2.0 + (s - window) * window


def attention_fwd_flops(m, batch, seq):
    """QK^T and PV of one step's forward, every layer by its kind."""
    per_key = 4.0 * m["num_attention_heads"] * m["head_dim"] * batch
    return per_key * sum(
        keys_seen(seq, m["sliding_window"] if k == "sliding_attention"
                  else None) for k in kinds(m))


def train_flops_per_token(m, seq, local_pairs):
    """Forward + backward model FLOPs per trained token: 6 x the weights it
    meets (the experts' at ``local_pairs`` per layer) + attention at 3 x its
    forward. Recomputation is not counted."""
    return 6.0 * (dense_matmul_params(m) + m["num_hidden_layers"]
                  * local_pairs * expert_params(m)) \
        + 3.0 * attention_fwd_flops(m, 1, seq) / seq


def flash_attention_work(m, batch, seq, forwards=1):
    """Flash attention of one step, ``forwards`` forwards + dq + dkv:
    FLOPs (forwards + 2) x the forward's (window layers over their window);
    bytes as ``flops.flash_attention_work`` counts them (a forward reads
    q, k, v and writes o; the backward reads q, k, v, o, do and writes dq,
    dk, dv)."""
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    q_b = batch * seq * h * d * ACT_BYTES
    kv_b = batch * seq * kvh * d * ACT_BYTES
    by = (2 * forwards + 4) * (q_b + kv_b) * m["num_hidden_layers"]
    return (forwards + 2.0) * attention_fwd_flops(m, batch, seq), by


def grouped_matmul_work(m, pairs, layer_passes, forwards=1):
    """FLOPs and bytes of the expert layers' grouped matmuls in training:
    the forward's three matmuls a pair, ``forwards`` times, then dx and dW
    as many again each. Bytes: every held expert's three matrices read by
    each forward and by dx and written by dW once per layer pass, and each
    pair's rows in and out of the three matmuls in each pass."""
    hid, inter = m["hidden_size"], m["moe_intermediate_size"]
    n = forwards + 2.0
    fl = n * 2.0 * expert_params(m) * pairs
    by = n * layer_passes * m["num_experts"] * expert_params(m) \
        * ACT_BYTES + n * pairs * 3.0 * (hid + inter) * ACT_BYTES
    return fl, by


# ---- the program's counters --------------------------------------------------

def train_counters():
    """{tokens, local_pairs, busiest_pairs} of the process's training
    steps, booked from the device-side sums; None for a program without
    them, or with no pass counted."""
    try:
        from paddle_tpu.ops import moe
        from paddle_tpu.profiler.metrics import get_registry
    except ImportError:
        return None
    book = getattr(moe, "book_train_stats", None)
    if book is None:
        return None
    book()
    reg, out = get_registry(), {}
    for key, name in zip(("tokens", "local_pairs", "busiest_pairs"),
                         COUNTERS):
        c = reg.get(name)
        if c is None:
            return None
        out[key] = float(sum(s.value for _, s in c._iter_series()))
    return out if out["tokens"] > 0 else None


def measured_pairs_per_token(c):
    return c["local_pairs"] / c["tokens"] if c else None


# ---- readers -------------------------------------------------------------------

def read_local_pairs_per_token(spec, ctx):
    """Local (token, expert) pairs per token-layer pass, from the
    program's counters: 8 x 16 / 64 = 2.0 under uniform routing."""
    return measured_pairs_per_token(train_counters())


def read_max_over_mean_load(spec, ctx):
    """The busiest held expert's pairs, summed over layer passes, x the
    held experts / the local pairs: 1.0 under a perfectly even load."""
    c = train_counters()
    if not c or not c["local_pairs"]:
        return None
    return c["busiest_pairs"] * ctx["cfg"]["sizes"]["num_experts"] \
        / c["local_pairs"]


def read_train_mfu(spec, ctx):
    """Model FLOPs of the window's trained tokens (the experts at the
    MEASURED local pairs per token) over the window's seconds and the
    chip's published peak."""
    run, tr, m = ctx["run"], ctx["traffic"], ctx["cfg"]["sizes"]
    secs = run.t_end - run.t_start
    per_pair = measured_pairs_per_token(train_counters())
    if secs <= 0 or not run.step_ends or per_pair is None:
        return None
    toks = len(run.step_ends) * tr["batch"] * tr["seq"]
    per = train_flops_per_token(m, tr["seq"], per_pair)
    return 100.0 * per * toks / secs / (ctx["peaks"].flops * ctx["chips"])


def _note(spec, ctx, **kv):
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = kv


def _events(ctx, pattern):
    """Events in the traced window whose name matches ``pattern``."""
    t0, t1 = ctx["reduced"]["window"]
    return sum(xplane.matching_seconds(xplane.clip(evs, t0, t1), pattern)[1]
               for evs in ctx["trace"].device_ops.values())


def forwards_run(ctx, fwd, bwd, extra=0):
    """Forwards the kernel ran per backward, from the traced events:
    (events matching ``fwd``) / (events matching ``bwd``) - ``extra``. A
    layer recomputed in the backward reads 2, one that is not 1."""
    n_bwd = _events(ctx, bwd)
    return _events(ctx, fwd) / n_bwd - extra if n_bwd else None


def read_flash_attn_roofline(spec, ctx):
    """max(FLOPs / peak, bytes / peak bandwidth) of the attention the
    traced steps ran, window layers over their window, every forward the
    events show (a recomputed layer's too), over the summed device time of
    the flash kernels' events."""
    secs, n = _kernel_seconds(spec, ctx)
    r = ctx.get("reduced")
    if not secs or not n or not r.get("steps"):
        return None
    fwd = forwards_run(ctx, r"^%(\w*_)?flash_attention_fwd",
                       r"^%(\w*_)?flash_attention_dq")
    if not fwd:
        return None
    tr = ctx["traffic"]
    f, b = flash_attention_work(ctx["cfg"]["sizes"], tr["batch"], tr["seq"],
                                fwd)
    f, b = f * r["steps"], b * r["steps"]
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    _note(spec, ctx, bound=bound, kernel_s=secs, events=n, flops=f, bytes=b,
          forwards=fwd)
    return 100.0 * least / secs


def read_grouped_matmul_roofline(spec, ctx):
    """max(FLOPs / peak, bytes / peak bandwidth) of the expert layers'
    forwards, dx and dW in the traced steps (pairs: the steps' tokens x
    layers x the MEASURED local pairs per token-layer pass; forwards: as
    many as the events show, each matmul's dx under the kernel's own name
    and its dW under ``grouped_matmul_dw``) over the summed device time of
    the grouped-matmul events, ``grouped_matmul_dw`` included."""
    secs, n = _kernel_seconds(spec, ctx)
    r = ctx.get("reduced")
    per_pair = measured_pairs_per_token(train_counters())
    if not secs or not n or not r.get("steps") or per_pair is None:
        return None
    fwd = forwards_run(ctx, r"^%(\w*_)?grouped_matmul(?!_dw)",
                       r"^%(\w*_)?grouped_matmul_dw", extra=1)
    if not fwd or fwd <= 0:
        return None
    m, tr = ctx["cfg"]["sizes"], ctx["traffic"]
    passes = r["steps"] * m["num_hidden_layers"]
    f, b = grouped_matmul_work(m, passes * tr["batch"] * tr["seq"]
                               * per_pair, passes, fwd)
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    _note(spec, ctx, bound=bound, kernel_s=secs, events=n, flops=f, bytes=b,
          pairs_per_token=per_pair, forwards=fwd)
    return 100.0 * least / secs
