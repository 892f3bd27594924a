"""Work counters and readers for a decoder whose token mixers are of two
kinds, chosen layer by layer (``mixer_types``): ``minicpm4``, block-sparse
GQA in which a query past ``sparse_dense_len`` attends the ``sparse_topk``
blocks its own scores choose, and ``lightning-attn``, decayed linear
attention over a per-head ``[d, d]`` state; every MLP is a dense SwiGLU.
Like ``flops.py`` this counts what the RULE requires from the configuration
file's sizes and the traffic, never what a kernel loads:

- a sparse layer's query at position ``p`` attends ``p + 1`` keys while ``p
  + 1 <= dense_len``, else the keys of its ``topk`` blocks that are at or
  before ``p`` — ``(topk - 1) block + p % block + 1``, a count that follows
  from the position alone (the block holding ``p`` is always chosen);
- its selection scores ``q`` against every compressed key ``p`` can see
  (``(p - K + 1) // stride + 1`` of them), for the sparse queries only;
- the attention's bytes are the DISTINCT K and V a pass needs per KV group:
  at most the slot's history, and at most what its queries attend — so a
  kernel that reads a block once per query, and not once per pass, reads
  low;
- the lightning rule counts its recurrent form, ``5 d^2`` a head and token
  (decay, rank-1 update, readout) whatever form runs, and the one-step
  kernel's bytes are one read and one write of the state of the rows that
  ADVANCED in a pass.

Readers return None where they find nothing to read (a program without the
kernel or the counters): the harness then leaves the metric out of the
line.
"""

from __future__ import annotations

import numpy as np

from . import flops, serve
from .qwen3_next import _kernel_roofline
from .readers import _cache_served

ACT_BYTES = flops.ACT_BYTES
STATE_BYTES = 4                     # the lightning state is float32


def kinds(m):
    return list(m["mixer_types"])[:m["num_hidden_layers"]]


def n_sparse(m):
    return kinds(m).count("minicpm4")


def n_lightning(m):
    return kinds(m).count("lightning-attn")


def keys_attended(m, p):
    """Keys a sparse layer's query at position(s) ``p`` attends."""
    p = np.asarray(p, np.float64)
    b = m["sparse_block_size"]
    sparse = (m["sparse_topk"] - 1) * b + p % b + 1
    return np.where(p + 1 <= m["sparse_dense_len"], p + 1, sparse)


def entries_scored(m, p):
    """Compressed keys a SPARSE query at ``p`` is scored against (0 for a
    dense one)."""
    p = np.asarray(p, np.float64)
    K, s = m["sparse_kernel_size"], m["sparse_kernel_stride"]
    n = np.maximum(np.floor((p - K + 1) / s) + 1, 0)
    return np.where(p + 1 <= m["sparse_dense_len"], 0, n)


def sparse_matmul_params(m):
    """q, k, v, the output gate, o."""
    hid, h, g, d = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return hid * (2 * h * d + 2 * g * d) + h * d * hid


def lightning_matmul_params(m):
    """q, k, v, the output gate, o."""
    hid, hd = m["hidden_size"], m["lightning_nh"] * m["lightning_head_dim"]
    return 4 * hid * hd + hd * hid


def lightning_rule_flops_token(m):
    d = m["lightning_head_dim"]
    return 5.0 * m["lightning_nh"] * d * d


def layers_flops_token(m):
    """One token through every layer, the sparse layers' attention and
    selection apart."""
    mlp = 2.0 * 3 * m["hidden_size"] * m["intermediate_size"]
    return n_sparse(m) * 2.0 * sparse_matmul_params(m) \
        + n_lightning(m) * (2.0 * lightning_matmul_params(m)
                            + lightning_rule_flops_token(m)) \
        + m["num_hidden_layers"] * mlp


def attn_flops(m, start, n):
    """Queries at positions start .. start + n - 1 of one sequence, every
    sparse layer: the attention over the keys each attends, and the
    selection's scores."""
    p = np.arange(start, start + n)
    h, d = m["num_attention_heads"], m["head_dim"]
    return n_sparse(m) * (4.0 * h * d * float(np.sum(keys_attended(m, p)))
                          + 2.0 * h * d * float(np.sum(entries_scored(m, p))))


def serve_flops(m, prompt_spans, sampled_ctx):
    """Model FLOPs of a serving window (``flops.serve_flops``'s contract:
    spans of prompt tokens pushed through the layers; per sampled token the
    context its own pass attended, None for a first token)."""
    layer = layers_flops_token(m)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    total = 0.0
    for start, n in prompt_spans:
        total += n * layer + attn_flops(m, start, n)
    for ctx in sampled_ctx:
        total += head
        if ctx is not None:
            total += layer + attn_flops(m, ctx - 1, 1)
    return total


def sparse_attention_work(m, calls, kv_bytes):
    """FLOPs and bytes of the block-sparse attention for ``calls`` =
    (ctx_before, n_q) per slot and pass, on the sparse layers: per query
    ``4 H d`` FLOPs a key it attends; per pass and KV group the distinct K
    and V it needs — the smaller of the history (``ctx + n`` keys) and the
    sum of its queries' keys — and q read and the context written once."""
    h, g, d = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    fl = by = 0.0
    for ctx, n in calls:
        if n <= 0:
            continue
        keys = float(np.sum(keys_attended(m, np.arange(ctx, ctx + n))))
        fl += 4.0 * h * d * keys
        by += 2.0 * g * d * min(ctx + n, keys) * kv_bytes \
            + 2.0 * n * h * d * ACT_BYTES
    return fl * n_sparse(m), by * n_sparse(m)


def linear_state_step_work(m, rows):
    """FLOPs and bytes of the one-step lightning kernel for ``rows`` (slot,
    lightning layer) pairs that ADVANCED by one token: the rule's FLOPs;
    one read and one write of the row's float32 states, and its q, k, v in
    and o out."""
    hh, d = m["lightning_nh"], m["lightning_head_dim"]
    by = rows * hh * (2.0 * d * d + 4 * d) * STATE_BYTES
    return rows * lightning_rule_flops_token(m), by


# ---- readers -----------------------------------------------------------------

def read_serve_mfu(spec, ctx):
    """Model FLOPs of every prompt and output token the window processed,
    over the window's seconds and the chip's published peak."""
    win = ctx["win"]
    if _cache_served(win):
        return None
    spans, sampled, _ = serve.work_items(win, ctx["chunk"], win.t_start,
                                         win.t_end)
    secs = win.t_end - win.t_start
    if secs <= 0 or not sampled:
        return None
    f = serve_flops(ctx["cfg"]["sizes"], spans, sampled)
    return 100.0 * f / secs / (ctx["peaks"].flops * ctx["chips"])


def read_sparse_attn_roofline(spec, ctx):
    """The sparse layers' attention work of the traced turns, rebuilt from
    the traffic, over the kernel's summed device time."""
    def work(m, spans, sampled, calls, turns):
        return sparse_attention_work(m, calls, ctx["cfg"]["kv_bytes"]), {}

    return _kernel_roofline(spec, ctx, work)


def read_linear_state_step_roofline(spec, ctx):
    """The one-step lightning kernel's least time in the traced turns:
    every output token after a request's first is one row that advanced,
    in every lightning layer (a first token comes out of the prompt's last
    chunk, which the block form takes)."""
    def work(m, spans, sampled, calls, turns):
        rows = sum(1 for c in sampled if c is not None) * n_lightning(m)
        return linear_state_step_work(m, rows), {"rows": rows}

    return _kernel_roofline(spec, ctx, work)
