"""Speculative decoding draft sources + distribution-exact verification.

Engine-level speculative decoding (ISSUE 18) through the EXISTING ragged
mixed pass: a drafting decode slot stops riding its pending token as a
length-1 query and instead rides ``1 + K`` tokens — the pending token in
column 0 (same contract as the plain unified step) followed by ``K``
draft tokens — so verification is just a short "prefill-shaped" chunk
through ``ragged_paged_attention``. No new kernel; no new compiled
shapes beyond the ``[num_slots, prefill_chunk]`` ladder already tuned
(``K + 1 <= prefill_chunk`` is enforced at the engine ctor).

This module owns the two halves that are independent of the engine's
scheduler:

- **Draft sources** (the strategy seam): given the engine's host view of
  each drafting slot, propose up to K tokens per slot.

  * :class:`NGramDraftSource` — prompt-lookup: match the last ``n``
    known tokens of ``prompt + emitted`` against every earlier position
    of the same history and propose the continuation. Pure host work,
    zero extra device programs.
  * :class:`SelfSpecDraftSource` — self-speculation: re-run the SAME
    model with a configurable subset of layers skipped as its own cheap
    draft model (one compiled K-step greedy scan whose functionally
    updated KV pools are DISCARDED — draft state never touches the
    verified cache).

- **Rejection sampling** (:func:`rejection_sample`): the classic
  speculative-sampling acceptance rule specialized to point-mass drafts
  (both sources propose single tokens, i.e. a delta draft
  distribution): accept draft ``d_j`` with probability
  ``min(1, p_j[d_j])``; at the first rejection, resample from the
  residual ``p_j`` with ``d_j`` zeroed out and renormalized; if every
  draft is accepted, the bonus token samples from ``p_K``. Each emitted
  position is marginally EXACTLY the target distribution — greedy
  degenerates to exact-match acceptance, making spec-on streams
  token-identical to the plain engine.

Draft state is invisible to every replay path: preemption recompute
(ISSUE 10), fleet failover (ISSUE 11) and prefix-cache attach (ISSUE 12)
all reconstruct from ``prompt + emitted tokens``, and rejected draft KV
is rollback-safe by construction (attention masks reads at ``<= ctx``;
later writes overwrite the garbage in place — see
``ops/paged_attention.py``'s verify-write notes).
"""

from __future__ import annotations

import numpy as np

__all__ = ["DraftSource", "NGramDraftSource", "SelfSpecDraftSource",
           "get_draft_source", "ngram_propose", "rejection_sample"]


# ---------------------------------------------------------------------------
# host-side rejection sampler (the numeric contract; the engine runs the
# same rule vectorized inside the compiled spec step — tests pin both)
# ---------------------------------------------------------------------------

def rejection_sample(probs, drafts, rng, greedy=False):
    """Verify point-mass drafts against target distributions.

    probs:  [K+1, V] float — target next-token distribution at each
            chunk position (position j conditions on the pending token
            plus drafts ``d_1..d_j``).
    drafts: [K] int — proposed tokens (a delta draft distribution).
    rng:    np.random.Generator (ignored under greedy).

    Returns ``(emitted, n_accepted)``: the emitted token list (always
    at least one token — the chain never leaves a step empty) and how
    many drafts were accepted. ``emitted[j] == drafts[j]`` for
    ``j < n_accepted``; the final entry is the rejection resample (or
    the bonus sample when every draft was accepted).

    Marginal exactness (the speculative-sampling theorem for q = delta):
    P(emit t at position j) = P(accept d_j) * 1[t == d_j]
    + P(reject) * residual_j(t) = min(1, p_j[d_j]) * 1[t == d_j]
    + (1 - p_j[d_j])_+ * (p_j(t) * 1[t != d_j]) / (1 - p_j[d_j])
    = p_j(t).
    """
    probs = np.asarray(probs, np.float64)
    drafts = [int(d) for d in drafts]
    k = len(drafts)
    assert probs.shape[0] >= k + 1
    emitted = []
    for j, d in enumerate(drafts):
        p = probs[j]
        if greedy:
            accept = d == int(np.argmax(p))
        else:
            accept = rng.random() < min(1.0, float(p[d]))
        if accept:
            emitted.append(d)
            continue
        # first rejection: resample from the renormalized residual
        if greedy:
            t = int(np.argmax(p))
        else:
            resid = p.copy()
            resid[d] = 0.0
            tot = resid.sum()
            if tot <= 0.0:           # p was a delta AT d yet u>=1 lost:
                t = d                # numerically impossible; stay exact
            else:
                t = int(rng.choice(len(resid), p=resid / tot))
        emitted.append(t)
        return emitted, j
    # every draft accepted: bonus token from the target at position K
    p = probs[k]
    if greedy:
        t = int(np.argmax(p))
    else:
        t = int(rng.choice(len(p), p=p / p.sum()))
    emitted.append(t)
    return emitted, k


# ---------------------------------------------------------------------------
# draft sources
# ---------------------------------------------------------------------------

class DraftSource:
    """Strategy seam: propose up to ``k`` draft tokens per drafting
    slot. ``propose`` sees the ENGINE (host token history, device
    mirrors) and returns host arrays — the engine clamps the counts to
    each slot's remaining budget and feeds the survivors into the spec
    step. Sources must be stateless across steps w.r.t. correctness:
    replay paths (preemption, failover, prefix attach) never see draft
    state."""

    name = "base"

    def propose(self, eng, slots, k):
        """-> (drafts [num_slots, k] int32, counts [num_slots] int32).

        ``slots`` lists the drafting slot indices; rows of other slots
        are ignored. ``counts[slot] <= k``; a 0 count degrades that
        slot to a plain length-1 decode inside the same spec step."""
        raise NotImplementedError


def ngram_propose(hist, k, max_n=3, min_n=1):
    """Prompt-lookup n-gram proposal: match the trailing ``n``-gram of
    ``hist`` (``prompt + emitted``, host ints) against every EARLIER
    window of the same history, longest n first, most recent match
    wins; propose the ``k`` tokens that followed the match. Returns an
    int32 array of length ``<= k`` (possibly empty)."""
    hist = np.asarray(hist, np.int32).reshape(-1)
    ln = hist.shape[0]
    for n in range(min(max_n, ln - 1), max(min_n, 1) - 1, -1):
        suffix = hist[ln - n:]
        # candidate windows hist[j:j+n] for j <= ln-n-1 — strictly
        # earlier than the suffix occurrence itself
        win = np.lib.stride_tricks.sliding_window_view(hist[:-1], n)
        hits = np.nonzero((win == suffix[None, :]).all(axis=1))[0]
        if hits.size == 0:
            continue
        j = int(hits[-1])
        prop = hist[j + n:j + n + k]
        if prop.size:
            return prop.astype(np.int32)
    return np.zeros((0,), np.int32)


class NGramDraftSource(DraftSource):
    """Prompt-lookup drafts (zero device work): the generated stream
    often repeats spans of its own prompt/history (code, quotes,
    templated text), so the continuation of the most recent matching
    n-gram is a cheap high-acceptance draft there — and a wrong draft
    costs only the already-paid ragged pass columns."""

    name = "ngram"

    def __init__(self, max_n=3, min_n=1):
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def propose(self, eng, slots, k):
        b = eng.num_slots
        drafts = np.zeros((b, k), np.int32)
        counts = np.zeros((b,), np.int32)
        for slot in slots:
            req = eng.slot_req[slot]
            if req is None:
                continue
            hist = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.tokens, np.int32)])
            prop = ngram_propose(hist, k, self.max_n, self.min_n)
            counts[slot] = prop.shape[0]
            drafts[slot, :prop.shape[0]] = prop
        return drafts, counts


class SelfSpecDraftSource(DraftSource):
    """Self-speculative skip-layer drafts: ONE compiled greedy K-step
    scan over the SAME weights with ``skip_layers`` decoder layers
    passed through (LayerSkip-style early-exit draft, PAPERS.md). The
    scan carries functionally-updated KV pools so draft token ``j+1``
    attends draft token ``j``'s KV — and then the updated pools are
    DISCARDED: the device-resident verified pools are never touched by
    drafting, which is what makes rejected drafts free to roll back.

    ``skip_layers`` accepts explicit layer indices or the default
    "skip the top half" (the standard self-speculation split: early
    layers carry most of the next-token signal)."""

    name = "self"

    def __init__(self, skip_layers=None):
        self._skip = tuple(sorted(skip_layers)) \
            if skip_layers is not None else None
        self._fns = {}  # (engine id, k) -> (compiled scan, its upload)

    def _skip_for(self, model):
        if self._skip is not None:
            return self._skip
        n = int(model.config.num_hidden_layers)
        return tuple(range((n + 1) // 2, n))

    def _draft_fn(self, eng, k):
        key = (id(eng), int(k))
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ..framework.core import Tensor, no_grad, apply
        from ..jit import to_static
        from .serving import _apply_slot_resets, _TurnUpload
        model = eng.model
        skip = self._skip_for(model)
        # the draft program's ONE upload: the engine's staged slot state
        # (block tables, context resets) and which slots draft
        up = _TurnUpload(eng._slot_fields()
                         + [("mask", (eng.num_slots,))])

        def dstep(up_t, tok_t, ctx_t, *pools):
            fwd = model.forward

            def fn(upload, tok, ctx, *pool_leaves):
                b = tok.shape[0]
                f = up.split(upload)
                tbl = f["tbl"]
                # a slot the host rebound since the last launch drafts
                # nothing, and reads its staged context like the step
                ctx, mask = _apply_slot_resets(f["reset"], ctx,
                                               f["mask"] != 0)

                def body(carry, _):
                    tok_c, ctx_c, leaves = carry
                    with no_grad():
                        lgs, ncaches = fwd(
                            Tensor(tok_c.reshape(b, 1)),
                            caches=[Tensor(a) for a in leaves],
                            pos=Tensor(ctx_c[:, None]),
                            tables=(Tensor(tbl), Tensor(mask)),
                            skip_layers=skip)
                    lg = lgs[:, -1]._data.astype(jnp.float32)
                    nx = jnp.argmax(lg, -1).astype(jnp.int32)
                    nx = jnp.where(mask, nx, tok_c)
                    ctx_n = ctx_c + mask.astype(jnp.int32)
                    new_leaves = tuple(t._data for t in ncaches)
                    return (nx, ctx_n, new_leaves), nx

                carry0 = (tok, ctx, tuple(pool_leaves))
                _, toks = jax.lax.scan(body, carry0, jnp.arange(k))
                # [K, B] -> [B, K]; the carried pools die here — draft
                # KV is never returned to the engine
                return toks.T.astype(jnp.int32)

            return apply(fn, up_t, tok_t, ctx_t, *pools,
                         n_outputs=1, differentiable=False,
                         name="spec_draft")

        self._fns[key] = to_static(dstep), up
        eng._compiled.add(("spec_draft", int(k)))
        return self._fns[key]

    def propose(self, eng, slots, k):
        from ..framework.core import Tensor
        b = eng.num_slots
        counts = np.zeros((b,), np.int32)
        if not slots or k <= 0:
            return np.zeros((b, max(k, 1)), np.int32)[:, :k], counts
        fn, up = self._draft_fn(eng, k)
        buf, f = eng._stage_upload(up)
        f["mask"][list(slots)] = 1
        toks = fn(eng._ship(buf), Tensor(eng._dev_tok),
                  Tensor(eng._dev_ctx), *eng.pools)
        drafts = np.asarray(toks._data).astype(np.int32)
        counts[f["mask"] != 0] = k
        return drafts, counts


def get_draft_source(spec):
    """Resolve a draft-source spec: a DraftSource instance passes
    through; the strings ``"ngram"`` and ``"self"`` build the default
    instances. (The tuner's ``spec_decode`` surface stores the
    string form.)"""
    if isinstance(spec, DraftSource):
        return spec
    if spec == "ngram":
        return NGramDraftSource()
    if spec in ("self", "skip_layer", "self_spec"):
        return SelfSpecDraftSource()
    raise ValueError(f"unknown draft source {spec!r} "
                     "(want 'ngram', 'self', or a DraftSource)")
