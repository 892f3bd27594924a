"""Autoregressive text generation (``model.generate``).

Reference role: PaddleNLP ``generation_utils.py`` ``GenerationMixin``
(greedy_search / sampling decode strategies over a ``cache_kv`` decoder
cache; reference mount empty, no cites — see SURVEY.md provenance note).

TPU-native design: decoding runs as ONE compiled XLA step per token —
model forward over a **static-shape KV cache** (`sdpa_with_cache`,
``lax.dynamic_update_slice`` writes), plus logits processing (repetition
penalty, temperature, top-k, top-p) and categorical sampling with an
explicit threaded PRNG key, all inside a single ``to_static`` program.
The host loop only carries the python step counter and the early-exit
check; shapes never change during decode, so the step compiles exactly
once (prefill compiles once per prompt length).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, apply

__all__ = ["GenerationConfig", "GenerationMixin"]


@dataclass
class GenerationConfig:
    max_new_tokens: int = 64
    decode_strategy: str = "sampling"  # "greedy_search" | "sampling"
    temperature: float = 1.0
    top_k: int = 0                     # 0 = disabled
    top_p: float = 1.0                 # 1.0 = disabled
    repetition_penalty: float = 1.0
    eos_token_id: int | None = None
    pad_token_id: int | None = None
    use_cache: bool = True
    seed: int | None = None


def _process_and_sample(logits, key, buf, write_pos, finished, *,
                        temperature, top_k, top_p, rep, greedy,
                        eos_id, pad_id):
    """Pure-jnp logits pipeline -> next token. Runs inside the compiled
    decode step. logits: [B, V] (last position), buf: [B, L] tokens so far,
    write_pos: int32 scalar (where the new token goes), finished: [B] bool.
    """
    b, vocab = logits.shape
    lg = logits.astype(jnp.float32)
    if rep != 1.0:
        # penalize every token id already present in buf[:, :write_pos]
        valid = jnp.arange(buf.shape[1])[None, :] < write_pos       # [B?, L]
        seen = jnp.zeros((b, vocab), jnp.float32).at[
            jnp.arange(b)[:, None], buf].add(valid.astype(jnp.float32))
        pen = jnp.where(lg > 0, lg / rep, lg * rep)
        lg = jnp.where(seen > 0, pen, lg)
    if temperature != 1.0 and not greedy:
        lg = lg / temperature
    if top_k and top_k > 0 and not greedy:
        kth = jax.lax.top_k(lg, min(top_k, vocab))[0][:, -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p < 1.0 and not greedy:
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set of tokens with cumulative prob >= top_p; the shifted
        # comparison keeps the first token crossing the threshold
        cutoff_mask = cum - probs > top_p
        cutoff = jnp.where(cutoff_mask, jnp.inf, sorted_lg).min(
            axis=-1, keepdims=True)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    if greedy:
        tok = jnp.argmax(lg, axis=-1).astype(buf.dtype)
        new_key = key
    else:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, lg).astype(buf.dtype)
        new_key = key
    logprob = jax.nn.log_softmax(lg, axis=-1)[jnp.arange(b), tok]
    if eos_id >= 0:
        tok = jnp.where(finished, jnp.asarray(pad_id, buf.dtype), tok)
        logprob = jnp.where(finished, 0.0, logprob)
        new_finished = finished | (tok == eos_id)
    else:
        new_finished = finished
    buf = jax.lax.dynamic_update_slice(
        buf, tok[:, None], (jnp.zeros((), jnp.int32),
                            write_pos.astype(jnp.int32)))
    return tok, logprob, new_key, buf, new_finished


class GenerationMixin:
    """Adds ``generate`` to a causal-LM Layer.

    The model must implement
      - ``init_kv_cache(batch_size, max_length)`` -> list[Tensor] and
      - ``forward(input_ids, caches=..., pos=...)`` -> (logits, new_caches).
    """

    generation_config: GenerationConfig | None = None

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        raise NotImplementedError

    # -- the compiled step ---------------------------------------------------

    def _gen_step_static(self):
        cached = self.__dict__.get("_generate_step_fn")
        if cached is None:
            from ..jit import to_static
            from ..framework.core import no_grad

            def step(tok, pos, key_t, buf, finished, caches, temperature,
                     top_k, top_p, rep, greedy, eos_id, pad_id):
                with no_grad():
                    logits, caches = self.forward(tok, caches=caches, pos=pos)
                last = logits[:, -1]

                def fn(lg, p, k, bf, fin):
                    s = tok.shape[1]
                    return _process_and_sample(
                        lg, k, bf, p.astype(jnp.int32) + s, fin,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        rep=rep, greedy=greedy, eos_id=eos_id, pad_id=pad_id)
                nxt, lp, nk, nbuf, nfin = apply(
                    fn, last, pos, key_t, buf, finished, n_outputs=5,
                    name="gen_select", differentiable=False)
                return nxt, lp, nk, nbuf, nfin, caches

            cached = to_static(step)
            self.__dict__["_generate_step_fn"] = cached
        return cached

    def _gen_fused_static(self):
        """Whole-generation compiled path: prefill + a ``lax.scan`` over
        every decode step in ONE program. Used when no eos early-exit is
        requested (the scan has a static trip count). This is the
        TPU-native serving shape — a device-side decode loop instead of
        one host dispatch per token (each of which pays scheduling
        latency)."""
        cached = self.__dict__.get("_generate_fused_fn")
        if cached is None:
            from ..jit import to_static
            from ..framework.core import no_grad

            def run(ids32, key_t, buf, caches, temperature, top_k, top_p,
                    rep, greedy, pad_id, n_new):
                # temperature/top_k/top_p/rep/greedy/pad_id/n_new are
                # python scalars: part of the to_static signature key
                prompt_len = ids32.shape[1]
                with no_grad():
                    logits, caches = self.forward(
                        ids32, caches=caches,
                        pos=Tensor(jnp.zeros((), jnp.int32)))
                last = logits[:, -1]
                fwd = self.forward

                def fn(lg, key, bufa, *cache_leaves):
                    b = lg.shape[0]
                    fin = jnp.zeros((b,), bool)
                    tok, lp, key, bufa, _ = _process_and_sample(
                        lg, key, bufa,
                        jnp.asarray(prompt_len, jnp.int32), fin,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        rep=rep, greedy=greedy, eos_id=-1, pad_id=pad_id)

                    def body(carry, i):
                        tok_c, key_c, buf_c, cl, acc = carry
                        with no_grad():
                            lg2, nc = fwd(
                                Tensor(tok_c.reshape(b, 1)),
                                caches=[Tensor(a) for a in cl],
                                pos=Tensor((prompt_len + i)
                                           .astype(jnp.int32)))
                        t2, lp2, key2, buf2, _ = _process_and_sample(
                            lg2[:, -1]._data, key_c, buf_c,
                            (jnp.asarray(prompt_len + 1, jnp.int32)
                             + i.astype(jnp.int32)), fin,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, rep=rep, greedy=greedy,
                            eos_id=-1, pad_id=pad_id)
                        new_cl = [t._data for t in nc]
                        return (t2, key2, buf2, new_cl,
                                acc + lp2.astype(jnp.float32)), None

                    carry0 = (tok, key, bufa, list(cache_leaves),
                              lp.astype(jnp.float32))
                    carry, _ = jax.lax.scan(body, carry0,
                                            jnp.arange(n_new - 1))
                    _, key_f, buf_f, _, lp_f = carry
                    return buf_f, lp_f, key_f

                outs = apply(fn, last, key_t, buf, *caches, n_outputs=3,
                             name="fused_decode", differentiable=False)
                return outs

            cached = to_static(run)
            self.__dict__["_generate_fused_fn"] = cached
        return cached

    # -- public API ----------------------------------------------------------

    def generate(self, input_ids, generation_config=None, max_new_tokens=None,
                 max_length=None, decode_strategy=None, temperature=None,
                 top_k=None, top_p=None, repetition_penalty=None,
                 eos_token_id=None, pad_token_id=None, use_cache=None,
                 seed=None, **kwargs):
        """Generate token ids. Returns ``(generated_ids, scores)`` where
        ``generated_ids`` is [B, new_len] (prompt excluded, PaddleNLP
        convention) and ``scores`` the mean logprob of each sequence."""
        cfg = generation_config or self.generation_config or \
            GenerationConfig()
        pick = lambda v, d: d if v is None else v  # noqa: E731
        strategy = pick(decode_strategy, cfg.decode_strategy)
        greedy = strategy in ("greedy_search", "greedy")
        temperature_ = float(pick(temperature, cfg.temperature))
        top_k_ = int(pick(top_k, cfg.top_k))
        top_p_ = float(pick(top_p, cfg.top_p))
        rep_ = float(pick(repetition_penalty, cfg.repetition_penalty))
        eos_ = pick(eos_token_id, cfg.eos_token_id)
        pad_ = pick(pad_token_id, cfg.pad_token_id)
        pad_ = (eos_ if pad_ is None else pad_) or 0
        seed_ = pick(seed, cfg.seed)
        ids = input_ids if isinstance(input_ids, Tensor) else \
            Tensor(jnp.asarray(np.asarray(input_ids)))
        b, prompt_len = ids.shape
        if max_new_tokens is None and max_length is not None:
            max_new_tokens = int(max_length) - prompt_len
        n_new = int(pick(max_new_tokens, cfg.max_new_tokens))
        if n_new <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got {n_new} "
                f"(max_length={max_length}, prompt length {prompt_len})")
        total = prompt_len + n_new

        if seed_ is not None:
            key = jax.random.PRNGKey(seed_)
        else:
            from ..framework import random as fr
            key = fr.default_generator.next_key()
        key_t = Tensor(key)

        ids32 = Tensor(ids._data.astype(jnp.int32))
        buf = Tensor(jnp.concatenate(
            [ids32._data, jnp.full((b, n_new), pad_, jnp.int32)], axis=1))
        finished = Tensor(jnp.zeros((b,), bool))
        caches = self.init_kv_cache(b, total)
        eos_i = -1 if eos_ is None else int(eos_)
        if eos_i < 0:
            # no eos early-exit -> static trip count -> the whole decode
            # runs as ONE compiled program (prefill + lax.scan over steps)
            buf_f, lp_f, _key_f = self._gen_fused_static()(
                ids32, key_t, buf, caches, temperature_, top_k_, top_p_,
                rep_, greedy, int(pad_), n_new)
            gen = Tensor(buf_f._data[:, prompt_len:prompt_len + n_new])
            scores = Tensor(lp_f._data / float(n_new))
            return gen, scores

        step = self._gen_step_static()

        pos = Tensor(jnp.zeros((), jnp.int32))
        tok, lp, key_t, buf, finished, caches = step(
            ids32, pos, key_t, buf, finished, caches, temperature_, top_k_,
            top_p_, rep_, greedy, eos_i, int(pad_))
        lp_sum = lp.jax().astype(jnp.float32)
        # per-row generated-token counts: a row stops accruing once finished
        counts = np.ones((b,), np.float32)
        steps_done = 1
        # this step loop only serves the eos path now (eos-less decode
        # returned above via the fused scan); poll finished per token to
        # early-exit once every row hit eos
        for i in range(1, n_new):
            fin_np = np.asarray(finished.jax())
            if bool(fin_np.all()):
                break
            counts += (~fin_np).astype(np.float32)
            pos = Tensor(jnp.asarray(prompt_len + i - 1, jnp.int32))
            tok2d = Tensor(tok._data.reshape(b, 1))
            tok, lp, key_t, buf, finished, caches = step(
                tok2d, pos, key_t, buf, finished, caches, temperature_,
                top_k_, top_p_, rep_, greedy, eos_i, int(pad_))
            lp_sum = lp_sum + lp.jax().astype(jnp.float32)
            steps_done += 1
        gen = Tensor(buf._data[:, prompt_len:prompt_len + steps_done])
        scores = Tensor(lp_sum / jnp.asarray(counts))
        return gen, scores
