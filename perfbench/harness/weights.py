"""Weights from ``--seed``, made on the device, in the type they are
served or trained in. The program's model and the plain reference get the
SAME numbers from the same rule, and neither takes anything from the other:
leaf i of a configuration's parameter list is

    base_i + std * normal(fold_in(key(seed), i))   cast to the leaf's dtype

with base 1 for norm scales and 0 elsewhere (so every bias and every norm
scale is alive in the comparison).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp


def seed_key(words):
    """A key from the seed's two 32-bit words (traceable). ``rbg`` keys: the
    bits come from the chip's own generator, a pure function of key and
    shape, some ten times faster than threefry for 3e9 numbers; splitting
    and fold_in stay threefry."""
    return jax.random.fold_in(jax.random.key(words[0], impl="rbg"), words[1])


def seed_words(seed):
    seed = int(seed)
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def leaf(key, index, shape, base, std, dtype):
    w = std * jax.random.normal(jax.random.fold_in(key, index), shape,
                                jnp.float32)
    if base:
        w = w + base
    return w.astype(dtype)


def make_all(specs, seed, std, dtype, donate=None):
    """Every leaf in ONE jitted call. ``specs``: [(name, shape, base)].
    ``donate``: arrays of the same shapes and dtype whose buffers the new
    leaves may take over (the program's own initial values)."""
    shapes = [tuple(s) for _, s, _ in specs]
    bases = [b for _, _, b in specs]

    def gen(words, old):
        del old
        key = seed_key(words)
        return [leaf(key, i, shapes[i], bases[i], std, dtype)
                for i in range(len(shapes))]

    fn = jax.jit(gen, donate_argnums=(1,) if donate is not None else ())
    return fn(seed_words(seed), donate)


class LeafSource:
    """What a reference is handed instead of weights: the rule. ``get``
    makes one named leaf, ``layer`` every leaf of decoder layer ``l`` (``l``
    may be traced, so a reference can scan over layers and hold one layer's
    weights at a time). Values are upcast to float32 AFTER the cast to the
    served dtype: the reference computes in float32 on the numbers the
    program was given."""

    def __init__(self, specs, seed_words_, std, dtype, layer_pattern):
        self.specs, self.std, self.dtype = specs, std, dtype
        self.words = seed_words_
        self.index = {n: i for i, (n, _, _) in enumerate(specs)}
        pat = re.compile(layer_pattern)          # e.g. r"^layers\.(\d+)\.(.+)$"
        per = {}
        for i, (n, s, b) in enumerate(specs):
            mt = pat.match(n)
            if mt:
                per.setdefault(int(mt.group(1)), []).append(
                    (mt.group(2), i, tuple(s), b))
        self.n_layers = len(per)
        self.layer0 = per.get(0, [])
        if self.n_layers > 1:
            self.stride = per[1][0][1] - per[0][0][1]
            for l, leaves in per.items():       # layers must be laid out alike
                assert [(a, i - l * self.stride, s, b)
                        for a, i, s, b in leaves] == self.layer0, n
        else:
            self.stride = 0

    def raw(self, name):
        """The leaf in its own dtype (for a lookup that upcasts its rows)."""
        i = self.index[name]
        _, shape, base = self.specs[i]
        return leaf(seed_key(self.words), i, tuple(shape), base, self.std,
                    self.dtype)

    def get(self, name):
        return self.raw(name).astype(jnp.float32)

    def layer(self, l):
        key = seed_key(self.words)
        return {a: leaf(key, i + l * self.stride, s, b, self.std,
                        self.dtype).astype(jnp.float32)
                for a, i, s, b in self.layer0}

    def all(self):
        key = seed_key(self.words)
        return {n: leaf(key, i, tuple(s), b, self.std, self.dtype)
                .astype(jnp.float32)
                for i, (n, s, b) in enumerate(self.specs)}
