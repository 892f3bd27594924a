"""Parameter leaves of the models that are built in their configuration's
dtype and, with ``empty_init``, without storage (a model over half the
chip cannot hold a float32 copy of itself first): ``nemotron_h.py`` and
``exaone_moe.py`` build from these. ``cfg`` needs ``dtype``,
``empty_init`` and ``initializer_range``."""

from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework.core import Parameter
from ..nn import initializer as I


class _Base(nn.Layer):
    """Parameters in the configuration's dtype, Normal(0, range) unless a
    leaf says otherwise."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg

    def _p(self, shape, init=None):
        if self.cfg.empty_init:
            data = jnp.zeros(tuple(shape), self._dtype)
            data.delete()
            return Parameter(data)
        return self.create_parameter(
            list(shape), default_initializer=init
            or I.Normal(0.0, self.cfg.initializer_range))


class _Weight(_Base):
    """One leaf named ``.weight``: a bias-free projection [in, out], a
    table, or (with ``init``) a norm scale."""

    def __init__(self, cfg, *shape, init=None):
        super().__init__(cfg)
        self.weight = self._p(shape, init)
