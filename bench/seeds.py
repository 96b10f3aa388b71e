"""Everything a run draws comes from ``--seed`` through these two, so one
seed always gives the same inputs, of any size up to 64 bits and beyond."""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A host generator for ``stream`` of ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def key(seed: int, stream: int = 0):
    """A JAX key for ``stream`` of ``seed``."""
    import jax
    state = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32))
