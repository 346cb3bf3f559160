"""Causal-skip attention (kv blocks above the diagonal skipped) gives the
forward pass it replaces, at smoke scale."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model_zoo import build_model


@pytest.fixture(scope="module")
def base():
    cfg = reduced(get_config("qwen3-1.7b"))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    return cfg, m, params, {"tokens": toks, "labels": toks}


def test_causal_skip_exact(base):
    cfg, m, params, batch = base
    cfg_s = dataclasses.replace(cfg, attn_causal_skip=True, attn_chunk=8)
    m_s = build_model(cfg_s)
    f1, _ = jax.jit(m.forward)(params, batch)
    f2, _ = jax.jit(m_s.forward)(params, batch)
    np.testing.assert_allclose(np.asarray(f1, np.float32),
                               np.asarray(f2, np.float32),
                               rtol=2e-4, atol=2e-4)
