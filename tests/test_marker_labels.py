"""Zero-overhead marker tracking "in simulation" (paper §III-D2): block
named_scope labels must survive into the compiled HLO so the profiler can
locate marker blocks by label (the gem5 PC-label analogue) without any
runtime hooks."""
import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core.hlo_analysis import find_scope_labels
from repro.models.model_zoo import build_model


def _hlo_for(arch):
    cfg = reduced(get_config(arch))
    m = build_model(cfg)
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    return jax.jit(lambda p, b: m.loss(p, b)[0]).lower(params, batch) \
        .compile().as_text()


def test_attn_and_mlp_markers_locatable():
    hlo = _hlo_for("qwen3-1.7b")
    assert find_scope_labels(hlo, "nugget_block_attn")
    assert find_scope_labels(hlo, "nugget_block_mlp")


def test_moe_marker_locatable():
    hlo = _hlo_for("olmoe-1b-7b")
    assert find_scope_labels(hlo, "nugget_block_moe")


def test_mamba_marker_locatable():
    hlo = _hlo_for("mamba2-780m")
    assert find_scope_labels(hlo, "nugget_block_mamba")
