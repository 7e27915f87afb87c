"""``TransformerConfig.layers``: what each layer is, resolved once from
whichever schedule the config gives (window and rotary alternation,
attention kinds, ``layer_pattern``, expert placement), against literal
tables at tiny sizes; the one uniformity rule, and the scanned stack, the
pipeline stage and the pipelined harness that refuse a stack whose layers
differ."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.models import AttentionKind, RopeScaling, TransformerConfig
from apex_tpu.models.gpt_stage import GPTStage
from apex_tpu.models.transformer_lm import _Layer, _LayerAttention
from apex_tpu.transformer import parallel_state

HID, SEQ = 32, 8
LINEAR = RopeScaling(rope_type="linear", factor=8.0)
YARN = RopeScaling(rope_type="yarn", factor=4.0,
                   original_max_position_embeddings=64, beta_fast=4.0)


def config(**kw):
    return TransformerConfig(**dict(dict(
        hidden_size=HID, num_layers=4, num_attention_heads=4,
        ffn_hidden_size=48, vocab_size=64, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, use_flash_attention=False,
        activation_checkpointing=False), **kw))


ROPE = dict(position_embedding_type="rope")
MOE = dict(num_moe_experts=4, moe_top_k=2)
SCHEDULES = {
    "gpt2": config(num_layers=3),
    "mistral": config(num_layers=3, sliding_window=4, **ROPE),
    "gemma2": config(sliding_window=4, sliding_window_pattern=2, **ROPE),
    "gemma3": config(num_layers=7, sliding_window=4,
                     sliding_window_pattern=6, rotary_base=1e6,
                     rotary_base_local=1e4, rope_scaling=LINEAR, **ROPE),
    "smollm3": config(num_layers=8, no_rope_layer_interval=4, **ROPE),
    "laguna": config(
        num_layers=5, num_query_groups=2, attention_kinds=(
            AttentionKind("full", heads=4, rotary_base=5e4,
                          rotary_percent=0.5, rope_scaling=YARN),
            AttentionKind("window", window=4, heads=8, rotary_base=1e4)),
        layer_attention=("full", "window", "window", "window", "full"),
        **ROPE),
    "nemotron_h": config(num_layers=9, layer_pattern="MEMEM*EME",
                         position_embedding_type="none",
                         normalization="rmsnorm", activation="relu2",
                         mamba_num_heads=4, mamba_head_dim=8,
                         mamba_n_groups=2, **MOE),
    "moonlight": config(num_layers=3, moe_first_dense_layers=1, **MOE),
    "moe_every_other": config(moe_layer_freq=2, **MOE),
    "moe_every_layer": config(num_layers=3, **MOE),
}
UNIFORM = {"gpt2", "mistral", "moe_every_layer"}
# where the layers differ, the schedule the refusals name
CAUSE = {"gemma2": "sliding_window_pattern",
         "gemma3": "sliding_window_pattern",
         "smollm3": "no_rope_layer_interval", "laguna": "layer_attention",
         "nemotron_h": "layer_pattern",
         "moonlight": "moe_first_dense_layers",
         "moe_every_other": "moe_layer_freq"}


def att(window=None, rope=True, base=10000.0, heads=4, kind=None,
        percent=1.0, scaling=None):
    return _LayerAttention(kind, heads, window, rope, base, percent, scaling)


def layer(mixer="attention", ffn="dense", attention=None):
    return _Layer(mixer, ffn, attention or att())


LEARNED = att(rope=False)
LOCAL, GLOBAL = att(4, base=1e4), att(base=1e6, scaling=LINEAR)
FULL = att(kind="full", base=5e4, percent=0.5, scaling=YARN)
BAND = att(4, kind="window", heads=8)
NONE = att(rope=False)
TABLES = {
    "gpt2": [layer(attention=LEARNED)] * 3,
    "mistral": [layer(attention=att(4))] * 3,
    "gemma2": [layer(attention=att(4)), layer()] * 2,
    "gemma3": [layer(attention=LOCAL)] * 5 + [layer(attention=GLOBAL),
                                              layer(attention=LOCAL)],
    "smollm3": ([layer()] * 3 + [layer(attention=att(rope=False))]) * 2,
    "laguna": [layer(attention=a)
               for a in (FULL, BAND, BAND, BAND, FULL)],
    "nemotron_h": [layer(*{"M": ("mamba", None), "E": (None, "moe"),
                           "*": ("attention", None)}[c], NONE)
                   for c in "MEMEM*EME"],
    "moonlight": [layer(attention=LEARNED),
                  layer(ffn="moe", attention=LEARNED),
                  layer(ffn="moe", attention=LEARNED)],
    "moe_every_other": [layer(ffn="moe", attention=LEARNED),
                        layer(attention=LEARNED)] * 2,
    "moe_every_layer": [layer(ffn="moe", attention=LEARNED)] * 3,
}
NON_UNIFORM = sorted(set(SCHEDULES) - UNIFORM)


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_the_resolved_table(name):
    assert list(SCHEDULES[name].layers) == TABLES[name]


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_the_uniformity_rule(name):
    assert SCHEDULES[name].uniform == (name in UNIFORM)


def test_the_table_is_no_field():
    """Resolved once, held beside the fields: equality, hashing and
    ``dataclasses.replace`` see only the fields."""
    cfg = SCHEDULES["gemma2"]
    assert cfg.layers is cfg.layers
    assert "layers" not in {f.name for f in dataclasses.fields(cfg)}
    again = dataclasses.replace(cfg)
    assert again == cfg and hash(again) == hash(cfg)
    assert dataclasses.replace(cfg, num_layers=2).layers == cfg.layers[:2]


@pytest.mark.parametrize("name", NON_UNIFORM)
def test_a_scan_refuses_layers_that_differ(name):
    # the kinds refuse scan_layers among their own conditions
    match = ("scan_layers" if name == "laguna"
             else f"scan_layers needs a uniform stack: .*{CAUSE[name]}")
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(SCHEDULES[name], scan_layers=True)


@pytest.mark.parametrize("change", [
    dict(num_layers=1, sliding_window=4, sliding_window_pattern=2, **ROPE),
    dict(num_layers=1, moe_first_dense_layers=1, **MOE),
    dict(num_layers=1, moe_layer_freq=2, **MOE),
    dict(num_layers=3, no_rope_layer_interval=4, **ROPE),
])
def test_a_schedule_that_leaves_the_layers_equal_scans(change):
    """A stack that names a schedule but whose layers are equal in fact
    is uniform: it scans and stages."""
    cfg = config(scan_layers=True, **change)
    assert cfg.uniform
    stage = GPTStage(cfg, layers_per_stage=cfg.num_layers)
    shapes = jax.eval_shape(lambda: stage.init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32),
        jnp.zeros((SEQ, 1, HID)), True))
    assert "layers" in shapes["params"]["transformer"]


def _stage(cfg):
    return GPTStage(cfg, layers_per_stage=1).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32),
        jnp.zeros((SEQ, 1, HID)), True)


@pytest.mark.parametrize("name", NON_UNIFORM)
def test_a_pipeline_stage_refuses_layers_that_differ(name):
    """Every stage numbers its layers from 0: a stack whose layers differ
    would read the first stage's layers on every stage."""
    with pytest.raises(ValueError,
                       match=f"pipeline stage needs a uniform stack: "
                             f".*{CAUSE[name]}"):
        _stage(SCHEDULES[name])


@pytest.mark.parametrize("name", ["mistral", "moe_every_layer"])
def test_a_pipeline_stage_takes_a_uniform_stack(name):
    params = jax.eval_shape(lambda: _stage(SCHEDULES[name]))["params"]
    assert set(params["transformer"]) == {"layer_0"}


@pytest.mark.parametrize("name", NON_UNIFORM)
def test_the_pipelined_harness_refuses_layers_that_differ(name):
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.amp.grad_scaler import GradScaler
    from apex_tpu.transformer.testing.gpt_3d import build_gpt_3d_harness

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=1, devices=jax.devices()[:1])
    try:
        with pytest.raises(ValueError, match=f"{CAUSE[name]}.*gpt_moe"):
            build_gpt_3d_harness(SCHEDULES[name], mesh, FusedAdam(lr=1e-3),
                                 GradScaler(enabled=False), pp=1, seq=SEQ,
                                 microbatch=1, num_microbatches=1)
    finally:
        parallel_state.destroy_model_parallel()
