"""Device time by module (``apex_tpu.telemetry.scopes``): two-layer GPT
and BERT steps (amp O2 + ``FusedAdam`` / ``FusedLAMB``, recomputation on
for GPT) compiled on the CPU give a scope to every block of the program,
and ``classify`` tells the blocks and the phases apart."""

import collections

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.models import (BertModel, GPTModel, TransformerConfig,
                             bert_loss_fn)
from apex_tpu.models.gpt import gpt_loss_fn
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.telemetry.scopes import classify, scope_table
from apex_tpu.transformer.enums import AttnMaskType

BATCH, SEQ = 2, 16
MODEL_BLOCKS = {"embedding", "layernorm", "attention", "mlp", "residual",
                "head"}
BLOCKS = sorted(MODEL_BLOCKS | {"loss", "amp", "optimizer"})
PHASES = ["forward", "backward", "recompute", "update"]


def _config(**kw):
    return TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=SEQ,
        compute_dtype=jnp.bfloat16, use_flash_attention=False, **kw)


def _compiled(model, optimizer, loss, batch, *init_args):
    params = model.init(jax.random.PRNGKey(0), *init_args)["params"]
    params, opt = amp.initialize(params, optimizer, opt_level="O2",
                                 verbosity=0)

    def train_step(params, opt_state, batch):
        scale = opt_state["scaler"].loss_scale
        value, grads = jax.value_and_grad(
            lambda p: loss(p, batch) * scale)(params)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, value / scale

    return jax.jit(train_step).lower(params, opt.init(params),
                                     batch).compile()


@pytest.fixture(scope="module")
def tables():
    """model name -> {instruction name: scope} of its compiled step."""
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)
    gpt = GPTModel(_config(attn_mask_type=AttnMaskType.causal,
                           tie_word_embeddings=True,
                           activation_checkpointing=True))
    bert = BertModel(_config(attn_mask_type=AttnMaskType.padding,
                             activation_checkpointing=False))
    ones = jnp.ones((BATCH, SEQ), jnp.int32)

    def gpt_loss(p, b):
        return gpt_loss_fn(gpt.apply({"params": p}, b["tokens"]),
                           b["labels"])

    def bert_loss(p, b):
        mlm, nsp = bert.apply({"params": p}, b["tokens"], b["mask"],
                              b["segments"])
        return bert_loss_fn(mlm, nsp, b["labels"], b["loss_mask"],
                            b["nsp_labels"])

    return {
        "gpt": scope_table(_compiled(
            gpt, FusedAdam(lr=1e-4), gpt_loss,
            {"tokens": tokens, "labels": tokens}, tokens)),
        "bert": scope_table(_compiled(
            bert, FusedLAMB(lr=1e-4), bert_loss,
            {"tokens": tokens, "mask": ones, "segments": tokens,
             "labels": tokens, "loss_mask": ones.astype(jnp.float32),
             "nsp_labels": jnp.zeros((BATCH,), jnp.int32)},
            tokens, ones, tokens)),
    }


@pytest.fixture(scope="module")
def counts(tables):
    """model name -> (instructions by block, instructions by phase)."""
    out = {}
    for model, table in tables.items():
        blocks, phases = collections.Counter(), collections.Counter()
        for scope in table.values():
            block, phase = classify(scope)
            blocks[block and block.split("/")[0]] += 1
            phases[phase] += 1
        out[model] = blocks, phases
    return out


@pytest.mark.parametrize("model", ["gpt", "bert"])
@pytest.mark.parametrize("block", BLOCKS)
def test_every_block_has_instructions(counts, model, block):
    blocks, _ = counts[model]
    assert blocks[block] > 0, sorted(blocks.items(), key=str)


@pytest.mark.parametrize("phase", PHASES)
def test_every_phase_has_instructions(counts, phase):
    # only GPT recomputes; BERT's step has the other three
    assert counts["gpt"][1][phase] > 0, counts["gpt"][1]
    if phase != "recompute":
        assert counts["bert"][1][phase] > 0


@pytest.mark.parametrize("model", ["gpt", "bert"])
def test_attention_names_its_parts(tables, model):
    parts = {classify(s)[0] for s in tables[model].values()}
    assert {"attention/qkv", "attention/dense"} <= parts


@pytest.mark.parametrize("model,optimizer", [("gpt", "fused_adam"),
                                             ("bert", "fused_lamb")])
def test_update_and_loss_are_not_charged_to_the_model(tables, model,
                                                      optimizer):
    seen = set()
    for scope in tables[model].values():
        parts = scope.split("/")
        block, phase = classify(scope)
        if "amp" in parts or "optimizer" in parts:
            assert block in ("amp", "optimizer"), scope
            assert phase == "update", scope
            seen.update(p for p in parts if p in (
                "unscale", "master_to_model", "scaler_update", optimizer))
        elif any(p.endswith("(loss)") or p == "loss" for p in parts):
            assert block == "loss", scope
            seen.add("loss")
        # (an instruction named after the argument it reads, a weight's
        # relayout, is in the weight's block and outside differentiation)
        assert not (block in MODEL_BLOCKS and phase == "update"
                    and "/" in scope), scope
    assert seen == {"unscale", "master_to_model", "scaler_update",
                    optimizer, "loss"}


@pytest.mark.parametrize("scope,want", [
    ("jit(train_step)/mul", (None, "update")),
    ("jit(f)/jvp(somebody_else)/dot_general", (None, "forward")),
    ("jit(f)/fused_adam/mul", ("optimizer", "update")),
    ("jit(f)/optimizer/fused_lamb/reduce_sum", ("optimizer", "update")),
    ("jit(f)/amp/unscale/mul", ("amp", "update")),
    ("jit(f)/transpose(jvp(loss))/mul", ("loss", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_3/mlp/dense_h_to_4h/dot_general",
     ("mlp", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/jvp(GPTModel)/transformer/"
     "checkpoint/rematted_computation/layer_1/self_attention/"
     "self_attention_flash_fwd/pallas_call",
     ("attention/kernel", "recompute")),
    ("jit(f)/transpose(jvp(BertModel))/transformer/layer_0/self_attention/"
     "transpose", ("attention", "backward")),
    # the indexer's selection kernel is the indexer's, not attention/kernel
    ("jit(f)/transpose(jvp(GPTModel))/transformer/checkpoint/"
     "rematted_computation/layer_1/self_attention/indexer/indexer/select/"
     "jit(_select)/indexer_topk_select/pallas_call",
     ("indexer", "recompute")),
    # the Mamba-2 mixer names its parts; the shared expert is the expert
    # layer's
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/mixer/ssm/scan/dot_general",
     ("ssm/scan", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/checkpoint/"
     "rematted_computation/layer_2/mixer/ssm/scan/while/body/mul",
     ("ssm/scan", "recompute")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_4/mixer/ssm/"
     "in_proj/dot_general", ("ssm/in_proj", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/mixer/ssm/conv/mul",
     ("ssm/conv", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/mixer/ssm/gate_norm/rsqrt",
     ("ssm/gate_norm", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/mixer/ssm/out_proj/"
     "dot_general", ("ssm/out_proj", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/mixer/ssm/add",
     ("ssm", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/mlp/moe/shared/shared_up/"
     "dot_general", ("moe", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/mlp/routed/moe/router/"
     "router/logistic", ("moe", "forward")),
    # the experts' grouped matmul keeps its scope and so its phase, which
    # XLA's own ragged-dot kernel (the oracle's, below) does not
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/mlp/routed/moe/experts/"
     "experts/jit(_rows)/moe_grouped_matmul_fwd/pallas_call",
     ("moe", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/checkpoint/"
     "rematted_computation/layer_1/mlp/routed/moe/experts/experts/"
     "jit(_rows)/moe_grouped_matmul_fwd/pallas_call", ("moe", "recompute")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_1/mlp/routed/moe/"
     "experts/experts/jit(_drhs)/moe_grouped_matmul_drhs/pallas_call",
     ("moe", "backward")),
    ("ragged-dot-none", ("moe", "update")),
    # latent attention names its parts inside self_attention, the kernels
    # among them; what it does outside its scopes stays attention's
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/self_attention/mla/q_proj/"
     "q_proj/dot_general", ("mla/q_proj", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/self_attention/mla/kv_down/"
     "kv_norm/rsqrt", ("mla/kv_down", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_2/self_attention/"
     "mla/kv_up/kv_up/dot_general", ("mla/kv_up", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/self_attention/mla/rope/cos",
     ("mla/rope", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/checkpoint/"
     "rematted_computation/layer_1/self_attention/mla/out_proj/dense/"
     "dot_general", ("mla/out_proj", "recompute")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/mla/kernel/"
     "jit(_mla_fwd_pallas)/mla_attention_flash_fwd/pallas_call",
     ("mla/kernel", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_1/self_attention/"
     "mla/kernel/jit(_mla_bwd_pallas)/mla_attention_flash_dkv/pallas_call",
     ("mla/kernel", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/mla/transpose",
     ("mla", "forward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/transpose",
     ("attention", "forward")),
    ("jit(f)/jvp(BertModel)/head/lm_layernorm/reduce_sum",
     ("head", "forward")),
    # training by diffusion over blocks: the noisy half's slice, head and
    # loss are one block; the rule's kernels stay in block attention
    ("jit(f)/jvp(GPTModel)/diffusion/select_noisy/slice",
     ("diffusion_head", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/diffusion/head/dot_general",
     ("diffusion_head", "backward")),
    ("jit(f)/jvp(diffusion)/loss/reduce_sum", ("diffusion_head", "forward")),
    ("jit(f)/transpose(jvp(diffusion))/loss/jit(_where)/select_n",
     ("diffusion_head", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_1/self_attention/"
     "jit(_bsnd_fwd_pallas)/blockdiff_attention_flash_fwd/pallas_call",
     ("attention/kernel", "forward")),
    ("jit(f)/transpose(jvp(GPTModel))/transformer/layer_1/self_attention/"
     "jit(_bsnd_bwd_pallas)/blockdiff_attention_flash_dkv/pallas_call",
     ("attention/kernel", "backward")),
    ("jit(f)/jvp(GPTModel)/transformer/layer_0/add",
     ("residual", "forward")),
    ("params[\\'transformer\\'][\\'layer_0\\'][\\'mlp\\']"
     "[\\'dense_h_to_4h\\'][\\'weight\\']", ("mlp", "update")),
    ("opt_state['inner']['amp_master']['transformer']['layer_0']['mlp']",
     ("optimizer", "update")),
    ("batch['tokens']", (None, "update")),
    ("jit(f)/ddp_allreduce_bucket_3/psum", ("collective", "update")),
    ("jit(f)/transpose(jvp(pp_bwd_unit))/ppermute",
     ("collective", "backward")),
])
def test_classify(scope, want):
    assert classify(scope) == want


@pytest.fixture(scope="module")
def hybrid_blocks():
    """Blocks of a compiled three-layer ``layer_pattern`` step (a Mamba-2
    mixer, an expert layer with its shared expert, attention)."""
    cfg = TransformerConfig(
        hidden_size=32, num_layers=3, num_attention_heads=2, head_dim=16,
        num_query_groups=1, ffn_hidden_size=16, vocab_size=64,
        max_position_embeddings=SEQ, compute_dtype=jnp.bfloat16,
        use_flash_attention=False, normalization="rmsnorm",
        activation="relu2", attention_bias=False,
        position_embedding_type="none", layer_pattern="ME*",
        mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2,
        mamba_state_size=8, mamba_chunk_size=8, num_moe_experts=8,
        moe_top_k=2, moe_local_experts=4, moe_capacity_factor=2.0,
        moe_router_score="sigmoid_bias", moe_shared_expert_size=24,
        moe_shared_expert_gated=False, activation_checkpointing=True)
    model = GPTModel(cfg)
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)

    def loss(p, b):
        logits, _ = model.apply({"params": p}, b["tokens"],
                                mutable=["moe_losses"])
        return gpt_loss_fn(logits, b["labels"])

    table = scope_table(_compiled(model, FusedAdam(lr=1e-4), loss,
                                  {"tokens": tokens, "labels": tokens},
                                  tokens))
    return collections.Counter(classify(s) for s in table.values())


@pytest.mark.parametrize("block", [
    "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm", "ssm/out_proj",
    "moe", "attention/qkv", "layernorm"])
@pytest.mark.parametrize("phase", ["forward", "backward", "recompute"])
def test_a_hybrid_step_gives_the_mixer_s_parts_their_time(hybrid_blocks,
                                                          block, phase):
    if (block, phase) == ("ssm/out_proj", "recompute"):
        # a layer's last product: its backward needs its inputs alone
        assert hybrid_blocks[(block, phase)] == 0
        return
    assert hybrid_blocks[(block, phase)] > 0, sorted(
        hybrid_blocks, key=str)


@pytest.fixture(scope="module")
def latent_blocks():
    """Blocks of a compiled two-layer latent-attention step (a leading
    dense layer, then gated experts with a shared expert)."""
    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=48, vocab_size=64, max_position_embeddings=SEQ,
        compute_dtype=jnp.bfloat16, normalization="rmsnorm",
        activation="swiglu", attention_bias=False,
        position_embedding_type="rope", rotary_interleaved=True,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_moe_experts=8, moe_top_k=2,
        moe_first_dense_layers=1, moe_ffn_hidden_size=16,
        moe_local_experts=4, moe_capacity_factor=2.0,
        moe_router_score="sigmoid_bias", moe_seq_aux_loss_coeff=0.001,
        moe_shared_expert_size=32, moe_shared_expert_gated=False,
        activation_checkpointing=True)
    model = GPTModel(cfg)
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)

    def loss(p, b):
        logits, _ = model.apply({"params": p}, b["tokens"],
                                mutable=["moe_losses"])
        return gpt_loss_fn(logits, b["labels"])

    table = scope_table(_compiled(model, FusedAdam(lr=1e-4), loss,
                                  {"tokens": tokens, "labels": tokens},
                                  tokens))
    return collections.Counter(classify(s) for s in table.values())


@pytest.mark.parametrize("block", [
    "mla/q_proj", "mla/kv_down", "mla/kv_up", "mla/rope", "mla/kernel",
    "mla/out_proj", "moe", "mlp", "layernorm"])
@pytest.mark.parametrize("phase", ["forward", "backward"])
def test_a_latent_step_gives_the_attention_s_parts_their_time(latent_blocks,
                                                              block, phase):
    assert latent_blocks[(block, phase)] > 0, sorted(latent_blocks, key=str)
    # the plain attention block's projection and kernel are not there
    assert latent_blocks[("attention/qkv", phase)] == 0
    assert latent_blocks[("attention/kernel", phase)] == 0


@pytest.fixture(scope="module")
def diffusion_blocks():
    """Blocks of a compiled two-layer block-diffusion step (rows
    ``[x0 ; xt]`` under the rule, held experts, the head and the weighted
    loss on the noisy half)."""
    from apex_tpu.models.gpt import block_diffusion_loss_fn

    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2, head_dim=16,
        num_query_groups=1, ffn_hidden_size=16, vocab_size=64,
        max_position_embeddings=SEQ, compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="swiglu", attention_bias=False,
        qk_norm="head", position_embedding_type="rope",
        attn_mask_type=AttnMaskType.block_diffusion,
        diffusion_block_length=4, num_moe_experts=8, moe_top_k=2,
        moe_local_experts=4, moe_capacity_factor=2.0,
        activation_checkpointing=True)
    model = GPTModel(cfg)
    tokens = jnp.zeros((BATCH, SEQ), jnp.int32)
    rows = jnp.zeros((BATCH, 2 * SEQ), jnp.int32)

    def loss(p, b):
        logits, _ = model.apply(
            {"params": p}, jnp.concatenate([b["tokens"], b["noisy"]], 1),
            mutable=["moe_losses"])
        return block_diffusion_loss_fn(logits, b["tokens"], b["weights"])

    table = scope_table(_compiled(
        model, FusedAdam(lr=1e-4), loss,
        {"tokens": tokens, "noisy": tokens,
         "weights": jnp.ones((BATCH, SEQ), jnp.float32)}, rows))
    return collections.Counter(classify(s) for s in table.values())


@pytest.mark.parametrize("block", ["diffusion_head", "attention/qkv",
                                   "attention/dense", "moe", "layernorm"])
@pytest.mark.parametrize("phase", ["forward", "backward"])
def test_a_diffusion_step_gives_the_noisy_half_s_head_its_block(
        diffusion_blocks, block, phase):
    assert diffusion_blocks[(block, phase)] > 0, sorted(diffusion_blocks,
                                                        key=str)
    # the head and the loss are the block's, not blocks of their own
    assert diffusion_blocks[("head", phase)] == 0
    assert diffusion_blocks[("loss", phase)] == 0


# what a fusion answers with: the scope of the matrix product or Mosaic
# kernel inside it, else of most of its instructions, else its own
_HLO = '''HloModule jit_step, is_scheduled=true

%fused_update (p.1: f32[8], g.1: bf16[8]) -> (f32[8], bf16[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %g.1 = bf16[8]{0} parameter(1)
  %convert.1 = f32[8]{0} convert(%g.1), metadata={op_name="jit(step)/amp/unscale/convert_element_type"}
  %mul.1 = f32[8]{0} multiply(%convert.1, %convert.1), metadata={op_name="jit(step)/optimizer/fused_adam/mul"}
  %add.1 = f32[8]{0} add(%mul.1, %p.1), metadata={op_name="jit(step)/optimizer/fused_adam/add"}
  %sub.1 = f32[8]{0} subtract(%p.1, %add.1), metadata={op_name="jit(step)/optimizer/fused_adam/sub"}
  %convert.2 = bf16[8]{0} convert(%sub.1), metadata={op_name="jit(step)/amp/master_to_model/convert_element_type"}
  ROOT %tuple.1 = (f32[8]{0}, bf16[8]{0}) tuple(%sub.1, %convert.2)
}

%fused_matmul (x.1: bf16[8,8], w.1: bf16[8,8]) -> f32[8] {
  %x.1 = bf16[8,8]{1,0} parameter(0)
  %w.1 = bf16[8,8]{1,0} parameter(1)
  %convolution.1 = f32[8,8]{1,0} convolution(%x.1, %w.1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jvp(GPTModel)/transformer/layer_0/mlp/dense_4h_to_h/dot_general"}
  %mul.2 = f32[8,8]{1,0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(step)/jvp(GPTModel)/transformer/layer_1/input_layernorm/mul"}
  %sub.2 = f32[8,8]{1,0} subtract(%mul.2, %convolution.1), metadata={op_name="jit(step)/jvp(GPTModel)/transformer/layer_1/input_layernorm/sub"}
  ROOT %reduce.1 = f32[8]{0} reduce(%sub.2, %mul.2), dimensions={1}, to_apply=%add, metadata={op_name="jit(step)/jvp(GPTModel)/transformer/layer_1/input_layernorm/reduce_sum"}
}

%fused_bitcast (b.1: f32[8]) -> f32[8,1] {
  %b.1 = f32[8]{0} parameter(0)
  ROOT %bitcast.1 = f32[8,1]{1,0} bitcast(%b.1)
}

ENTRY %main (a: f32[8], b: bf16[8], x: bf16[8,8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = bf16[8]{0} parameter(1), metadata={op_name="grads"}
  %x = bf16[8,8]{1,0} parameter(2)
  %fusion.1 = (f32[8]{0}, bf16[8]{0}) fusion(%a, %b), kind=kLoop, calls=%fused_update, metadata={op_name="jit(step)/amp/master_to_model/convert_element_type"}
  %fusion.2 = f32[8]{0} fusion(%x, %x), kind=kOutput, calls=%fused_matmul, metadata={op_name="jit(step)/jvp(GPTModel)/transformer/layer_1/input_layernorm/reduce_sum"}
  %fusion.3 = f32[8,1]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_bitcast, metadata={op_name="jit(step)/jvp(loss)/reshape"}
  %fusion.4 = f32[8,1]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_bitcast
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  ROOT %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
}
'''


@pytest.mark.parametrize("instruction,want", [
    # root: amp's cast; three of five instructions are Adam's
    ("fusion.1", ("optimizer", "update")),
    # root: the next layer's LayerNorm reduce; the time is the matmul's
    ("fusion.2", ("mlp", "forward")),
    # nothing inside has a scope: its own
    ("fusion.3", ("loss", "forward")),
    ("convert.2", ("amp", "update")),
    ("convolution.1", ("mlp", "forward")),
    # no scope anywhere: left out
    ("fusion.4", None), ("copy-start.1", None), ("copy-done.1", None),
    ("a", None),
])
def test_what_a_fusion_answers_with(instruction, want):
    from apex_tpu.analysis.hlo import instruction_scopes

    table = instruction_scopes(_HLO)
    if want is None:
        assert instruction not in table
    else:
        assert classify(table[instruction]) == want
