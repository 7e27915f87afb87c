"""apex_tpu.models — model families used by examples, tests and benches.

Parity: reference apex/transformer/testing/standalone_transformer_lm.py
(GPT/BERT Megatron models, 1,574 LoC), examples/imagenet (ResNet),
examples/dcgan (DCGAN).
"""

from apex_tpu.models.transformer_lm import (  # noqa: F401
    ParallelAttention,
    ParallelMLP,
    ParallelTransformerLayer,
    ParallelTransformer,
    TransformerConfig,
)
from apex_tpu.models.gpt import (  # noqa: F401
    GPTModel,
    block_diffusion_loss_fn,
    gpt_loss_fn,
)
from apex_tpu.models.generation import (  # noqa: F401
    beam_search,
    generate,
    init_cache,
    init_params_tp,
    prefill_prefix,
    sample_logits,
    speculative_generate,
    tensor_parallel_beam_search,
    tensor_parallel_generate,
    verify_step,
)
from apex_tpu.models.tp_split import (  # noqa: F401
    split_mla_params_for_tp,
    split_params_for_tp,
    split_t5_params_for_tp,
)
from apex_tpu.models.t5 import (  # noqa: F401
    T5Config,
    T5Model,
    t5_beam_generate,
    t5_cached_generate,
    t5_greedy_generate,
    t5_loss_fn,
    tensor_parallel_t5_generate,
)
from apex_tpu.models.reshard import (  # noqa: F401
    load_checkpoint_for_3d,
    load_moe_checkpoint_for_ep,
    split_gpt_params_for_pp,
    split_moe_params_for_ep,
)
from apex_tpu.models.bert import BertModel, bert_loss_fn  # noqa: F401
from apex_tpu.models.resnet import ResNet, ResNet18, ResNet50  # noqa: F401
from apex_tpu.models.dcgan import Discriminator, Generator  # noqa: F401
from apex_tpu.models.vit import (  # noqa: F401
    ViTModel,
    vit_config,
    vit_loss_fn,
)
from apex_tpu.models.whisper import (  # noqa: F401
    WhisperConfig,
    WhisperModel,
    whisper_beam_generate,
    whisper_cached_generate,
    whisper_greedy_generate,
)
from apex_tpu.models.mla import (  # noqa: F401
    DeepseekModel,
    MLAConfig,
    mla_cached_generate,
    mla_greedy_generate,
)
