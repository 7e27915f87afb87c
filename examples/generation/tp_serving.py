"""Tensor-parallel serving walkthrough: one checkpoint, many chips.

Flow: build (or HF-convert, tools/convert_hf_*.py) a tp=1 GPT, split its
params into per-rank shards, and decode with the KV-cache loop running
inside shard_map over the 'tp' mesh axis — sampling and beam search both
see the full vocabulary via the per-step tp all-gather, and every rank
emits identical tokens.

Run (8-way virtual CPU mesh for demonstration):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/generation/tp_serving.py
"""

import os
import sys

_d = os.path.dirname(os.path.abspath(__file__))
while _d != os.path.dirname(_d) and not os.path.isdir(os.path.join(_d, "apex_tpu")):
    _d = os.path.dirname(_d)
sys.path.insert(0, _d)  # repo root (walk up: examples may be nested)

import jax
import numpy as np

if "--xla_force_host_platform_device_count" in os.environ.get(
        "XLA_FLAGS", ""):
    # the demo run line asks for the virtual CPU mesh; without it the
    # example runs on whatever accelerator jax finds, or fails there
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

from apex_tpu.models import (
    GPTModel,
    TransformerConfig,
    split_params_for_tp,
    tensor_parallel_beam_search,
    tensor_parallel_generate,
)
from apex_tpu.transformer import parallel_state


def main():
    # largest tp <= 4 that divides the K/V groups (split_params_for_tp
    # validates divisibility) and fits the visible devices
    n_dev = len(jax.devices())
    tp = max(t for t in (1, 2, 4) if t <= n_dev and 4 % t == 0)
    cfg = TransformerConfig(
        hidden_size=256, num_layers=4, num_attention_heads=8,
        vocab_size=1024, max_position_embeddings=256,
        compute_dtype=jnp.bfloat16, use_flash_attention=False,
        position_embedding_type="rope", activation="swiglu",
        normalization="rmsnorm", num_query_groups=4)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, (2, 16)))

    # a tp=1 checkpoint (stand-in for an HF-converted one)
    parallel_state.initialize_model_parallel(devices=jax.devices()[:1])
    model = GPTModel(cfg, decode=True)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    parallel_state.destroy_model_parallel()

    # split once, serve sharded
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=tp, devices=jax.devices()[:tp])
    shards = split_params_for_tp(cfg, params, tp)

    out = tensor_parallel_generate(
        GPTModel(cfg, decode=True), shards, prompt, max_new_tokens=32,
        mesh=mesh, rng=jax.random.PRNGKey(1), temperature=0.8, top_p=0.95)
    print(f"tp={tp} sampled: {np.asarray(out[0, 16:26])}...")

    seqs, scores = tensor_parallel_beam_search(
        GPTModel(cfg, decode=True), shards, prompt, max_new_tokens=16,
        num_beams=4, mesh=mesh, length_penalty=0.9)
    print(f"tp={tp} beam-4:  {np.asarray(seqs[0, 16:26])}...  "
          f"scores {np.asarray(scores)}")


if __name__ == "__main__":
    main()
