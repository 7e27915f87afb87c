"""Enums. Parity: reference apex/transformer/enums.py:18-35."""

import enum


class LayerType(enum.Enum):
    encoder = 1
    decoder = 2


class AttnType(enum.Enum):
    self_attn = 1
    cross_attn = 2


class AttnMaskType(enum.Enum):
    padding = 1
    causal = 2
    # beyond the reference: block diffusion over a row of clean tokens and
    # their noised copies (contrib/fmha.py ``_BlockDiffusion``)
    block_diffusion = 3


class ModelType(enum.Enum):
    encoder_or_decoder = 1
    encoder_and_decoder = 2
