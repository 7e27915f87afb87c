"""The FLOP counts against hand-worked values for both configurations."""

import json

import pytest

from benchmark import families, flops, weights
from bench_tiny import REPO


def arch_of(name):
    config = json.loads(
        (REPO / "benchmark" / "configs" / f"{name}.json").read_text())
    return families.of(config["family"]).arch(config)


def test_gpt2_345m_flops_per_token():
    arch = arch_of("gpt2-345m")
    # per layer 4*1024^2 (qkv, out) + 2*1024*4096 (mlp) = 12,582,912;
    # 24 layers 301,989,888; head 1024*50304 = 51,511,296
    assert flops.matmul_params(arch) == 301_989_888 + 51_511_296
    fwd = 2 * 353_501_184 + 4 * 1024 * 1024 * 24      # + attention
    assert flops.fwd_flops_per_token(arch, 1024) == fwd == 807_665_664
    assert flops.train_flops_per_token(arch, 1024) / 1e9 == \
        pytest.approx(2.42, abs=0.005)


def test_bert_large_flops_per_token():
    arch = arch_of("bert-large")
    # 24 layers 301,989,888; head 1024*30528 = 31,260,672; MLM dense 1024^2
    assert flops.matmul_params(arch) == 301_989_888 + 31_260_672 + 1_048_576
    assert flops.train_flops_per_token(arch, 128) / 1e9 == \
        pytest.approx(2.04, abs=0.005)


def test_attention_flops_per_step():
    arch = arch_of("gpt2-345m")
    full = flops.attention_train_flops_per_step(arch, 16, 1024, False)
    # forward 4*s^2*h per row and layer, backward twice that
    assert full == 3 * 4 * 1024 ** 2 * 1024 * 16 * 24
    assert flops.attention_train_flops_per_step(arch, 16, 1024, True) \
        == full / 2


def test_serve_flops_from_contexts():
    arch = arch_of("gpt2-345m")
    one = flops.serve_flops(arch, [10])
    assert one == 2 * flops.matmul_params(arch) + 4 * 1024 * 24 * 10
    assert flops.serve_flops(arch, [10, 20]) == \
        2 * 2 * flops.matmul_params(arch) + 4 * 1024 * 24 * 30


@pytest.mark.parametrize("name,millions", [("gpt2-345m", 355),
                                           ("bert-large", 367)])
def test_parameter_count_is_the_published_size(name, millions):
    # GPT-2 medium: 355M with the padded table; Megatron BERT-large with
    # an untied MLM matrix: 336M + 31M
    assert weights.n_params(arch_of(name)) / 1e6 == \
        pytest.approx(millions, abs=1.0)
