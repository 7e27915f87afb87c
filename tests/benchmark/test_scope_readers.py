"""The per-layer readers that read device time by the program's own
blocks (``benchmark/scopes.py`` and the eight readers over it), on a
synthetic trace with a hand-made table: each gives the hand-worked
number, and nothing where there is no trace, no table or no such
operation."""

import pytest

from bench_tiny import REPO
from benchmark import harness, scopes
from benchmark.xplane import Op, Trace

# two steps in a ten-second window; every operation on device 0
OPS = [
    Op(0, "fusion.1", 0.0, 1.0, "fusion", "kLoop"),
    Op(0, "fusion.2", 1.0, 1.5, "fusion", "kLoop"),
    Op(0, "fusion.3", 1.5, 2.5, "fusion", "kLoop"),
    Op(0, "fusion.4", 2.5, 3.0, "fusion", "kLoop"),
    Op(0, "fusion.5", 3.0, 4.0, "fusion", "kOutput"),
    Op(0, "self_attention_flash_fwd.4", 4.0, 5.0, "custom-call"),
    Op(0, "self_attention_flash_fwd.6.remat", 5.0, 5.5, "custom-call"),
    Op(0, "self_attention_flash_dq.2", 5.5, 6.5, "custom-call"),
    Op(0, "self_attention_flash_dkv.2", 6.5, 7.0, "custom-call"),
    Op(0, "copy.10", 7.0, 7.5, "copy"),
    Op(0, "copy.11", 7.5, 8.0, "copy"),
    Op(0, "copy.12", 8.0, 8.25, "copy"),
    Op(0, "copy-done.1", 8.25, 8.5, "copy-done"),
    Op(0, "softmax_fwd.2", 8.5, 8.75, "custom-call"),
    Op(0, "softmax_bwd.3", 8.75, 9.0, "custom-call"),
    Op(0, "fusion.9", 9.5, 10.0, "fusion", "kLoop"),
]
BLOCKS = {
    "fusion.1": ("optimizer", "update"),
    "fusion.2": ("amp", "update"),
    "fusion.3": ("layernorm", "forward"),
    "fusion.4": ("layernorm", "recompute"),
    "fusion.5": ("mlp", "recompute"),
    "self_attention_flash_fwd.4": ("attention/kernel", "forward"),
    "self_attention_flash_fwd.6.remat": ("attention/kernel", "recompute"),
    "self_attention_flash_dq.2": ("attention/kernel", "backward"),
    "self_attention_flash_dkv.2": ("attention/kernel", "backward"),
    "softmax_fwd.2": ("attention/kernel", "forward"),
    "softmax_bwd.3": ("attention/kernel", "backward"),
    "copy.10": ("attention", "forward"),
    "copy.11": ("embedding", "forward"),
    "fusion.9": (None, "update"),     # traced outside every scope
    # copy.12 and copy-done.1: XLA gave them no scope
}
# device seconds / 2 steps, in ms; shares of the 10 s window or of the
# 9.5 s the device is busy
WANT = {
    "optimizer_ms_per_step": 500.0,
    "amp_ms_per_step": 250.0,
    "layernorm_ms_per_step": 750.0,
    "recompute_time_share_pct": 100.0 * (0.5 + 1.0 + 0.5) / 10.0,
    "attention_kernel_fwd_ms_per_step": 1e3 * (1.0 + 0.5 + 0.25) / 2,
    "attention_kernel_bwd_ms_per_step": 1e3 * (1.0 + 0.5 + 0.25) / 2,
    "attention_copy_ms_per_step": 250.0,
    "unscoped_time_share_pct": 100.0 * (0.25 + 0.25 + 0.5) / 9.5,
}
NEEDS_TABLE = set(WANT) - {"attention_kernel_fwd_ms_per_step",
                           "attention_kernel_bwd_ms_per_step"}


def ctx(ops=OPS, blocks=BLOCKS):
    tr = Trace(ops, []) if ops is not None else None
    return {"trace": tr, "window": {"steps": 2, "elapsed_s": 10.0},
            "scope_blocks": blocks}


def read(name, context):
    return harness.load_reader(name, REPO)(context)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_hand_worked_number(name):
    assert read(name, ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_without_a_trace(name):
    assert read(name, ctx(ops=None)) is None
    assert read(name, ctx(ops=[])) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_a_scope_table(name):
    """The parent of the PR that brought the table: block readers read
    nothing; the kernels are read by name, and the parent's have none."""
    got = read(name, ctx(blocks=None))
    if name in NEEDS_TABLE:
        assert got is None
    else:
        assert got == pytest.approx(WANT[name])
    unnamed = [Op(0, "self_attention.7", 0.0, 1.0, "custom-call"),
               Op(0, "fusion.1", 1.0, 2.0, "fusion", "kLoop")]
    assert read(name, ctx(ops=unnamed, blocks=None)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_where_no_such_operation_ran(name):
    other = [Op(0, "fusion.77", 0.0, 1.0, "fusion", "kOutput")]
    blocks = {"fusion.77": ("mlp", "forward")}
    assert read(name, ctx(ops=other, blocks=blocks)) is None


def test_block_times_sum_to_no_more_than_the_busy_time():
    c = ctx()
    tr = c["trace"]
    by_block = {}
    for op in tr.ops:
        block = scopes.block_of(c, op)[0]
        by_block[block] = by_block.get(block, 0.0) + op.end - op.start
    assert sum(by_block.values()) == pytest.approx(tr.busy_s())
    assert by_block[None] / tr.busy_s() * 100 == pytest.approx(
        WANT["unscoped_time_share_pct"])


@pytest.mark.parametrize("name,opcode,want", [
    ("self_attention_flash_dq.3", "custom-call", "self_attention_flash_dq"),
    ("softmax_fwd.12.remat2", "custom-call", "softmax_fwd"),
    ("softmax_bwd", "custom-call", "softmax_bwd"),
    ("self_attention_flash_dq.3", "fusion", ""),
])
def test_kernel_name_drops_xla_numbering(name, opcode, want):
    assert scopes.kernel_name(Op(0, name, 0.0, 1.0, opcode)) == want


def test_the_table_is_built_once_and_only_with_a_trace(monkeypatch):
    calls = []
    monkeypatch.setattr(scopes, "_build",
                        lambda cell: calls.append(cell) or dict(BLOCKS))
    c = {"trace": None, "window": {"steps": 2, "elapsed_s": 10.0},
         "cell": "the cell"}
    assert scopes.table(c) is None and not calls
    c["trace"] = Trace(OPS, [])
    assert scopes.table(c) == BLOCKS and scopes.table(c) == BLOCKS
    assert calls == ["the cell"]
