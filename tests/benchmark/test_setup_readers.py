"""The six readers under ``setup_s`` (``benchmark/setup_phases.py`` and
``layer_metrics/setup_*.py``): the hand-worked numbers over a hand-made
record list, the identity that the named phases' union and the
unattributed rest add up to ``setup_s`` (hand-made and over a tiny cell's
own records), nothing on a program without the record, and the entries in
``BENCHMARK.json``."""

import collections
import json

import pytest

from bench_tiny import REPO, any_device
from benchmark import harness, setup_phases

Rec = collections.namedtuple("Rec", "phase fun_name start end thread "
                                    "cache_hit")
READERS = {"setup_import_s": "import_s", "setup_trace_s": "trace_s",
           "setup_lower_s": "lower_s", "setup_compile_s": "compile_s",
           "setup_cache_misses": "cache_misses",
           "setup_unattributed_s": "unattributed_s"}
LAYERS = {"setup_import_s": "package import",
          "setup_unattributed_s": "set-up"}

# a process that started at 100.0 and opened its window 20 s later
START, SETUP_S = 100.0, 20.0
RECORDS = [
    Rec("import", "apex_tpu", 101.0, 103.0, 1, None),
    # an eager constant while the package was being imported
    Rec("compile", "jit(iota)", 102.5, 102.75, 1, True),
    # an inner jit's trace inside its caller's: 4 s, not 4 + 1 + 0.5
    Rec("trace", "inner", 105.0, 106.0, 1, None),
    Rec("trace", "inner", 107.0, 107.5, 1, None),
    Rec("trace", "step", 104.0, 108.0, 1, None),
    Rec("lower", "jit(step)", 108.5, 110.5, 1, None),
    Rec("compile", "jit(step)", 110.5, 113.5, 1, False),
    # another thread's compile beside the main thread's lowering
    Rec("compile", "jit(init)", 109.0, 110.0, 2, False),
    Rec("compile", "jit(norms)", 114.0, 114.5, 1, True),
    # after the opening (the readers run after the window and the
    # reference; scopes.py lowers the step again by then)
    Rec("trace", "step", 130.0, 134.0, 1, None),
    Rec("compile", "jit(step)", 136.0, 137.0, 1, False),
]
WANT = {"import_s": 2.0, "trace_s": 4.0, "lower_s": 2.0,
        "compile_s": 0.25 + 1.0 + 3.0 + 0.5, "cache_misses": 2,
        # [101, 103] + [104, 108] + [108.5, 113.5] + [114, 114.5]
        "named_s": 2.0 + 4.0 + 5.0 + 0.5,
        "unattributed_s": 20.0 - 11.5, "records": 9, "opening": 120.0}


def ctx(values=None):
    return {"values": {"setup_s": SETUP_S} if values is None else values}


@pytest.fixture
def hand_made(monkeypatch):
    """The program's record, hand-made."""
    def use(records=RECORDS, start=START):
        monkeypatch.setattr(
            setup_phases, "_record",
            lambda: (lambda: list(records), lambda: start))
    return use


def read(name, context):
    return harness.load_reader(name, REPO)(context)


def test_split_gives_the_hand_worked_numbers():
    assert setup_phases.split(RECORDS, START, SETUP_S) == pytest.approx(WANT)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_worked_number(name, hand_made):
    hand_made()
    assert read(name, ctx()) == pytest.approx(WANT[READERS[name]])


def test_the_union_is_smaller_than_the_sum():
    got = setup_phases.split(RECORDS, START, SETUP_S)
    total = sum(r.end - r.start for r in RECORDS if r.end <= 120.0)
    assert got["named_s"] < total
    assert got["trace_s"] < 4.0 + 1.0 + 0.5


def test_named_phases_and_the_rest_add_up_to_setup_s(hand_made):
    hand_made()
    c = ctx()
    assert (setup_phases.reading(c, "named_s")
            + read("setup_unattributed_s", c)) == pytest.approx(SETUP_S)
    # phases that overlap (a compile inside the import, two threads)
    # make the four unions add up to more than their union, never less
    four = sum(read(n, c) for n in ("setup_import_s", "setup_trace_s",
                                    "setup_lower_s", "setup_compile_s"))
    assert four >= setup_phases.reading(c, "named_s")
    assert all(read(n, c) >= 0 for n in READERS)


def test_the_clock_starts_where_the_running_script_s_does(monkeypatch,
                                                          hand_made):
    """``run.py`` and ``tools/block_parts.py`` count ``setup_s`` from
    their ``_T0``; a process whose ``__main__`` has none (this one) from
    its own start."""
    import sys

    assert setup_phases.clock_start(lambda: START) == START
    monkeypatch.setattr(sys.modules["__main__"], "_T0", START + 0.25,
                        raising=False)
    assert setup_phases.clock_start(lambda: START) == START + 0.25
    hand_made()
    got = setup_phases.phases(ctx())
    assert got["opening"] == pytest.approx(START + 0.25 + SETUP_S)
    assert got["named_s"] + got["unattributed_s"] == pytest.approx(SETUP_S)


def test_a_record_from_before_the_process_start_is_cut_to_it():
    early = [Rec("trace", "f", 99.0, 101.0, 1, None)]
    got = setup_phases.split(early, START, SETUP_S)
    assert got["trace_s"] == pytest.approx(1.0)
    assert got["unattributed_s"] == pytest.approx(19.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_the_record(name, monkeypatch,
                                                 hand_made):
    """A program from before the record (``compile_watch`` has no
    ``phase_records``), a platform with no ``/proc``, a line with no
    ``setup_s``: nothing, and no exception."""
    from apex_tpu.telemetry import compile_watch

    hand_made(start=None)
    assert read(name, ctx()) is None
    hand_made()
    assert read(name, ctx(values={})) is None
    assert read(name, {}) is None
    monkeypatch.undo()
    monkeypatch.delattr(compile_watch, "phase_records")
    assert setup_phases._record() is None
    assert read(name, ctx()) is None


def test_a_tiny_cell_s_own_records(tiny_root):
    """A traced tiny run whose clock starts where the readers place the
    process's start, as ``run.py``'s does to within its interpreter's
    start-up: the line holds all six, each at least 0, and the identity
    holds over the program's own records."""
    from apex_tpu.telemetry import compile_watch
    from benchmark import train_cell

    cell = harness.load_cell("gpt2_345m_train", tiny_root)
    kept = {}
    line = harness.result_line

    def keep(cell, outcome, values, *rest):
        kept["values"] = values
        return line(cell, outcome, values, *rest)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", any_device)
        mp.setattr(harness, "result_line", keep)
        result, _ = train_cell.run(
            cell, 5, 0.3, True,
            harness.Clock(compile_watch.process_start_perf()))
    metrics = result["metrics"]
    assert set(READERS) <= set(metrics)
    assert all(metrics[n]["value"] >= 0 for n in READERS)
    assert metrics["setup_cache_misses"]["unit"] == "count"
    setup_s = kept["values"]["setup_s"]
    got = setup_phases.split(compile_watch.phase_records(),
                             compile_watch.process_start_perf(), setup_s)
    assert got["named_s"] + metrics["setup_unattributed_s"]["value"] \
        == pytest.approx(setup_s)
    # this process traced, lowered and compiled the tiny step before the
    # window opened, and imported the package
    for name in ("setup_import_s", "setup_trace_s", "setup_lower_s",
                 "setup_compile_s"):
        assert metrics[name]["value"] > 0, name
    assert metrics["setup_unattributed_s"]["value"] < setup_s


def test_entries_in_benchmark_json():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "host_clock"
        assert m["unit"] == ("count" if name == "setup_cache_misses"
                             else "s")
        assert m["layer"] == LAYERS.get(name, "compile")
        assert m["workloads"] == cells
        assert callable(harness.load_reader(name, REPO))
    # appended: the entries that were there keep their places
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        "setup_import_s", "setup_trace_s", "setup_lower_s",
        "setup_compile_s", "setup_cache_misses", "setup_unattributed_s"]
    for workload in cells:
        cell = harness.load_cell(workload, REPO)
        assert set(READERS) <= {m["name"] for m in cell.per_layer}
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
