"""GPT-2 345M MFU sweep: which knobs move tokens/sec on the real chip?

Thin driver over ``bench.bench_gpt2`` (one engine — sweep numbers stay
comparable to the flagship ``bench.py gpt2`` metric). One variant per
invocation (a fresh process per point keeps an OOM in one variant from
killing the sweep), or ``all`` to print the plan as shell commands:

    python tools/mfu_sweep.py all          # print the plan
    python tools/mfu_sweep.py base         # flash on, remat off, batch 8
    python tools/mfu_sweep.py noflash
    python tools/mfu_sweep.py scan         # scan_layers=True
    python tools/mfu_sweep.py b16 | b32    # batch sweep
    python tools/mfu_sweep.py remat        # per-layer recompute back ON
    python tools/mfu_sweep.py xent         # fused-xentropy loss path

Each point prints one JSON line (tokens/sec, ms/step, TFLOP/s, MFU).
One process per chip: run the points one after another, never side by
side.
CPU smoke: APEX_TPU_SWEEP_TINY=1 JAX_PLATFORMS=cpu python tools/mfu_sweep.py <v>
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

VARIANTS = {
    "base":    {},
    "noflash": {"flash": False},
    "scan":    {"scan": True},
    "b16":     {"batch": 16},
    "b32":     {"batch": 32},
    "remat":   {"remat": True},   # per-layer activation recompute ON
    "xent":    {"loss": "xent"},
}


def run(name):
    from apex_tpu._compile_cache import enable_compile_cache
    from bench import bench_gpt2

    enable_compile_cache()

    v = dict(VARIANTS[name])
    tiny = os.environ.get("APEX_TPU_SWEEP_TINY") == "1"
    batch = v.pop("batch", 2 if tiny else 8)
    steps = 2 if tiny else 20
    t0 = time.perf_counter()
    result = bench_gpt2(batch, steps, tiny=tiny, emit=False, **v)
    result.update(variant=name,
                  total_incl_compile_s=round(time.perf_counter() - t0, 1))
    print(json.dumps(result), flush=True)


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "base"
    if name == "all":
        for n in VARIANTS:
            print(f"python tools/mfu_sweep.py {n}")
        return
    if name not in VARIANTS:
        raise SystemExit(f"unknown variant {name!r}; one of {list(VARIANTS)}")
    run(name)


if __name__ == "__main__":
    main()
