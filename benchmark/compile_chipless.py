#!/usr/bin/env python3
"""Hand-run rehearsal: compile a train cell's step, and its reference's
gradient block, at the real size for a described ``v5e:2x2`` without the
chip. Not a test (``tests/L0/test_tpu_lowering.py`` is the one test file
that may load libtpu); nothing runs, so it says nothing about results or
times. A refusal here (a block shape, fast memory, HBM) costs no chip
time.

    JAX_PLATFORMS=cpu python3 benchmark/compile_chipless.py --workload gpt2_345m_train
"""

import argparse
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reference", type=int, default=1)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import apex_tpu.kernels.registry as kreg
    from apex_tpu import amp
    from benchmark import families, harness, program, weights
    from benchmark.reference import family as load_family

    kreg._on_tpu = lambda: True    # the gates ask this; steer it here
    cell = harness.load_cell(args.workload)
    arch, mix = cell.arch, cell.mix
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    family = families.of(arch)
    first_batch = next(families.batches(arch, mix, 0))
    batch = described(first_batch)
    model = family.build_model(arch, mix)
    shapes = jax.eval_shape(lambda: family.to_program(
        weights.make(weights.seed_key(0), arch), arch))
    cast = jax.eval_shape(lambda p: amp.frontend.cast_model(
        p, jnp.bfloat16, keep_batchnorm_fp32=True), shapes)
    _, opt = amp.initialize({}, program.make_optimizer(mix),
                            opt_level=program.AMP_LEVEL, verbosity=0)
    state = jax.eval_shape(opt.init, cast)
    t0 = time.perf_counter()
    compiled = jax.jit(program.make_train_step(model, opt, arch),
                       donate_argnums=(0, 1)).lower(
        described(cast), described(state), batch).compile()
    print(f"train step compiled in {time.perf_counter() - t0:.1f} s; "
          f"tpu_custom_calls {compiled.as_text().count('tpu_custom_call')}")
    print(compiled.memory_analysis())

    if args.reference:
        fam = load_family(arch["family"])
        rows = mix.get("reference_block_rows", 2)
        block = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                           sharding=chip), batch)
        canon = described(jax.eval_shape(
            lambda: weights.make(weights.seed_key(0), arch)))
        totals = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
                  for k in fam.totals(first_batch)}
        t0 = time.perf_counter()
        ref = jax.jit(lambda p, b, t: jax.value_and_grad(
            lambda q: fam.loss_part(q, arch, b, t))(p)).lower(
            canon, block, totals).compile()
        print(f"reference block compiled in {time.perf_counter() - t0:.1f} s")
        print(ref.memory_analysis())


if __name__ == "__main__":
    main()
