"""How the benchmark drives the program under test: the train step a
cell times and its state from a seed. What differs by model family (the
model, its loss, how the benchmark's weights map onto the program's tree)
is in ``benchmark/families/<family>.py``, what differs by optimizer in
``benchmark/optimizers/<name>.py``. The yardstick (traffic, FLOP counts,
trace reduction, references, the comparison) imports nothing from
``apex_tpu``.

Train cells drive the README quick-start step as ``chip_smoke.py`` has
it: ``amp.initialize(params, Fused*, "O2")`` and a jitted, donating step
of ``value_and_grad`` + ``opt.step``. Serve cells drive
``Scheduler.submit`` / ``Scheduler.step`` over a ``ServeEngine``
(``serve_cell.py``).
"""

import jax

from benchmark import families, optimizers, weights

AMP_LEVEL = "O2"    # bf16 compute and model weights, float32 masters


def make_optimizer(mix: dict):
    return optimizers.of(mix["optimizer"]).program(mix["hp"])


def make_train_step(model, opt, arch: dict, sync=None):
    """The README quick-start step (``chip_smoke.make_train_step``), over
    a batch dict; ``sync`` averages gradients over the data axis."""
    loss = families.of(arch).loss(model)

    def train_step(params, opt_state, batch):
        scale = opt_state["scaler"].loss_scale
        value, grads = jax.value_and_grad(
            lambda p: loss(p, batch) * scale)(params)
        if sync is not None:
            grads = sync(grads)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, value / scale

    return train_step


class TrainProgram:
    """The compiled step and how to make its state from a seed: weights
    in one jitted call (``weights.make_on_device``), laid out as the
    program's tree in a second, cast and wrapped by ``amp.initialize`` O2,
    the step jitted with donation. With ``mesh`` (``{"data": n}``) the step
    runs under ``shard_map`` with ``DistributedDataParallel`` and the
    batch is split over the axis."""

    def __init__(self, arch: dict, mix: dict, mesh=None):
        from apex_tpu import amp

        self.arch, self.mix = arch, mix
        family = families.of(arch)
        self.model = family.build_model(arch, mix)
        # amp.initialize casts a tree and wraps the optimizer; the
        # wrapper does not depend on the weights, so the step is built
        # once around this one and init_state only keeps the cast tree
        _, self.opt = amp.initialize({}, make_optimizer(mix),
                                     opt_level=AMP_LEVEL, verbosity=0)
        self._to_program = jax.jit(
            lambda canon: family.to_program(canon, arch))
        self._opt_init = jax.jit(self.opt.init)
        self.batch_sharding = self._state_sharding = None
        if not mesh:
            self.step = jax.jit(make_train_step(self.model, self.opt, arch),
                                donate_argnums=(0, 1))
            return

        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from apex_tpu.parallel import DistributedDataParallel

        (axis, n), = mesh.items()
        devices = jax.devices()[:n]
        if len(devices) < n:
            raise RuntimeError(f"mesh {mesh} needs {n} devices, "
                               f"jax has {len(devices)}")
        jmesh = Mesh(np.asarray(devices), (axis,))
        local = make_train_step(
            self.model, self.opt, arch,
            sync=DistributedDataParallel(axis_name=axis).sync)

        def spmd(params, opt_state, batch):
            params, opt_state, loss = local(params, opt_state, batch)
            return params, opt_state, jax.lax.pmean(loss, axis)

        self.step = jax.jit(jax.shard_map(
            spmd, mesh=jmesh, in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0, 1))
        self._state_sharding = NamedSharding(jmesh, P())
        self.batch_sharding = NamedSharding(jmesh, P(axis))

    def init_state(self, seed: int):
        """-> (params, opt_state) from ``seed``."""
        from apex_tpu import amp

        params, _ = amp.initialize(
            self._to_program(weights.make_on_device(self.arch, seed)),
            make_optimizer(self.mix), opt_level=AMP_LEVEL, verbosity=0)
        opt_state = self._opt_init(params)
        if self._state_sharding is not None:
            params, opt_state = jax.device_put((params, opt_state),
                                               self._state_sharding)
        return params, opt_state


def masters(opt_state):
    """The float32 parameters the optimizer steps (amp O2's masters)."""
    return opt_state["inner"]["amp_master"]
