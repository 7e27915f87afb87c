"""One module per model family, found by the ``family`` a configuration
file names: ``benchmark/families/<family>.py``. A later PR brings a new
family as a new file here, with its plain reference beside it in
``benchmark/reference/<family>.py``; nothing that is there is edited.

What a family module supplies:

- ``arch(config)``: the configuration file's sizes under the names the
  module's own functions, its reference and the FLOP counts read; always
  with ``family``, ``vocab`` and ``vocab_real``, and ``heads`` where the
  fused QKV layout has to be split per tensor;
- ``shapes(arch)``: canonical tensor name -> shape (``weights.py`` makes
  them from the seed; per-layer tensors are stacked under ``layers.``);
- ``TASKS``: batch task name (a train mix's ``task``) -> generator
  ``(mix, arch, seed)`` of endless seeded batches;
- ``matmul_params(arch)`` and ``fwd_flops_per_token(arch, seq)``: what the
  algorithm needs, from shapes;
- towards the program (these alone import ``apex_tpu``, inside the
  function): ``to_program(canon, arch)`` and ``from_program(tree, arch)``
  between the canonical dict and the program's tree,
  ``build_model(arch, mix, decode=False)`` and ``loss(model)``, the
  ``(params, batch) -> loss`` the train step differentiates.
"""

import importlib


def of(arch_or_name):
    """The family module of an ``arch`` (or of a family's name)."""
    name = arch_or_name if isinstance(arch_or_name, str) \
        else arch_or_name["family"]
    return importlib.import_module(f"benchmark.families.{name}")


def batches(arch: dict, mix: dict, seed: int):
    """The endless seeded batch stream of a train mix, by its ``task``."""
    return of(arch).TASKS[mix["task"]](mix, arch, seed)
