"""Plain float32 references of the served model families, one module per
family (``dense``, ``ssm``), found by the ``family`` of a configuration
file. Each module gives:

* ``PROGRAM_KEYS``: the configuration file's ``model`` keys and the
  program's ``ArchConfig`` fields they must equal;
* ``param_spec(model)``: every weight's path, shape and seeded initialiser,
  in the layout the serve engine takes as ``params``;
* ``logits(model, params, tokens, score_pos, quant)``: the forward pass over
  one sequence, in float32 at ``highest`` matmul precision, returning the
  logits at ``score_pos``. ``quant="int8"`` or ``"fp8"`` rounds both
  operands of every weight matmul to that precision (the control: a
  precision below bfloat16).

They import nothing of the program.
"""
import importlib


def family_module(family):
    return importlib.import_module(f"{__name__}.{family}")
