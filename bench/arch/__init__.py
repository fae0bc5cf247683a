"""One module per architecture, ``bench/arch/<arch>.py``, found by the
``"arch"`` key of a configuration file.

An architecture module holds everything of the benchmark that depends on
the model's layers, so a new architecture comes as a new file:

- ``shapes(c)``: leaf shapes of the weight tree, by path, which has to
  match the program's parameter layout;
- ``make(c, key, shardings=None)``: the weights from the seed, made on
  the device in one jitted call, laid out by ``shardings`` where given;
- ``program_config(T, c)``: the program's ``ModelConfig``
  (``T`` is ``repro.models.transformer``);
- ``logits(params, c, tokens, rows, mode)``: the plain reference in
  float32 (``mode="float32"``) and its control (``mode="fp8"``);
- ``layer_matmul_params(c)`` and ``kv_bytes(c, rows)``: the counts of
  ``bench/work.py`` that depend on the layers' shapes.
"""

from __future__ import annotations

import importlib
import re

EXPORTS = ("shapes", "make", "program_config", "logits",
           "layer_matmul_params", "kv_bytes")
NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def load(c: dict):
    """The module named by the configuration's ``"arch"``."""
    name = c.get("arch")
    if name is None:
        raise SystemExit(f"configuration {c.get('name')!r} names no "
                         f"\"arch\" (a module in bench/arch)")
    if not isinstance(name, str) or not NAME.match(name):
        raise SystemExit(f"configuration {c.get('name')!r}: {name!r} is "
                         f"not the name of a module in bench/arch")
    try:
        mod = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise SystemExit(f"configuration {c.get('name')!r}: no "
                         f"bench/arch/{name}.py") from None
    missing = [f for f in EXPORTS if not hasattr(mod, f)]
    if missing:
        raise SystemExit(f"bench/arch/{name}.py lacks {', '.join(missing)}")
    return mod
