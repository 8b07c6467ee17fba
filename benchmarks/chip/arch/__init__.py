"""One module per architecture, found from a configuration's own
``model_type`` (the key of the published ``config.json``):
``arch/<model_type>.py``.

Each such module provides what the generic harness needs of a model and
nothing outside this package knows:

* ``shapes(cfg)``: leaf name -> (shape, init kind), the weights layout that
  ``weights.make`` builds from the seed;
* ``program(cfg, name)``: the program's ``ModelConfig``, raising
  ``ProgramFault`` for what the program lacks;
* ``check_traffic(cfg, mix, max_len)``: raises ``ValueError`` where the
  traffic reaches what the program's path for this architecture cannot
  serve as the configuration states;
* ``logits_at(w, tokens, rows, cfg, quant=None)`` and
  ``loss(w, tokens, labels, cfg)``: the plain float32 reference at
  ``precision="highest"`` (its file under ``reference/``), with the
  control at ``quant="fp8"``;
* ``matmul_params_per_token(cfg)``: the parameters of every matrix one
  token is multiplied by, what ``mfu`` counts (for sparse experts, only
  those a token is routed to).
"""

from __future__ import annotations

import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent


class MissingArch(LookupError):
    """The benchmark has no module for a configuration's architecture: a
    fault of the benchmark, not of the program."""


class ProgramFault(RuntimeError):
    """The program could not run the cell as configured."""


def load(cfg: dict):
    """The module of ``cfg``'s architecture, ``arch/<model_type>.py``."""
    model_type = cfg.get("model_type")
    path = HERE / f"{model_type}.py"
    if not (isinstance(model_type, str) and model_type.isidentifier()
            and not model_type.startswith("_") and path.is_file()):
        raise MissingArch(f"model_type {model_type!r}: the benchmark expects "
                          f"its architecture in {path}, which does not exist")
    return importlib.import_module(f"{__name__}.{model_type}")
