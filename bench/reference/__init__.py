"""Plain references of the backbone families, one module per family.

Each module has ``init(cfg, key)``, which makes a configuration's weights
from a seed in the layout the program's forward reads, ``embed(params,
cfg, tokens, kind)``, the frozen backbone's mean-pooled embedding in plain
``jax.numpy`` and float32, with every product at the precision ``kind``
(see ``bench.precision``), and ``forward_ops(cfg, seq)``, the operations of
that forward for one sequence (``bench.flops``). They import nothing of the
program. A new family joins the benchmark as a new module here.
"""

from __future__ import annotations

import importlib


def of(cfg: dict):
    """The module of the configuration's family, found by its name; a
    family with no module is refused."""
    name = f"{__name__}.{cfg['family']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"no reference module for family "
                         f"{cfg['family']!r}") from None
