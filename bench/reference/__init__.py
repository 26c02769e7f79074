"""Plain references of the backbone families, one module per family.

Each module has ``init(cfg, key)``, which makes a configuration's weights
from a seed in the layout the program's forward reads, and ``embed(params,
cfg, tokens, kind)``, the frozen backbone's mean-pooled embedding in plain
``jax.numpy`` and float32, with every product at the precision ``kind``
(see ``bench.precision``). They import nothing of the program.
"""
