"""Production mesh construction + TPU v5e hardware model.

Functions only (no module-level jax device access) so importing this module
never initializes the backend — ``dryrun.py`` must set XLA_FLAGS before the
first jax call, and smoke tests must keep seeing the real single CPU device.

Mesh layout (target: TPU v5e pods, 256 chips each):
  single-pod : (16, 16)    axes ('data', 'model')
  multi-pod  : (2, 16, 16) axes ('pod', 'data', 'model')

The 'pod'+'data' axes together form the *federation* axes for AFL: each shard
group along them plays a client cohort; the single aggregation round is one
all-reduce over them. 'model' carries tensor parallelism for the backbone.
"""

from __future__ import annotations

import dataclasses

import jax

# ----------------------------------------------------------------- hardware
# TPU v5e (target; this container lowers on CPU stand-in devices).
PEAK_FLOPS_BF16 = 197e12      # per chip, FLOP/s
HBM_BW = 819e9                # per chip, B/s
ICI_BW = 50e9                 # per link, B/s (~ per-chip collective bandwidth)
HBM_BYTES = 16 * 2**30        # 16 GiB per chip

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


def auto_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """Every mesh of the repo is built here, with all axes Auto.

    ``jax.make_mesh`` makes Explicit axes by default, and the activation
    policy's ``with_sharding_constraint`` (``core/act.py``) and the
    shard_map collectives are written for Auto ones. ``devices`` defaults
    to the first ``prod(shape)`` devices of the process.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """(n, 1) mesh over every device of the process: 'data' spans them."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes that shard the batch / act as AFL federation axes."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def model_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("model",) if a in mesh.shape)


def num_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size


@dataclasses.dataclass(frozen=True)
class Roofline:
    """Three-term roofline for one compiled step on this mesh."""

    flops: float
    hbm_bytes: float
    collective_bytes: float
    chips: int

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS_BF16)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * ICI_BW)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }
