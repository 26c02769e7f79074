"""Every Pallas kernel of the main path compiles for a TPU v5e at real width.

The interpret-mode parity tests cannot see what Mosaic refuses (values
indexed by a loop counter, more VMEM than the scoped limit), so each kernel
here is compiled for a v5e that is described, not attached: the TPU
compiler is installed, and a compile takes a second or two. The topology is
described inside a fixture, never at import, and every test of the file
skips from there where it cannot be described. ``flash_attention`` is left
out: no model path calls it.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gram, solve


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it refuses the topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# name → (kernel called with interpret=False, argument shapes)
KERNELS = {
    "gram_update_d1024": (gram.gram_update, [(4096, 1024), (4096, 16)]),
    "gram_update_d1536": (gram.gram_update, [(4096, 1536), (4096, 16)]),
    "panel_factor_b256": (solve.panel_factor, [(256, 256)]),
    "panel_tri_inv_b256": (solve.panel_tri_inv, [(256, 256)]),
    "panel_trsm_b256": (solve.panel_trsm, [(1024, 256), (256, 256)]),
    "panel_update_b256": (solve.panel_update,
                          [(1024, 768), (1024, 256), (768, 256)]),
    "blocked_cholesky_d1024": (solve.blocked_cholesky, [(1, 1024, 1024)]),
    "cholesky_solve_d1024": (solve.cholesky_solve,
                             [(1, 1024, 1024), (1, 1024, 16)]),
    "multi_gamma_solve_d1024": (solve.multi_gamma_solve,
                                [(1024, 1024), (1024, 16), (8,)]),
    "chol_rank_update_d1024": (solve.chol_rank_update,
                               [(1024, 1024), (8, 1024)]),
    "streamed_cholesky_d2048": (solve.streamed_cholesky, [(2048, 2048)]),
    "streamed_cholesky_solve_d2048": (solve.streamed_cholesky_solve,
                                      [(2048, 2048), (2048, 16)]),
}


# the kernels a wrapper runs, where they are not the wrapper itself
RUNS = {"streamed_cholesky_d2048": {"panel_factor", "panel_trsm",
                                    "panel_update"},
        "streamed_cholesky_solve_d2048": {"panel_tri_inv"}}


@functools.lru_cache(maxsize=None)
def _compiled_text(one_chip, name):
    kernel, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(
        lambda *a: kernel(*a, interpret=False)).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    assert "tpu_custom_call" in _compiled_text(one_chip, name)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_keeps_its_name(one_chip, name):
    """Each kernel's custom call is named after it (``name=`` of its
    ``pallas_call``), so a device trace finds it by name however the
    compiler numbers it (``%gram_update.1``)."""
    text = _compiled_text(one_chip, name)
    calls = re.findall(r"^\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = [^\n]*"
                       r"custom-call\(", text, re.M)
    assert calls and set(calls) == RUNS.get(name, {KERNELS[name][0].__name__})
