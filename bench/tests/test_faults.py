"""Each fault a local cell can have, planted under the timed path, makes
``correct`` come out false."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny
from repro.core import engine
from repro.models import transformer


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(engine.AnalyticEngine, "update",
                        lambda self, stats, x, y: stats)


def _half_batch_mean_of_rest(monkeypatch):
    real = engine.JaxBackend.gram_update

    def half(self, x, y):
        n = x.shape[0] // 2
        g, q, _ = real(self, x[:n], y[:n])
        return 2 * g, 2 * q, jnp.asarray(x.shape[0], self.dtype)

    monkeypatch.setattr(engine.JaxBackend, "gram_update", half)


def _pool_over_half(monkeypatch):
    monkeypatch.setattr(transformer, "pool",
                        lambda h: jnp.mean(h[:, : h.shape[1] // 2], axis=1))


def _token_altered(monkeypatch):
    """The last token of every sequence read as its neighbour id."""
    real = transformer.embed_inputs

    def altered(params, cfg, batch):
        toks = batch["tokens"]
        toks = toks.at[:, -1].set((toks[:, -1] + 1) % cfg.vocab_size)
        return real(params, cfg, dict(batch, tokens=toks))

    monkeypatch.setattr(transformer, "embed_inputs", altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch_mean_of_rest": _half_batch_mean_of_rest,
          "pool_over_half": _pool_over_half,
          "token_altered": _token_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    tiny.no_cache(monkeypatch)
    FAULTS[fault](monkeypatch)
    res = tiny.run(tiny.cell("xlstm_350m.local"), jax.devices()[:1])
    assert not res.correct, res.check_lines()


_MESH_RUN = textwrap.dedent("""
    import sys
    import jax
    from bench.tests import tiny
    from bench import harness
    from repro.kernels import ops, gram

    harness.use_compile_cache = lambda *a: None
    if sys.argv[1] == "no_exchange":
        def fold(mesh, rows, kw_items):
            kw = dict(kw_items)
            return jax.jit(jax.shard_map(
                lambda xs, ys: gram.gram_update(xs, ys, **kw), mesh=mesh,
                in_specs=(jax.P(rows), jax.P(rows)), out_specs=jax.P(),
                check_vma=False))
        ops._sharded_gram = fold
    res = tiny.run(tiny.cell("xlstm_350m.local", chips=4, batch=16),
                   jax.devices()[:4])
    print("\\n".join(res.check_lines()))
    print("CORRECT", res.correct)
""")


@pytest.mark.parametrize("mode,want", [("sound", True), ("no_exchange", False)])
def test_exchange_between_chips(mode, want):
    """A cell on four chips, here four virtual devices: a sound run passes;
    leaving out the psum that joins the chips' partial statistics fails."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    out = subprocess.run([sys.executable, "-c", _MESH_RUN, mode], env=env,
                         capture_output=True, text=True, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"CORRECT {want}" in out.stdout, out.stdout + out.stderr[-2000:]
