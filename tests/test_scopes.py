"""The names the client's local stage gives its layers (``repro.scopes``)
reach the compiled programs: each device scope is in the HLO op names of
the forward, the Gram fold and the psum, and the client's fold opens its
host spans on the profiler's clock."""

import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes as S
from repro.configs.registry import get_config
from repro.fl.api import AFLClient
from repro.kernels import gram
from repro.launch import mesh as M
from repro.launch import train as TR
from repro.models import transformer as T


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]+)"', compiled_text))


def _has(names: set, scope: str) -> bool:
    """Some op name holds the scope's names in a row."""
    want = scope.split("/")
    return any(p[i:i + len(want)] == want
               for p in (n.split("/") for n in names)
               for i in range(len(p)))


FORWARD = [S.EMBED, S.MIXER, f"{S.MIXER}/{S.SEQMIX}", S.FINAL_NORM, S.POOL]


@pytest.mark.parametrize("arch", ["minicpm_2b", "xlstm_350m"])
def test_forward_names_every_block(arch):
    cfg = get_config(arch).reduced(num_classes=4)
    params = T.init_params(jax.random.key(0), cfg)
    mesh = M.auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    embed = TR._embed_fn(params, cfg, mesh)
    names = _op_names(embed.lower(params, jnp.zeros((2, 8), jnp.int32))
                      .compile().as_text())
    for scope in FORWARD:
        assert _has(names, scope), (arch, scope)
    # xLSTM's blocks are projection-only (d_ff = 0): no feed-forward
    assert _has(names, S.FFN) == (cfg.d_ff > 0), arch
    # the mixing across the sequence sits inside a mixer, never alone
    assert not any(S.SEQMIX in n.split("/") and S.MIXER not in n.split("/")
                   for n in names)


def test_gram_program_is_named():
    x = jnp.ones((16, 64), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    names = _op_names(gram.gram_update.lower(x, y, interpret=True)
                      .compile().as_text())
    assert names and all(S.GRAM_FOLD in n.split("/") for n in names
                         if n.startswith("jit(gram_update)/"))


def test_psum_is_named_on_four_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent("""
        import re
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import ops
        mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                             axis_types=(jax.sharding.AxisType.Auto,))
        rows = NamedSharding(mesh, P("data"))
        x = jax.device_put(jnp.ones((32, 64), jnp.float32), rows)
        y = jax.device_put(jnp.ones((32, 4), jnp.float32), rows)
        fold = ops._sharded_gram(mesh, "data", (("interpret", True),))
        text = fold.lower(x, y).compile().as_text()
        for line in text.splitlines():
            if " all-reduce(" in line:
                print("ALLREDUCE", re.search(r'op_name="([^"]+)"', line)[1])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    found = re.findall(r"^ALLREDUCE (\S+)", out.stdout, re.M)
    assert found and all(S.GRAM_PSUM in n.split("/") for n in found), found


def test_fold_opens_its_host_spans(tmp_path):
    """``afl.fold`` around each update and ``afl.fold.root`` inside it
    while the client copies batches to the host (below d rows)."""
    from jax.profiler import ProfileData

    client = AFLClient(0, gamma=1.0, backend="jax")
    x = np.ones((4, 16), np.float32)        # 12 rows, below d = 16
    y = np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]
    client.update(x, y)                       # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        client.update(x, y)
        client.update(x, y)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = [e for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("afl.")]
    folds = [e for e in spans if e.name == S.FOLD_SPAN]
    roots = [e for e in spans if e.name == S.FOLD_ROOT_SPAN]
    assert len(folds) == 2 and len(roots) == 2
    assert all(any(f.start_ns <= r.start_ns and r.end_ns <= f.end_ns
                   for f in folds) for r in roots)
