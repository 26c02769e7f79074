"""Sharding-rule unit tests against the production mesh *abstractly* (no
devices needed: AbstractMesh provides axis names/sizes for spec resolution)."""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.config import INPUT_SHAPES
from repro.configs.registry import get_config
from repro.launch import sharding as SH
from repro.models import transformer as T

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(arch, mesh):
    cfg = get_config(arch)
    p_shape = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    return cfg, p_shape, SH.param_specs(p_shape, mesh)


def test_dense_param_rules_single_pod():
    cfg, p_shape, specs = _specs("qwen3_32b", MESH_1POD)
    lyr = specs["layers"]
    # stacked (L, d, features) col-parallel: leading stack dim replicated
    assert lyr["attn"]["wq"] == P(None, ("data",), ("model",))
    assert lyr["attn"]["wo"] == P(None, ("model",), ("data",))
    assert lyr["mlp"]["w_down"] == P(None, ("model",), ("data",))
    assert specs["embed"] == P(("model",), ("data",))
    # norms replicated
    assert lyr["ln1"]["scale"] == P()


def test_multi_pod_fsdp_axes():
    _, _, specs = _specs("qwen3_32b", MESH_2POD)
    assert specs["layers"]["attn"]["wq"] == P(None, ("pod", "data"), ("model",))


def test_divisibility_guard_drops_axis():
    # granite router: (d_model, E=40); 40 % 16 != 0 → E replicated
    _, _, specs = _specs("granite_moe_3b_a800m", MESH_1POD)
    assert specs["layers"]["moe"]["router"] == P(None, ("data",), None)
    # moe expert weights: (E, d_in, d_out) → E replicated, matrices sharded
    assert specs["layers"]["moe"]["w_up"] == P(None, None, ("data",), ("model",))


def test_minicpm_odd_heads_still_shards_flat_features():
    # 36 heads ∤ 16, but h*hd = 2304 is divisible → flat feature dim shards
    _, _, specs = _specs("minicpm_2b", MESH_1POD)
    assert specs["layers"]["attn"]["wq"] == P(None, ("data",), ("model",))


def test_cache_specs_decode_batched():
    cfg = get_config("qwen3_32b")
    shape = INPUT_SHAPES["decode_32k"]
    cache = jax.eval_shape(lambda: T.init_cache(cfg, shape.global_batch, 2048))
    specs = SH.cache_specs(cfg, cache, shape, MESH_1POD)
    # (L, B, Hk, S, hd): batch over data, head_dim over model, seq UNsharded
    # (a sharded update dim makes GSPMD sweep the cache — §Perf decode iter 2)
    assert specs["k"] == P(None, ("data",), None, None, ("model",))


def test_cache_specs_long_context_b1():
    cfg = get_config("zamba2_7b")
    shape = INPUT_SHAPES["long_500k"]
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))
    specs = SH.cache_specs(cfg, cache, shape, MESH_1POD)
    # B=1: the attention cache spreads its sequence over the idle data axis
    k = specs["attn"]["k"]
    norm = lambda e: e if isinstance(e, tuple) else (e,)
    assert norm(k[3]) == ("data",) and norm(k[-1]) == ("model",)


def test_batch_specs_shard_leading_dim():
    cfg = get_config("llava_next_mistral_7b")
    shape = INPUT_SHAPES["train_4k"]
    from repro.launch.inputs import input_specs
    sp = SH.batch_specs(cfg, input_specs(cfg, shape), MESH_2POD)
    assert sp["tokens"][0] == ("pod", "data")
    assert sp["prefix_embeds"][0] == ("pod", "data")
