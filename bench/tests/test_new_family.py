"""A backbone family the benchmark has never run joins it as new files: a
module ``bench/reference/<family>.py`` with its reference and its own
operation count, and a configuration file whose nested settings (``moe``,
``ssm``) become the program's nested dataclasses. No file of the harness
names the family."""

import dataclasses
import json
import os
import sys
import types

import pytest

from bench import flops, harness, local
from bench import reference as R
from repro.config import ModelConfig, MoEConfig, SSMConfig

FAMILY = "toy_moe"


def _ops(cfg, seq):
    # a made-up count that no other family gives: experts per token matter
    return 7 * cfg["d_model"] * cfg["moe"]["top_k"] * seq


def _cfg():
    return {"name": "toy_moe_1b", "family": FAMILY, "arch_type": "moe",
            "num_layers": 2, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 2, "d_ff": 96, "vocab_size": 256,
            "moe": {"num_experts": 64, "top_k": 6, "capacity_factor": 10.5,
                    "group_size": 128},
            "dtype": "bfloat16", "num_classes": 8, "gamma": 1.0,
            "assumed": {"weights": "random"}}


@pytest.fixture
def family(monkeypatch):
    """The family's module, as a new file would provide it."""
    module = types.ModuleType(f"bench.reference.{FAMILY}")
    module.init = lambda cfg, key: {}
    module.embed = lambda params, cfg, tokens, kind="highest": None
    module.forward_ops = _ops
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return module


def test_nested_settings_become_the_programs_dataclasses():
    mc = local.program_config(_cfg())
    assert isinstance(mc, ModelConfig) and mc.arch_type == "moe"
    assert mc.moe == MoEConfig(num_experts=64, top_k=6, capacity_factor=10.5,
                               group_size=128)
    assert mc.ssm is None
    mc = local.program_config(dict(_cfg(), moe={"num_experts": 4, "top_k": 1},
                                   ssm={"d_state": 16, "chunk": 32}))
    assert mc.moe == MoEConfig(4, 1)        # the rest at the defaults
    assert mc.ssm == SSMConfig(d_state=16, chunk=32)


def test_a_nested_key_the_dataclass_lacks_is_an_error():
    cfg = _cfg()
    cfg["moe"] = dict(cfg["moe"], shared_experts=2)
    with pytest.raises(ValueError, match="MoEConfig has no field shared_experts"):
        local.program_config(cfg)


def test_the_family_is_found_by_name(family):
    assert R.of(_cfg()) is family


def test_the_count_is_the_familys_own(family):
    cfg, seq = _cfg(), 128
    want = _ops(cfg, seq) + flops.fold_per_sample(64, 8)
    assert flops.forward_per_sample(cfg, seq) == _ops(cfg, seq)
    assert flops.local_per_sample(cfg, seq) == want
    cell = harness.Cell("toy_moe_1b.local", 1, cfg, {"seq": seq}, {}, [], [])
    facts = {"seq": seq, "samples_per_s": 250.0, "chips": 1}
    peaks = {"bf16_flops_per_s": 1e12}
    run = harness.TracedRun(cell, None, facts, peaks)
    mfu = harness.metric_reader("local_mfu")(run)
    assert mfu == pytest.approx(100 * want * 250.0 / 1e12)


def test_a_family_with_no_module_is_refused():
    with pytest.raises(ValueError, match="no_such_family"):
        flops.forward_per_sample({"family": "no_such_family"}, 8)
    with pytest.raises(ValueError, match="no_such_family"):
        R.of({"family": "no_such_family"})


@pytest.mark.parametrize("name", ["xlstm_350m", "minicpm_2b"])
def test_existing_configs_build_as_before(name):
    """The configuration files give the ``ModelConfig`` that the file's
    top-level keys gave before nested settings were built, field for
    field."""
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        cfg = json.load(f)
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    before = ModelConfig(**{k: v for k, v in cfg.items() if k in fields})
    now = local.program_config(cfg)
    assert dataclasses.asdict(now) == dataclasses.asdict(before)
    assert now == before
