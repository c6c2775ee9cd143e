"""FLConfig.model: the registry of models clients train, and the
DeepSeek-V2-Lite presets' dense first layer."""
import dataclasses

import pytest

from repro.configs import deepseek_v2_lite as DS
from repro.configs import get_config
from repro.configs.base import FLConfig
from repro.fl import models as FLM
from repro.models import lora_lm as LM
from repro.models import transformer as T


def test_model_field_names_a_registered_model():
    assert FLConfig().model == "mlp"
    assert {"mlp", "deepseek-v2-lite"} <= set(FLM.available_models())
    with pytest.raises(ValueError, match="registered FL model"):
        FLConfig(model="no-such-model")


def test_register_refuses_a_second_model_of_one_name():
    with pytest.raises(ValueError, match="already registered"):
        FLM.register_model("mlp", lambda: None)


def test_lora_model_refuses_classification_data():
    from repro.data.synthetic import federated_classification
    from repro.fl.simulator import SimConfig
    data = federated_classification(4, n_per_client=8, n_test=8)
    with pytest.raises(ValueError, match="integer token data"):
        FLM.get_model("deepseek-v2-lite").init(0, data, SimConfig())


def _mlp_kinds(cfg):
    return ["moe" if "moe" in lp else "mlp"
            for lp in LM.trunk_spec(cfg)["layers"]]


def test_deepseek_v2_presets_have_a_dense_first_layer():
    for cfg in (DS.CONFIG, DS.FLEET):
        assert cfg.moe.first_k_dense_replace == 1
        assert _mlp_kinds(cfg) == ["mlp"] + ["moe"] * (cfg.num_layers - 1)
    assert DS.FLEET.num_layers == 6 and DS.FLEET.moe.experts_held == 8
    lite = DS.CONFIG
    assert lite.mla.q_lora_rank is None and lite.norm_eps == 1e-6


def test_all_moe_models_keep_one_stack():
    """Without ``first_k_dense_replace`` every layer is MoE; the 236B
    preset keeps its uniform all-MoE scan in ``transformer.py``."""
    cfg = dataclasses.replace(DS.FLEET, moe=dataclasses.replace(
        DS.FLEET.moe, first_k_dense_replace=0))
    assert _mlp_kinds(cfg) == ["moe"] * cfg.num_layers
    spec = T.build_spec(get_config("deepseek-v2-236b").reduced())
    assert "moe" in spec["blocks"] and "mlp" not in spec["blocks"]
    assert [k for k in spec if k.endswith("blocks")] == ["blocks"]
