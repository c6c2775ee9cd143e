"""The models fleet clients train, by name (``FLConfig.model``).

An ``FLModel`` gives FleetEngine everything it needs of a model:

* ``init(seed, data, sim_cfg) -> (trainable, frozen)``: the initial
  leaves.  Only ``trainable`` is trained, packed, aggregated, cached and
  returned as ``History.final_params``; ``frozen`` is placed once and
  handed to every jitted trainer and eval call as an argument the whole
  cohort shares (never donated, never closed over);
* ``value_and_grad(params_x, frozen, xb, yb) -> ((C,) losses, grads)``:
  each client's mean batch loss and its gradient, for trainable leaves
  with a leading client axis (C, ...) and per-client batches;
* ``accuracy(params, frozen, x, y)``: the eval metric of the global
  model on the held-out set;
* optionally ``per_class_accuracy`` (end-of-run diagnostics) and
  ``expert_load(params, frozen, tokens)`` (the per-round expert-load
  counter under telemetry).

Registered: ``"mlp"``, the classifier of ``repro.fl.classifier`` (every
leaf trainable, widths from ``SimConfig`` and the data), and
``"deepseek-v2-lite"``, rank-16 LoRA adapters over one chip's share of
DeepSeek-V2-Lite (``repro.configs.deepseek_v2_lite``) on token data.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax

from repro.fl import classifier as CLF


@dataclasses.dataclass(frozen=True)
class FLModel:
    name: str
    init: Callable
    value_and_grad: Callable
    accuracy: Callable
    per_class_accuracy: Optional[Callable] = None
    expert_load: Optional[Callable] = None


def _mlp() -> FLModel:
    vg = jax.vmap(jax.value_and_grad(CLF.clf_loss))

    def init(seed, data, sim_cfg):
        return CLF.init_classifier(
            jax.random.key(seed + 1), dim=data.x.shape[-1],
            num_classes=data.num_classes, hidden=sim_cfg.model_hidden,
            depth=sim_cfg.model_depth), {}

    return FLModel(
        "mlp", init, lambda p, frozen, x, y: vg(p, x, y),
        lambda p, frozen, x, y: CLF.clf_accuracy(p, x, y),
        per_class_accuracy=lambda p, frozen, x, y, k:
            CLF.clf_per_class_accuracy(p, x, y, k))


def lora_lm(name: str, cfg, lora) -> FLModel:
    """LoRA adapters (trainable) over the frozen trunk of ``cfg``."""
    from repro.models import lora_lm as LM

    def init(seed, data, sim_cfg):
        if data.x.ndim != 3 or data.x.dtype.kind != "i":
            raise ValueError(f"model {name!r} trains on (N, n, S) integer "
                             f"token data, got {data.x.dtype} "
                             f"{tuple(data.x.shape)}")
        return LM.init_adapters(cfg, lora, seed), LM.init_trunk(cfg, seed)

    return FLModel(
        name, init, LM.value_and_grad(cfg, lora),
        functools.partial(LM.token_accuracy, cfg=cfg, lora=lora),
        expert_load=functools.partial(LM.expert_load, cfg=cfg, lora=lora))


_REGISTRY: Dict[str, Callable[[], FLModel]] = {}


def register_model(name: str, factory: Callable[[], FLModel],
                   allow_override: bool = False):
    """Register ``factory() -> FLModel`` under ``name``; ``get_model``
    calls it."""
    if name in _REGISTRY and not allow_override:
        raise ValueError(f"FL model {name!r} already registered")
    _REGISTRY[name] = factory


def get_model(name: str) -> FLModel:
    if name not in _REGISTRY:
        raise KeyError(f"unknown FL model {name!r}; registered: "
                       f"{available_models()}")
    return _REGISTRY[name]()


def available_models():
    return sorted(_REGISTRY)


def _deepseek_v2_lite() -> FLModel:
    from repro.configs import deepseek_v2_lite as DS
    return lora_lm("deepseek-v2-lite", DS.FLEET, DS.LORA)


register_model("mlp", _mlp)
register_model("deepseek-v2-lite", _deepseek_v2_lite)
