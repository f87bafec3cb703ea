"""Model registry: the BASELINE.json config matrix by name.

Configs covered (BASELINE.json ``configs``):
  - resnet18  — ResNet-18 / CIFAR-100 (the reference's only model)
  - resnet50  — ResNet-50 (pod-scale sync config; ImageNet-1k shapes)
  - vit_b16   — ViT-B/16 (transformer / non-conv MXU path)
  - vit_tiny  — small ViT for CIFAR-resolution runs and tests
  - joyai_llm_flash — the decoder LM (models/joyai.py): latent attention,
    a top-k expert layer told which experts it holds, multi-token
    prediction; built from a ``JoyAIConfig`` (``config=``, or one of its
    ``PRESETS`` by name)
  - smallthinker — the decoder LM of models/smallthinker.py: sliding-window
    and full attention mixed, grouped queries, a softmax router that reads
    the block's input, ReLU-gated experts; from a ``SmallThinkerConfig``
  - nemotron_h — the hybrid decoder LM of models/nemotron_h.py: a layer is
    one mixer (a Mamba-2 state-space scan, attention without positions, or
    ungated experts in a latent); from a ``NemotronHConfig``

A decoder LM's configuration object comes from :func:`lm_config`: itself, a
preset's name, or (``lm_config_from_file``) a dict of the source's published
keys with what the caller overrides, which is how a benchmark driver builds
any of them without importing a model's module by name.

A model's *family* (``family_of``) says which task trains it
(train/tasks.py): ``image`` (uint8 images and labels) or ``lm`` (packed
token rows).
"""

from __future__ import annotations

import jax.numpy as jnp

from .joyai import PRESETS as JOYAI_PRESETS, JoyAIConfig, JoyAILM
from .nemotron_h import (PRESETS as NEMOTRON_H_PRESETS, NemotronHConfig,
                         NemotronHLM)
from .resnet import ResNet18, ResNet50
from .smallthinker import (PRESETS as SMALLTHINKER_PRESETS,
                           SmallThinkerConfig, SmallThinkerLM)
from .vit import ViT_B16, ViT_Tiny

_REGISTRY = {
    # ResNets switch to the ImageNet stem (7x7/2 + maxpool/2) at large
    # resolutions: the CIFAR stem carries full-resolution feature maps into
    # stage 0 and needs ~37 GB HBM for one 224px batch-128 train step.
    "resnet18": lambda num_classes, dtype, axis_name, image_size: ResNet18(
        num_classes=num_classes, dtype=dtype, axis_name=axis_name,
        imagenet_stem=image_size >= 96),
    "resnet50": lambda num_classes, dtype, axis_name, image_size: ResNet50(
        num_classes=num_classes, dtype=dtype, axis_name=axis_name,
        imagenet_stem=image_size >= 96),
    "vit_b16": lambda num_classes, dtype, axis_name, image_size: ViT_B16(
        num_classes=num_classes, dtype=dtype),
    "vit_tiny": lambda num_classes, dtype, axis_name, image_size: ViT_Tiny(
        num_classes=num_classes, dtype=dtype),
}

#: decoder LMs: name -> (module, configuration class, presets)
_LM_REGISTRY = {
    "joyai_llm_flash": (JoyAILM, JoyAIConfig, JOYAI_PRESETS),
    "smallthinker": (SmallThinkerLM, SmallThinkerConfig,
                     SMALLTHINKER_PRESETS),
    "nemotron_h": (NemotronHLM, NemotronHConfig, NEMOTRON_H_PRESETS),
}

MODEL_NAMES = tuple(_REGISTRY) + tuple(_LM_REGISTRY)


def family_of(name: str) -> str:
    """``image`` or ``lm``: the task that trains the model."""
    if name in _LM_REGISTRY:
        return "lm"
    if name in _REGISTRY:
        return "image"
    raise ValueError(f"unknown model {name!r}; have {MODEL_NAMES}")


def lm_config(name: str, config=None):
    """The configuration a decoder LM is built from: ``config`` itself, a
    preset's name, or the ``tiny`` preset when nothing is given."""
    _module, config_cls, presets = _LM_REGISTRY[name]
    if isinstance(config, config_cls):
        return config
    if (config or "tiny") not in presets:
        raise ValueError(f"{name} has presets {tuple(presets)}, not "
                         f"{config!r}")
    return presets[config or "tiny"]


def lm_config_from_file(name: str, published: dict, **overrides):
    """The configuration of decoder LM ``name`` from a dict that holds the
    source's published keys (its ``config.json``, or a benchmark's
    configuration file), with ``overrides`` for the fields that are no
    published key: the share held here, what the source leaves open."""
    return _LM_REGISTRY[name][1].from_hf(published, **overrides)


def get_model(name: str, num_classes: int = 100, dtype=jnp.bfloat16,
              axis_name: str | None = None, image_size: int = 32,
              config=None):
    """Build a model by registry name. ViT models ignore ``axis_name``
    (LayerNorm needs no cross-replica sync; BN models use it).
    ``image_size`` selects resolution-dependent choices (ResNet-50 stem).
    A decoder LM takes ``config`` (``lm_config``) and ``dtype`` alone."""
    if name in _LM_REGISTRY:
        return _LM_REGISTRY[name][0](lm_config(name, config), dtype=dtype)
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {MODEL_NAMES}")
    return _REGISTRY[name](num_classes, dtype, axis_name, image_size)
