"""CNN inference configs — the paper's native workload, registered
alongside the LM archs (same sparsity knob, same DBB defaults).

``sparsity`` maps to the paper's nominal formats exactly like the LM
registry: 0.625 → 3/8 DBB. ``pattern='matrix'`` (tc kernel mode) is the
TPU co-design default; pass ``pattern=None`` for the paper-faithful
per-column patterns (bw kernel mode). See DESIGN.md §2/§6.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax.numpy as jnp

from repro.core.vdbb import DBBFormat
from repro.models.cnn import CNNConfig
from repro.models.resnet import ResNetConfig


def _dbb(sparsity: Optional[Union[str, float]], pattern="matrix") -> Optional[DBBFormat]:
    if sparsity in (None, "dense", 0.0):
        return None
    if isinstance(sparsity, str):
        sparsity = float(sparsity)
    nnz = max(1, min(8, round((1.0 - sparsity) * 8)))
    return DBBFormat(8, nnz, pattern)


def sparse_cnn_tiny(sparsity=0.625, pattern="matrix") -> CNNConfig:
    """CIFAR-scale smoke model: 6 convs, 32×32×3 input."""
    return CNNConfig(
        name="sparse-cnn-tiny", in_channels=3, image_size=32,
        stage_channels=(32, 64, 128), convs_per_stage=2, num_classes=10,
        dbb=_dbb(sparsity, pattern), dtype=jnp.float32,
    )


def sparse_cnn_s(sparsity=0.625, pattern="matrix") -> CNNConfig:
    """ImageNet-tile-scale: 8 convs, 64×64×3 input, VGG-ish widths."""
    return CNNConfig(
        name="sparse-cnn-s", in_channels=3, image_size=64,
        stage_channels=(64, 128, 256, 512), convs_per_stage=2, num_classes=1000,
        dbb=_dbb(sparsity, pattern), dtype=jnp.float32,
    )


def sparse_resnet50(sparsity=0.625, pattern="matrix") -> ResNetConfig:
    """ResNet-50 v1.5 at its published widths (He et al., arXiv:1512.03385,
    Table 1; stride on the 3×3 conv, as torchvision's ``resnet50``):
    224×224×3, 7×7/2 stem, bottleneck stages [3, 4, 6, 3] of widths
    64/128/256/512 (expansion 4), 1000 classes. ``sparsity=0.5`` is the
    paper's 4/8 point."""
    return ResNetConfig(
        name="sparse-resnet50", in_channels=3, image_size=224,
        stem_channels=64, stem_kernel=7, stage_widths=(64, 128, 256, 512),
        stage_blocks=(3, 4, 6, 3), expansion=4, num_classes=1000,
        dbb=_dbb(sparsity, pattern), dtype=jnp.float32,
    )


CNN_ARCHS = {
    "sparse-cnn-tiny": sparse_cnn_tiny,
    "sparse-cnn-s": sparse_cnn_s,
    "sparse-resnet50": sparse_resnet50,
}


def get_cnn_config(name: str, sparsity=0.625, pattern="matrix"):
    return CNN_ARCHS[name](sparsity=sparsity, pattern=pattern)


def smoke_cnn_config(name: str, sparsity=0.625, pattern="matrix"):
    """Reduced CPU-runnable variant of the same family: a ResNet keeps one
    block per stage at narrow widths, 32×32."""
    cfg = get_cnn_config(name, sparsity=sparsity, pattern=pattern)
    if isinstance(cfg, ResNetConfig):
        return dataclasses.replace(
            cfg, image_size=32, stem_channels=16, stage_widths=(8, 16, 16, 32),
            stage_blocks=(1, 1, 1, 1), num_classes=10,
        )
    return dataclasses.replace(
        cfg, image_size=16, stage_channels=tuple(cfg.stage_channels[:2]),
        convs_per_stage=1, num_classes=min(cfg.num_classes, 10),
    )


def cnn_model(cfg):
    """The model of a registered CNN config: a ``SparseResNet`` for a
    ``ResNetConfig``, else a ``SparseCNN``."""
    from repro.models.cnn import SparseCNN
    from repro.models.resnet import SparseResNet

    return (SparseResNet if isinstance(cfg, ResNetConfig) else SparseCNN)(cfg)
