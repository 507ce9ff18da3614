"""Architecture registry: the 10 assigned archs + the paper's own FNOs."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (  # noqa: F401
    ArchConfig,
    EncoderConfig,
    LM_SHAPES,
    MLAConfig,
    ShapeConfig,
    cell_supported,
    get_shape,
    input_specs,
)

ARCH_IDS = (
    "deepseek-moe-16b",
    "deepseek-v2-lite-16b",
    "mamba2-370m",
    "whisper-tiny",
    "chameleon-34b",
    "qwen1.5-32b",
    "chatglm3-6b",
    "gemma-7b",
    "minitron-8b",
    "recurrentgemma-2b",
)

FNO_IDS = ("fno-ns3d", "fno-sleipner", "fno-sleipner-2d")

_MODULES = {arch_id: arch_id.replace("-", "_").replace(".", "_") for arch_id in ARCH_IDS}


def get_arch(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def _fno_module(name: str):
    if name not in FNO_IDS:
        raise KeyError(f"unknown FNO config {name!r}")
    return importlib.import_module(f"repro.configs.{name.replace('-', '_')}")


def get_fno(name: str):
    mod = _fno_module(name)
    return mod.CONFIG, mod.SHAPES


# FNOConfig fields a named config may override (architecture and sizing;
# the kernel path and comm chunking have their own launcher flags).
FNO_OVERRIDE_KEYS = (
    "grid", "modes", "width", "in_channels", "out_channels", "n_blocks",
    "decoder_dim",
)
_FNO_TUPLE_KEYS = ("grid", "modes")


def parse_fno_overrides(items) -> dict:
    """``["grid=64,16,24,88", "width=40"]`` -> ``{"grid": (64, 16, 24, 88),
    "width": 40}``; raises ValueError on an unknown key or a malformed
    value."""
    out = {}
    for item in items:
        key, sep, val = item.partition("=")
        if not sep or key not in FNO_OVERRIDE_KEYS:
            raise ValueError(
                f"override {item!r}: expected KEY=VALUE with KEY one of "
                f"{FNO_OVERRIDE_KEYS}"
            )
        try:
            nums = tuple(int(v) for v in val.split(","))
        except ValueError:
            raise ValueError(f"override {item!r}: not integers") from None
        want = 4 if key in _FNO_TUPLE_KEYS else 1
        if len(nums) != want:
            raise ValueError(f"override {item!r}: {key} takes {want} value(s)")
        out[key] = nums if key in _FNO_TUPLE_KEYS else nums[0]
    return out


def fno_with_overrides(name: str, overrides: dict):
    """The named FNO config with ``overrides`` (FNO_OVERRIDE_KEYS fields,
    e.g. as recorded in a checkpoint's fno_config.json) replaced."""
    unknown = sorted(set(overrides) - set(FNO_OVERRIDE_KEYS))
    if unknown:
        raise ValueError(f"unknown FNO override key(s) {unknown}")
    cfg, _ = get_fno(name)
    return dataclasses.replace(cfg, **{
        k: tuple(int(x) for x in v) if k in _FNO_TUPLE_KEYS else int(v)
        for k, v in overrides.items()
    })


def get_fno_model_axes(name: str):
    """Model-parallel layout for an FNO config: (model_axis, pencil_shape).

    1-D configs return ("model", None); pencil configs declare MODEL_AXES
    (e.g. ("mx", "my")) and PENCIL_SHAPE (e.g. (8, 4)) in their module.
    """
    mod = _fno_module(name)
    return getattr(mod, "MODEL_AXES", "model"), getattr(mod, "PENCIL_SHAPE", None)


def reduced(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests (one fwd/train step)."""
    from repro.models.moe import MoEConfig
    from repro.models.ssm import SSMConfig
    from repro.models.rglru import RGLRUConfig

    changes = dict(
        n_layers=3 if cfg.family == "hybrid" else 2,
        d_model=64,
        n_heads=4,
        kv_heads=max(1, min(cfg.kv_heads, 2)),
        d_ff=0 if cfg.family == "ssm" else 128,
        vocab=512,
        head_dim=16,
        window=16 if cfg.window else None,
    )
    if cfg.moe:
        changes["moe"] = MoEConfig(
            n_experts=8,
            top_k=2,
            d_expert=32,
            n_shared=cfg.moe.n_shared and 1,
            first_dense_ff=64 if cfg.moe.first_dense_ff else 0,
            norm_topk=cfg.moe.norm_topk,
        )
    if cfg.mla:
        changes["mla"] = MLAConfig(kv_lora=32, dh_nope=16, dh_rope=8, dh_v=16)
        changes["head_dim"] = None
    if cfg.ssm:
        changes["ssm"] = SSMConfig(d_state=16, head_dim=16, chunk=16)
        changes["head_dim"] = None
        changes["n_heads"] = 8
        changes["kv_heads"] = 8
    if cfg.rglru:
        changes["rglru"] = RGLRUConfig(d_rnn=0, conv_kernel=4)
    if cfg.encoder:
        changes["encoder"] = EncoderConfig(n_layers=2, frames=12)
    return dataclasses.replace(cfg, **changes)
