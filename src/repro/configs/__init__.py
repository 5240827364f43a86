"""Architecture registry: the 10 assigned architectures and AI21-Jamba2-3B,
the one the federation trains (+ the paper's own CIFAR-scale classifier via
repro.models.classifier)."""
from __future__ import annotations

from repro.configs import (
    gemma3_4b,
    gemma_7b,
    grok_1_314b,
    h2o_danube_3_4b,
    internvl2_2b,
    jamba2_3b,
    jamba_1_5_large_398b,
    llama4_maverick_400b_a17b,
    minitron_8b,
    rwkv6_3b,
    whisper_large_v3,
)
from repro.configs.shapes import SHAPES, InputShape  # noqa: F401
from repro.models.transformer import ArchConfig

ARCHS: dict[str, ArchConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (
        gemma3_4b,
        gemma_7b,
        llama4_maverick_400b_a17b,
        grok_1_314b,
        jamba_1_5_large_398b,
        internvl2_2b,
        h2o_danube_3_4b,
        rwkv6_3b,
        whisper_large_v3,
        minitron_8b,
        jamba2_3b,
    )
}

#: architectures the federation can train (LoRA over a frozen base): name ->
#: builder of the config from overrides (``TrainSpec.model_overrides``)
FEDERATED_ARCHS = {"jamba2-3b": jamba2_3b.make_config}

# long_500k applicability (see DESIGN.md §5): sub-quadratic decode only.
LONG_CONTEXT_OK = {
    "gemma3-4b": True,            # 5:1 SWA-1024 : global
    "gemma-7b": False,            # pure full attention
    "llama4-maverick-400b-a17b": True,   # iRoPE chunked-local 3:1
    "grok-1-314b": False,         # pure full attention
    "jamba-1.5-large-398b": True,  # mamba-dominant hybrid
    "internvl2-2b": False,        # full-attention LM backbone
    "h2o-danube-3-4b": True,      # SWA-8192
    "rwkv6-3b": True,             # recurrent, O(1) state
    "whisper-large-v3": False,    # enc-dec, 448-token decoder spec
    "minitron-8b": False,         # pure full attention
    "jamba2-3b": True,            # mamba-dominant hybrid
}


def get_config(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def shape_applicable(arch: str, shape: str) -> tuple[bool, str]:
    """Whether (arch, shape) is runnable; returns (ok, reason_if_not)."""
    if shape == "long_500k" and not LONG_CONTEXT_OK[arch]:
        return False, "full-attention arch: no sub-quadratic decode variant (DESIGN.md §5)"
    return True, ""
