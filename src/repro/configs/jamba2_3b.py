"""AI21-Jamba2-3B [hybrid] — 28L d_model=2560, Mamba-1 mixers with an
attention layer at l ≡ 7 (mod 14) (2 attention, 26 Mamba), dense SwiGLU
d_ff=8192 (``num_experts`` 1), multi-query attention 20H / 1 KV head of 128
with no positional encoding, Mamba d_inner=5120 (expand 2) d_state=16
d_conv=4 dt_rank=160 with Jamba's Δ/B/C RMSNorms, vocab 65,536 tied,
RMSNorm eps 1e-6.  [huggingface.co/ai21labs/AI21-Jamba2-3B config.json]

Federated as LoRA adapters over a frozen bfloat16 base (``repro.models.
lora``): activations, the scan and its state stay float32, each layer is
rematerialised in the backward pass.
"""
from repro.models.transformer import ArchConfig, LayerSpec

SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"


def make_config(n_layers: int = 28, attn_layer_period: int = 14,
                attn_layer_offset: int = 7, **overrides) -> ArchConfig:
    """The published configuration; ``n_layers`` and the attention period
    and offset (Jamba's own config keys) may be cut, and any other
    ``ArchConfig`` field overridden (a small test model)."""
    pattern = tuple(
        LayerSpec(mixer="attn" if i == attn_layer_offset else "mamba",
                  rope=False)
        for i in range(attn_layer_period))
    fields = dict(
        name="jamba2-3b",
        n_layers=n_layers,
        d_model=2560,
        n_heads=20,
        n_kv_heads=1,
        head_dim=128,
        d_ff=8192,
        vocab_size=65536,
        pattern=pattern,
        activation="swiglu",
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_inner_norms=True,
        tie_embeddings=True,
        scale_embed=False,
        act_dtype="float32",
        remat_layers=True,
        source=SOURCE,
    )
    fields.update(overrides)
    return ArchConfig(**fields)


CONFIG = make_config()
