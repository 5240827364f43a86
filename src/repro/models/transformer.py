"""Unified architecture machinery for all 10 assigned families.

Layers are described by a repeating **pattern** of :class:`LayerSpec`
(e.g. gemma3 = 5×local-SWA + 1×global; jamba = 7×mamba + 1×attn with MoE on
odd positions).  Parameters for each pattern position are stacked over the
``n_periods`` repeats so the forward pass is a single ``lax.scan`` over
periods — HLO size stays O(pattern length) regardless of depth, which keeps
512-device dry-run compiles tractable.  Layers left over when ``n_layers %
len(pattern) != 0`` are applied inline ("remainder" layers).

Every mixer (attn / mamba / rwkv) and FFN (dense / MoE) shares the same
residual skeleton; decode carries a per-position cache pytree through the
same scan.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import rwkv as rk
from repro.models.layers import (
    dense,
    dense_init,
    ffn_apply,
    ffn_init,
    init_norm,
    norm,
    rms_norm,
    sinusoidal_positions,
)
from repro.models.moe import moe_apply, moe_capacity, moe_init

Pytree = Any


@dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"          # attn | mamba | rwkv
    window: int = 0              # 0 = full attention, >0 = sliding window
    rope: bool = True
    moe: bool = False
    causal: bool = True
    cross_attn: bool = False     # decoder cross-attention (whisper)


@dataclass(frozen=True)
class EncoderConfig:
    n_layers: int
    n_heads: int
    d_ff: int
    n_frames: int = 1500         # whisper conv-frontend output length (stub)


@dataclass(frozen=True)
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    activation: str = "swiglu"
    norm: str = "rmsnorm"
    qk_norm: bool = False
    # MoE
    n_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared_expert: bool = False
    capacity_factor: float = 1.25
    # Mamba
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_inner_norms: bool = False   # Jamba's RMSNorms on Δ, B and C
    # RWKV
    rwkv_head_dim: int = 64
    rwkv_lora_rank: int = 64
    # encoder-decoder / frontends
    encoder: EncoderConfig | None = None
    frontend: str = "tokens"     # tokens | audio_stub | vision_stub
    abs_pos: bool = False        # add sinusoidal absolute positions (whisper)
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    param_dtype: str = "bfloat16"
    act_dtype: str = ""          # activations ("" = param_dtype)
    scale_embed: bool = True     # multiply embeddings by sqrt(d_model)
    remat: bool = True
    remat_layers: bool = False   # rematerialise each layer, not each period
    sharding_mode: str = "tp"    # tp | fsdp_tp | ep_tp (expert-parallel MoE)
    swa_skip: bool = False       # skip fully-masked attention chunks (§Perf)
    # §Perf: pin attention activations to batch-sharded / model-replicated.
    # GQA head counts rarely divide the model axis, so auto-SPMD otherwise
    # contract-shards the score einsums and all-reduces GB-scale score
    # tensors inside the kv scan (measured 100×+ collective blow-up).
    # Set by the launcher (requires an active mesh); None = let GSPMD choose.
    attn_batch_axes: tuple | None = None
    vocab_pad_multiple: int = 2048  # Megatron-style padding so vocab shards evenly
    # provenance
    source: str = ""

    # ------------------------------------------------------------------ #
    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def remainder(self) -> tuple[LayerSpec, ...]:
        return self.pattern[: self.n_layers % len(self.pattern)]

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab_size // m) * m

    @property
    def activation_dtype(self):
        return jnp.dtype(self.act_dtype or self.param_dtype)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_dt_rank(self) -> int:
        return max(self.d_model // 16, 8)

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    def reduced(self, **overrides) -> "ArchConfig":
        """A small same-family variant for CPU smoke tests (≤2 pattern periods,
        d_model ≤ 512, ≤4 experts)."""
        d_model = min(self.d_model, 256)
        head_dim = 32
        n_heads = max(self.n_heads // 8, 2)
        n_kv = max(min(self.n_kv_heads, n_heads), 1)
        changes = dict(
            n_layers=len(self.pattern) * min(self.n_periods, 1) or len(self.pattern),
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            moe_top_k=min(self.moe_top_k, 2) if self.moe_top_k else 0,
            moe_d_ff=min(self.moe_d_ff, 256) if self.moe_d_ff else 0,
            rwkv_head_dim=32,
            rwkv_lora_rank=16,
            param_dtype="float32",
            remat=False,
            vocab_pad_multiple=1,
        )
        if self.encoder is not None:
            changes["encoder"] = EncoderConfig(
                n_layers=2, n_heads=n_heads, d_ff=min(self.encoder.d_ff, 512),
                n_frames=16)
        # shrink sliding windows so short smoke sequences exercise the ring buffer
        changes["pattern"] = tuple(
            dataclasses.replace(s, window=min(s.window, 8)) if s.window else s
            for s in self.pattern)
        changes.update(overrides)
        return dataclasses.replace(self, **changes)


# --------------------------------------------------------------------------- #
# Parameter construction
# --------------------------------------------------------------------------- #

def _init_layer(cfg: ArchConfig, spec: LayerSpec, key: jax.Array) -> dict:
    ks = jax.random.split(key, 12)
    dt = cfg.dtype
    D = cfg.d_model
    p: dict = {}
    if spec.mixer == "attn":
        p["norm1"] = init_norm(cfg.norm, D, dt)
        p["q"] = dense_init(ks[0], D, cfg.n_heads * cfg.head_dim, dt)
        p["k"] = dense_init(ks[1], D, cfg.n_kv_heads * cfg.head_dim, dt)
        p["v"] = dense_init(ks[2], D, cfg.n_kv_heads * cfg.head_dim, dt)
        p["o"] = dense_init(ks[3], cfg.n_heads * cfg.head_dim, D, dt)
        if cfg.qk_norm:
            p["q_norm"] = {"scale": jnp.zeros((cfg.head_dim,), dt)}
            p["k_norm"] = {"scale": jnp.zeros((cfg.head_dim,), dt)}
        if spec.cross_attn:
            p["norm_c"] = init_norm(cfg.norm, D, dt)
            p["qc"] = dense_init(ks[8], D, cfg.n_heads * cfg.head_dim, dt)
            p["kc"] = dense_init(ks[9], D, cfg.n_kv_heads * cfg.head_dim, dt)
            p["vc"] = dense_init(ks[10], D, cfg.n_kv_heads * cfg.head_dim, dt)
            p["oc"] = dense_init(ks[11], cfg.n_heads * cfg.head_dim, D, dt)
    elif spec.mixer == "mamba":
        p["norm1"] = init_norm(cfg.norm, D, dt)
        p["mamba"] = mb.mamba_init(ks[0], D, cfg.mamba_d_inner, cfg.mamba_d_state,
                                   cfg.mamba_d_conv, cfg.mamba_dt_rank, dt,
                                   inner_norms=cfg.mamba_inner_norms)
    elif spec.mixer == "rwkv":
        p["norm1"] = init_norm(cfg.norm, D, dt)
        p["time_mix"] = rk.rwkv_time_mix_init(
            ks[0], D, cfg.rwkv_heads, cfg.rwkv_head_dim, cfg.rwkv_lora_rank, dt)
        p["norm2"] = init_norm(cfg.norm, D, dt)
        p["channel_mix"] = rk.rwkv_channel_mix_init(ks[1], D, cfg.d_ff, dt)
        return p
    else:
        raise ValueError(spec.mixer)

    p["norm2"] = init_norm(cfg.norm, D, dt)
    if spec.moe:
        p["moe"] = moe_init(ks[4], cfg.activation, D, cfg.moe_d_ff or cfg.d_ff,
                            cfg.n_experts, dt, cfg.moe_shared_expert)
    else:
        p["ffn"] = ffn_init(ks[5], cfg.activation, D, cfg.d_ff, dt)
    return p


def init_params(cfg: ArchConfig, key: jax.Array) -> Pytree:
    keys = jax.random.split(key, 8)
    dt = cfg.dtype
    params: dict = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.padded_vocab, dt)

    # pattern-period stacked layers
    def init_period(k):
        pk = jax.random.split(k, len(cfg.pattern))
        return [_init_layer(cfg, spec, pk[i]) for i, spec in enumerate(cfg.pattern)]

    period_keys = jax.random.split(keys[2], max(cfg.n_periods, 1))
    if cfg.n_periods > 0:
        stacked = jax.vmap(init_period)(period_keys)
        params["layers"] = stacked
    rem_keys = jax.random.split(keys[3], max(len(cfg.remainder), 1))
    params["rem_layers"] = [
        _init_layer(cfg, spec, rem_keys[i]) for i, spec in enumerate(cfg.remainder)]

    if cfg.encoder is not None:
        enc = cfg.encoder
        enc_spec = LayerSpec(mixer="attn", rope=False, causal=False)
        enc_cfg = dataclasses.replace(
            cfg, n_heads=enc.n_heads, n_kv_heads=enc.n_heads, d_ff=enc.d_ff,
            head_dim=cfg.d_model // enc.n_heads, qk_norm=False, activation="gelu",
            norm="layernorm")
        ek = jax.random.split(keys[4], enc.n_layers)
        params["encoder"] = {
            "layers": [_init_layer(enc_cfg, enc_spec, ek[i]) for i in range(enc.n_layers)],
            "final_norm": init_norm("layernorm", cfg.d_model, dt),
        }
    return params


def param_specs(cfg: ArchConfig) -> Pytree:
    """ShapeDtypeStruct tree (no allocation) — dry-run input."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# --------------------------------------------------------------------------- #
# Forward (train / prefill)
# --------------------------------------------------------------------------- #

def _constrain_attn(cfg: ArchConfig, *ts):
    """Pin (B, S, H, hd) tensors to batch-sharded/model-replicated (§Perf)."""
    if cfg.attn_batch_axes is None:
        return ts if len(ts) > 1 else ts[0]
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(cfg.attn_batch_axes), None, None, None)
    out = tuple(jax.lax.with_sharding_constraint(t, spec) for t in ts)
    return out if len(out) > 1 else out[0]


@jax.named_scope("attention")
def _attn_sublayer(cfg: ArchConfig, spec: LayerSpec, p: dict, h: jax.Array,
                   pos_ids: jax.Array, enc_out: jax.Array | None) -> jax.Array:
    B, S, D = h.shape
    x = norm(cfg.norm, h, p["norm1"])
    q = dense(x, p["q"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = dense(x, p["k"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = dense(x, p["v"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q, k, v = _constrain_attn(cfg, q, k, v)
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if spec.rope:
        q = _rope(q, pos_ids, cfg.rope_theta)
        k = _rope(k, pos_ids, cfg.rope_theta)
    if S >= 2048:
        out = attn.attend_chunked(q, k, v, causal=spec.causal, window=spec.window,
                                  skip_masked_chunks=cfg.swa_skip)
    else:
        out = attn.attend_full(q, k, v, causal=spec.causal, window=spec.window,
                               q_pos=pos_ids[0], k_pos=pos_ids[0])
    out = _constrain_attn(cfg, out)
    h = h + dense(out.reshape(B, S, -1), p["o"])

    if spec.cross_attn and enc_out is not None:
        xc = norm(cfg.norm, h, p["norm_c"])
        Se = enc_out.shape[1]
        qc = (xc @ p["qc"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        kc = (enc_out @ p["kc"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        vc = (enc_out @ p["vc"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        co = attn.attend_full(qc, kc, vc, causal=False)
        h = h + co.reshape(B, S, -1) @ p["oc"]
    return h


def _rope(x, pos_ids, theta):
    from repro.models.layers import apply_rope
    return apply_rope(x, pos_ids, theta)


def _ffn_sublayer(cfg: ArchConfig, spec: LayerSpec, p: dict, h: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    x = norm(cfg.norm, h, p["norm2"])
    if spec.moe:
        T = x.shape[0] * x.shape[1]
        cap = moe_capacity(T, cfg.moe_top_k, cfg.n_experts, cfg.capacity_factor)
        if cfg.sharding_mode == "ep_tp":
            from repro.models.layers import is_gated as _gated
            from repro.models.moe_sharded import ambient_mesh_shape, moe_apply_shard_map
            ms = ambient_mesh_shape()
            if (_gated(cfg.activation) and ms.get("data")
                    and cfg.n_experts % ms["data"] == 0):
                baxes = ("pod", "data") if "pod" in ms else ("data",)
                y, aux = moe_apply_shard_map(
                    cfg.activation, p["moe"], x, top_k=cfg.moe_top_k,
                    capacity=cap, batch_axes=baxes)
                return h + y, aux
        y, aux = moe_apply(cfg.activation, p["moe"], x,
                           top_k=cfg.moe_top_k, capacity=cap)
        return h + y, aux
    return h + ffn_apply(cfg.activation, p["ffn"], x), jnp.zeros((), jnp.float32)


def _apply_layer(cfg: ArchConfig, spec: LayerSpec, p: dict, h: jax.Array,
                 pos_ids: jax.Array, enc_out: jax.Array | None
                 ) -> tuple[jax.Array, jax.Array]:
    if spec.mixer == "attn":
        h = _attn_sublayer(cfg, spec, p, h, pos_ids, enc_out)
        return _ffn_sublayer(cfg, spec, p, h)
    if spec.mixer == "mamba":
        x = norm(cfg.norm, h, p["norm1"])
        h = h + mb.mamba_apply(p["mamba"], x, d_state=cfg.mamba_d_state,
                               d_conv=cfg.mamba_d_conv, dt_rank=cfg.mamba_dt_rank)
        return _ffn_sublayer(cfg, spec, p, h)
    if spec.mixer == "rwkv":
        B = h.shape[0]
        st = rk.rwkv_init_state(B, cfg.d_model, cfg.rwkv_heads, cfg.rwkv_head_dim,
                                h.dtype)
        x = norm(cfg.norm, h, p["norm1"])
        y, _, _ = rk.time_mix_apply(p["time_mix"], x, st["tm_x"], st["wkv"],
                                    n_heads=cfg.rwkv_heads, head_dim=cfg.rwkv_head_dim)
        h = h + y
        x = norm(cfg.norm, h, p["norm2"])
        y, _ = rk.channel_mix_apply(p["channel_mix"], x, st["cm_x"])
        return h + y, jnp.zeros((), jnp.float32)
    raise ValueError(spec.mixer)


def backbone(cfg: ArchConfig, params: Pytree, h: jax.Array,
             pos_ids: jax.Array, enc_out: jax.Array | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Apply all layers to hidden states h (B, S, D). Returns (h, moe_aux)."""

    def layer_fn(spec):
        f = lambda p, h: _apply_layer(cfg, spec, p, h, pos_ids, enc_out)  # noqa: E731
        return jax.checkpoint(f) if cfg.remat and cfg.remat_layers else f

    def period_body(carry, period_params):
        h, aux = carry
        for i, spec in enumerate(cfg.pattern):
            h, a = layer_fn(spec)(period_params[i], h)
            aux = aux + a
        return (h, aux), None

    body = jax.checkpoint(period_body) if cfg.remat and not cfg.remat_layers \
        else period_body
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_periods > 0:
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["layers"])
    for i, spec in enumerate(cfg.remainder):
        h, a = layer_fn(spec)(params["rem_layers"][i], h)
        aux = aux + a
    return h, aux


def encode(cfg: ArchConfig, params: Pytree, enc_embeds: jax.Array) -> jax.Array:
    """Whisper-style encoder over stub frame embeddings (B, n_frames, D)."""
    assert cfg.encoder is not None
    enc = cfg.encoder
    pos = sinusoidal_positions(enc_embeds.shape[1], cfg.d_model).astype(enc_embeds.dtype)
    h = enc_embeds + pos[None]
    spec = LayerSpec(mixer="attn", rope=False, causal=False)
    enc_cfg = dataclasses.replace(
        cfg, n_heads=enc.n_heads, n_kv_heads=enc.n_heads, d_ff=enc.d_ff,
        head_dim=cfg.d_model // enc.n_heads, qk_norm=False, activation="gelu",
        norm="layernorm")
    pos_ids = jnp.broadcast_to(jnp.arange(h.shape[1])[None], h.shape[:2])
    for lp in params["encoder"]["layers"]:
        h = _attn_sublayer(enc_cfg, spec, lp, h, pos_ids, None)
        h, _ = _ffn_sublayer(enc_cfg, spec, lp, h)
    return norm("layernorm", h, params["encoder"]["final_norm"])


def forward(cfg: ArchConfig, params: Pytree, tokens: jax.Array | None = None,
            embeds: jax.Array | None = None, enc_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward: returns (logits (B,S,V), final hidden (B,S,D), moe_aux)."""
    if embeds is None:
        assert tokens is not None
        h = params["embed"][tokens].astype(cfg.activation_dtype)
        if cfg.scale_embed:
            h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    else:
        h = embeds.astype(cfg.activation_dtype)
    B, S = h.shape[:2]
    pos_ids = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.abs_pos:
        h = h + sinusoidal_positions(S, cfg.d_model).astype(h.dtype)[None]
    enc_out = None
    if cfg.encoder is not None and enc_embeds is not None:
        enc_out = encode(cfg, params, enc_embeds)
    h, aux = backbone(cfg, params, h, pos_ids, enc_out)
    h = norm(cfg.norm, h, params["final_norm"])
    logits = unembed(cfg, params, h)
    return logits, h, aux


def hidden_states(cfg: ArchConfig, params: Pytree, tokens: jax.Array
                  ) -> jax.Array:
    """Token-only decoder: tokens (B, S) -> final-norm hidden states
    (B, S, D), the input of the (tied) head."""
    h = params["embed"][tokens].astype(cfg.activation_dtype)
    if cfg.scale_embed:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    B, S = tokens.shape
    pos_ids = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, _ = backbone(cfg, params, h, pos_ids)
    return norm(cfg.norm, h, params["final_norm"])


def unembed(cfg: ArchConfig, params: Pytree, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = h @ params["lm_head"]
    if cfg.padded_vocab != cfg.vocab_size:
        # mask Megatron-style vocab padding so it never receives probability
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits
