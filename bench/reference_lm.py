"""Plain reference of the LoRA-adapted hybrid LM a federation cell trains.

Written from AI21's Jamba description and the LoRA paper, in float32
``jax.numpy`` with ``precision="highest"`` on every product, on the base's
bfloat16 values upcast; it imports nothing of the program.  One client at a
time, each layer under ``jax.checkpoint``: those are its only concessions to
size (a run of layers of one kind is a ``lax.scan`` over them, which
computes the same).  The selective scan is a plain ``lax.scan`` over time.

The model (``model``: the configuration's ``model`` section): tokens ->
embedding -> ``n_layers`` pre-norm layers -> final RMSNorm -> tied head.
Layer ``l`` mixes with attention where ``l % attn_layer_period ==
attn_layer_offset`` and with a Mamba-1 mixer elsewhere, then a SwiGLU MLP:

    h = x + mixer(rms(x)),  out = h + W_down(SiLU(W_gate n) * W_up n),  n = rms(h)

- attention: multi-query (``n_kv_heads`` shared key/value heads), causal,
  no positional encoding, no bias, scores over sqrt(head_dim);
- Mamba: ``[u, z] = W_in n``; ``u = SiLU(causal depthwise conv(u) + b_conv)``;
  ``[d, B, C] = W_x u``, each RMSNorm'd; ``dt = softplus(W_dt d + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t``;
  ``y_t = C_t h_t + D u_t``; ``out = W_out(y * SiLU(z))``.

RMSNorm weights are stored as offsets from one (``x / rms(x) * (1 + w)``),
eps ``rms_norm_eps``.  Departures from the published description:

- documents are packed end to end into rows with no state reset at their
  boundaries (Jamba's training packs the same way; a reset would need
  document ids the rows do not carry);
- LoRA (rank ``lora_rank``, scale ``lora_alpha / lora_rank``) sits on the
  mixers' projections only: Mamba's ``W_in`` and ``W_out``, attention's
  ``q, k, v, o``; the MLPs, ``W_x``, ``W_dt``, norms and head stay frozen.

Weights come from a key (:func:`init_weights`): the base a dict whose
``layers`` list holds one dict per position of the attention period, each
leaf with a leading axis over the periods; the adapters one float32 row in
the arena's column order (:func:`row_layout`: the leaves' path strings,
sorted).  The control computes the same with the scan's state rounded to
bfloat16 at every step (``bf16_state``).  The control and the faults the
benchmark checks its limits with (``norms`` off; the loss over the first
``n_tokens`` of each row) are traced arguments, so that one compiled
program serves them all.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def model_key(model: dict):
    """Hashable form of a model section (a static jit argument)."""
    return tuple(sorted(model.items()))


def _m(mk) -> dict:
    return dict(mk)


def dims(model: dict) -> dict:
    d = model["d_model"]
    return dict(d=d, di=model["mamba_expand"] * d, n=model["mamba_d_state"],
                r=model["mamba_dt_rank"], kc=model["mamba_d_conv"],
                hq=model["n_heads"] * model["head_dim"],
                hkv=model["n_kv_heads"] * model["head_dim"])


def is_attn(model: dict, pos: int) -> bool:
    return pos == model["attn_layer_offset"]


def periods(model: dict) -> int:
    p = model["attn_layer_period"]
    if model["n_layers"] % p:
        raise ValueError("n_layers must be a whole number of attention periods")
    return model["n_layers"] // p


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

def targets(model: dict, pos: int) -> dict[str, tuple[int, int]]:
    """The adapted projections of a pattern position: name -> (in, out)."""
    k = dims(model)
    if is_attn(model, pos):
        return {"q": (k["d"], k["hq"]), "k": (k["d"], k["hkv"]),
                "v": (k["d"], k["hkv"]), "o": (k["hq"], k["d"])}
    return {"in_proj": (k["d"], 2 * k["di"]), "out_proj": (k["di"], k["d"])}


def row_layout(model: dict) -> list[tuple[tuple[int, str, str], tuple]]:
    """((position, projection, "a" | "b"), shape) of every adapter leaf in
    the arena's column order: sorted by the leaf's path string
    ``['layers'][<position>]['<projection>']['<a|b>']``."""
    P, r = periods(model), model["lora_rank"]
    leaves = []
    for pos in range(model["attn_layer_period"]):
        for name, (fi, fo) in targets(model, pos).items():
            leaves.append(((pos, name, "a"), (P, fi, r)))
            leaves.append(((pos, name, "b"), (P, r, fo)))
    return sorted(leaves, key=lambda t: f"['layers'][{t[0][0]}]"
                  f"['{t[0][1]}']['{t[0][2]}']")


def unflatten(model: dict, row: jax.Array) -> dict:
    out, off = {}, 0
    for key, shape in row_layout(model):
        size = int(np.prod(shape))
        out[key] = row[off:off + size].reshape(shape)
        off += size
    return out


def flatten(model: dict, ad: dict) -> jax.Array:
    return jnp.concatenate([ad[key].reshape(-1)
                            for key, _ in row_layout(model)])


def init_weights(model: dict, key: jax.Array) -> tuple[dict, jax.Array]:
    """(base, adapter row) from ``key``.  Matrices ``N(0, 1/fan_in)``, the
    embedding ``N(0, 0.02^2)``, norm offsets ``N(0, 0.1^2)``, all bfloat16;
    Mamba's ``A_log = log(1..N)``, ``D = 1`` and ``b_dt`` the inverse
    softplus of a log-uniform step in [1e-3, 1e-1], float32.  Adapters:
    ``A ~ N(0, 1/in)``, ``B = 0``, float32.  Each position of the attention
    period is made by one compiled program, shared by the positions of one
    kind, so that the compile does not grow with the depth."""
    mk = model_key(model)
    k_layers, k_rest = jax.random.split(key)
    keys = jax.random.split(k_layers, model["attn_layer_period"])
    layers = [_init_layer(mk, is_attn(model, pos), keys[pos])
              for pos in range(model["attn_layer_period"])]
    embed, final_norm, row = _init_rest(mk, k_rest)
    return {"embed": embed, "final_norm": final_norm, "layers": layers}, row


@partial(jax.jit, static_argnums=(0, 1))
def _init_layer(mk, attn: bool, key: jax.Array) -> dict:
    """One position's base weights, each leaf with the leading period axis."""
    model = _m(mk)
    k = dims(model)
    P = periods(model)
    keys = iter(jax.random.split(key, 16))

    def nrm(shape, std):
        return (jax.random.normal(next(keys), (P,) + shape, jnp.float32)
                * std).astype(BF16)

    def mat(fi, fo):
        return nrm((fi, fo), fi ** -0.5)

    lw = {"norm1": nrm((k["d"],), 0.1), "norm2": nrm((k["d"],), 0.1),
          "w_gate": mat(k["d"], model["d_ff"]),
          "w_up": mat(k["d"], model["d_ff"]),
          "w_down": mat(model["d_ff"], k["d"])}
    if attn:
        lw.update(q=mat(k["d"], k["hq"]), k=mat(k["d"], k["hkv"]),
                  v=mat(k["d"], k["hkv"]), o=mat(k["hq"], k["d"]))
        return lw
    dt = jnp.exp(jax.random.uniform(next(keys), (P, k["di"]), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    lw.update(
        in_proj=mat(k["d"], 2 * k["di"]),
        conv_w=nrm((k["kc"], k["di"]), k["kc"] ** -0.5),
        conv_b=nrm((k["di"],), 0.1),
        x_proj=mat(k["di"], k["r"] + 2 * k["n"]),
        dt_proj=mat(k["r"], k["di"]),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.broadcast_to(jnp.log(jnp.arange(
            1, k["n"] + 1, dtype=jnp.float32)), (P, k["di"], k["n"])),
        D=jnp.ones((P, k["di"]), jnp.float32),
        out_proj=mat(k["di"], k["d"]),
        dt_norm=nrm((k["r"],), 0.1), b_norm=nrm((k["n"],), 0.1),
        c_norm=nrm((k["n"],), 0.1))
    return lw


@partial(jax.jit, static_argnums=(0,))
def _init_rest(mk, key: jax.Array):
    """The embedding, the final norm and the adapter row."""
    model = _m(mk)
    layout = row_layout(model)
    keys = iter(jax.random.split(key, len(layout) + 2))
    embed = (jax.random.normal(next(keys), (model["vocab_size"],
                                            model["d_model"])) * 0.02
             ).astype(BF16)
    final_norm = (jax.random.normal(next(keys), (model["d_model"],)) * 0.1
                  ).astype(BF16)
    ad = {}
    for (pos, name, ab), shape in layout:
        ad[(pos, name, ab)] = (jax.random.normal(next(keys), shape)
                               * shape[1] ** -0.5 if ab == "a"
                               else jnp.zeros(shape, jnp.float32))
    return embed, final_norm, flatten(model, ad)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def lora(x, w, a, b, scale):
    return mm(x, w.astype(jnp.float32)) + mm(mm(x, a), b) * scale


def scan(u, delta, a_log, b, c, d, z, bf16_state=False):
    """The selective scan over time (one sequence): u, delta, z (S, di);
    b, c (S, N); a_log (di, N); d (di,).  The state is float32, or with
    ``bf16_state`` rounded to bfloat16 at every step."""
    A = -jnp.exp(a_log)

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * u_t)[:, None] * b_t[None, :]
        # rounded by an explicit reduce-precision op: a bfloat16 round
        # trip of converts may be kept in float32 by the compiler
        h = jnp.where(bf16_state, jax.lax.reduce_precision(h, 8, 7), h)
        return h, jnp.sum(h * c_t[None, :], axis=1)

    h0 = jnp.zeros(a_log.shape, jnp.float32)
    _, y = jax.lax.scan(step, h0, (u, delta, b, c))
    return (y + d * u) * jax.nn.silu(z)


def mamba_terms(model, lw, ad, x, scale, norms):
    """The selective scan's inputs of a Mamba mixer on x (S, D): u, Δ, z
    (S, di); B, C (S, N)."""
    k = dims(model)
    eps = model["rms_norm_eps"]
    uz = lora(x, lw["in_proj"], *ad["in_proj"], scale)
    u, z = uz[:, :k["di"]], uz[:, k["di"]:]
    S = u.shape[0]
    up = jnp.pad(u, ((k["kc"] - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    u = sum(up[i:i + S] * w[i] for i in range(k["kc"])) \
        + lw["conv_b"].astype(jnp.float32)
    u = jax.nn.silu(u)
    dbc = mm(u, lw["x_proj"].astype(jnp.float32))
    dl, B, C = (dbc[:, :k["r"]], dbc[:, k["r"]:k["r"] + k["n"]],
                dbc[:, k["r"] + k["n"]:])
    dl, B, C = (jnp.where(norms, rms(t, lw[w], eps), t)
                for t, w in ((dl, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
    delta = jax.nn.softplus(mm(dl, lw["dt_proj"].astype(jnp.float32))
                            + lw["dt_bias"])
    return u, delta, B, C, z


def mamba(model, lw, ad, x, scale, bf16_state, norms):
    u, delta, B, C, z = mamba_terms(model, lw, ad, x, scale, norms)
    y = scan(u, delta, lw["A_log"], B, C, lw["D"], z, bf16_state)
    return lora(y, lw["out_proj"], *ad["out_proj"], scale)


def attention(model, lw, ad, x, scale):
    S = x.shape[0]
    H, hd, G = model["n_heads"], model["head_dim"], model["n_kv_heads"]
    q = lora(x, lw["q"], *ad["q"], scale).reshape(S, G, H // G, hd)
    kk = lora(x, lw["k"], *ad["k"], scale).reshape(S, G, hd)
    v = lora(x, lw["v"], *ad["v"], scale).reshape(S, G, hd)
    s = jnp.einsum("qgjd,kgd->gjqk", q, kk, precision=HI) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("gjqk,kgd->qgjd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(S, H * hd)
    return lora(o, lw["o"], *ad["o"], scale)


def hidden(model: dict, base: dict, row: jax.Array, tokens: jax.Array,
           bf16_state=False, norms=True) -> jax.Array:
    """tokens (S,) -> final-norm hidden states (S, D), float32.  Each run of
    consecutive layers of one kind is a ``lax.scan`` over their stacked
    weights, each layer under ``jax.checkpoint``: one compiled layer body a
    run, however deep the model."""
    ad_all = unflatten(model, row)
    scale = model["lora_alpha"] / model["lora_rank"]
    eps = model["rms_norm_eps"]
    plen = model["attn_layer_period"]

    def weights(l):
        pos, per = l % plen, l // plen
        lw = {n: v[per] for n, v in base["layers"][pos].items()}
        ad = {name: (ad_all[(pos, name, "a")][per], ad_all[(pos, name, "b")][per])
              for name in targets(model, pos)}
        return lw, ad

    def layer(attn, h, w):
        lw, ad = w
        x = rms(h, lw["norm1"], eps)
        if attn:
            h = h + attention(model, lw, ad, x, scale)
        else:
            h = h + mamba(model, lw, ad, x, scale, bf16_state, norms)
        n = rms(h, lw["norm2"], eps)
        g = mm(n, lw["w_gate"].astype(jnp.float32))
        up = mm(n, lw["w_up"].astype(jnp.float32))
        return h + mm(jax.nn.silu(g) * up, lw["w_down"].astype(jnp.float32))

    kinds = [is_attn(model, l % plen) for l in range(model["n_layers"])]
    h = base["embed"][tokens].astype(jnp.float32)
    start = 0
    while start < len(kinds):
        end = start + 1
        while end < len(kinds) and kinds[end] == kinds[start]:
            end += 1
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[weights(l) for l in range(start, end)])
        body = jax.checkpoint(partial(layer, kinds[start]))
        h, _ = jax.lax.scan(lambda h, w: (body(h, w), None), h, stacked)
        start = end
    return rms(h, base["final_norm"], eps)


def logits(model, base, row, tokens, bf16_state=False, norms=True):
    h = hidden(model, base, row, tokens, bf16_state, norms)
    return mm(h, base["embed"].astype(jnp.float32).T)


def loss(model, base, row, x, y, bf16_state=False, norms=True,
         n_tokens=None):
    """Mean next-token cross-entropy of one client's rows x, y (R, S), over
    the first ``n_tokens`` positions of each row (all by default; the
    model is causal, so the positions after them change nothing)."""
    S = x.shape[-1]
    keep = jnp.arange(S) < (S if n_tokens is None else n_tokens)

    def one(xy):
        z = logits(model, base, row, xy[0], bf16_state, norms)
        nll = jax.nn.logsumexp(z, axis=-1) \
            - jnp.take_along_axis(z, xy[1][:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0)) / jnp.sum(keep)
    return jnp.mean(jax.lax.map(one, (x, y)))


# --------------------------------------------------------------------------- #
# local training and PAA
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnums=(0, 5))
def train_client(mk, base, row, x, y, steps: int, lr, bf16_state, norms,
                 n_tokens):
    """One client's local training from a fresh Adam state (b1 0.9, b2
    0.999, eps 1e-8): ``steps`` steps cycling over its (nb, R, S) rows, the
    loss over the first ``n_tokens`` of each row, with ``norms`` (Jamba's
    Δ/B/C norms) and ``bf16_state`` as in :func:`hidden`.  Returns the
    trained row and the mean loss of the steps."""
    model = _m(mk)
    b1, b2, eps = 0.9, 0.999, 1e-8
    f = jax.value_and_grad(lambda r, bx, by: loss(
        model, base, r, bx, by, bf16_state, norms, n_tokens))
    m = jnp.zeros_like(row)
    v = jnp.zeros_like(row)
    total = jnp.zeros((), jnp.float32)
    nb = x.shape[0]
    for t in range(1, steps + 1):
        val, g = f(row, x[(t - 1) % nb], y[(t - 1) % nb])
        total = total + val
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        row = row - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                                + eps)
    return row, total / steps


@partial(jax.jit, static_argnums=(0,))
def prototype(mk, base, row, probe, bf16_state=False):
    """PAA's representation of a row: the final-norm hidden state averaged
    over the probe batch's positions and rows, (D,)."""
    model = _m(mk)
    return jnp.mean(jax.lax.map(lambda t: jnp.mean(hidden(
        model, base, row, t, bf16_state), axis=0), probe), axis=0)


@partial(jax.jit, static_argnums=(0,))
def client_logits(mk, base, row, tokens, bf16_state=False, norms=True):
    return logits(_m(mk), base, row, tokens, bf16_state, norms)


# --------------------------------------------------------------------------- #
# the selective scan alone
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnums=(0,))
def first_scan_inputs(mk, base, row, tokens):
    """The scan's arguments in the model's first layer, a Mamba layer, with
    adapters ``row`` on tokens (S,): (u, Δ, A_log, B, C, D, z) as
    :func:`scan` takes them."""
    model = _m(mk)
    if is_attn(model, 0):
        raise ValueError("the model's first layer is not a Mamba layer")
    ad_all = unflatten(model, row)
    lw = {n: v[0] for n, v in base["layers"][0].items()}
    ad = {name: (ad_all[(0, name, "a")][0], ad_all[(0, name, "b")][0])
          for name in targets(model, 0)}
    x = rms(base["embed"][tokens], lw["norm1"], model["rms_norm_eps"])
    u, delta, B, C, z = mamba_terms(model, lw, ad, x,
                                    model["lora_alpha"] / model["lora_rank"],
                                    True)
    return u, delta, lw["A_log"], B, C, lw["D"], z


scan_alone = jax.jit(scan)
