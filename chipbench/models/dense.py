"""The dense decoder: the repo's transformer, as ``chipbench/models`` asks.

Pre-norm decoder blocks with RMSNorm, rotary position embedding
(rotate-half convention over ``partial_rotary_factor`` of each head), causal
softmax attention with grouped key/value heads and optional QKV biases, a
SwiGLU MLP, and a tied or untied head; the loss is the next-token
cross-entropy. The configuration is the file as it is run (``departures`` in
it). The parameter tree is the program's own layout: stacked layers and
``(d, heads, head_dim)`` projections.

The reference's forward pass runs in blocks so that it fits: a checkpoint
around each layer and each block of query rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import _mm, _rms
from chipbench.weights import ONES, ZEROS

Q_CHUNK = 1024  # query rows per attention block

SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=512, num_hidden_layers=2)
# below the published widths a model's logits are too small for fp8 to move its loss
WIDE = dict(num_hidden_layers=1, vocab_size=4096)

# configuration-file key -> ModelConfig field, for every key that shapes the model
_FIELDS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "head_dim": "head_dim",
}


def dims(cfg: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "k": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // h),
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def _qkv_bias(cfg: dict) -> bool:
    return bool(cfg.get("use_qkv_bias", cfg.get("qkv_bias", False)))


def _eps(cfg: dict) -> float:
    return cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))


def leaf_specs(cfg: dict) -> dict:
    """{path: (shape, init)} of every parameter; init is a std, ONES or ZEROS."""
    s = dims(cfg)
    d, h, k, hd, ff, v, L = (s[n] for n in ("d", "h", "k", "hd", "ff", "v", "L"))
    std = 0.02
    # output projections scale with the depth of the whole published model
    out_std = std / math.sqrt(2 * cfg["reduced"].get("num_hidden_layers", [L])[0])
    specs = {
        "embed/tok": ((v, d), std),
        "layers/ln1/w": ((L, d), ONES),
        "layers/attn/wq": ((L, d, h, hd), std),
        "layers/attn/wk": ((L, d, k, hd), std),
        "layers/attn/wv": ((L, d, k, hd), std),
        "layers/attn/wo": ((L, h, hd, d), out_std),
        "layers/ln2/w": ((L, d), ONES),
        "layers/mlp/wi": ((L, d, ff), std),
        "layers/mlp/wg": ((L, d, ff), std),
        "layers/mlp/wo": ((L, ff, d), out_std),
        "final_norm/w": ((d,), ONES),
    }
    if _qkv_bias(cfg):
        specs.update({"layers/attn/bq": ((L, h, hd), ZEROS),
                      "layers/attn/bk": ((L, k, hd), ZEROS),
                      "layers/attn/bv": ((L, k, hd), ZEROS)})
    if not cfg["tie_word_embeddings"]:
        specs["head/w"] = ((d, v), std)
    return specs


def _rope(x, positions, theta, rot):
    """Rotate the first ``rot`` dims of each head (rotate-half convention)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(q, k, v, precision):
    """Causal softmax attention, one checkpointed block of query rows at a time."""
    s, hd = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    if g > 1:
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    out = []
    for start in range(0, s, Q_CHUNK):
        stop = min(start + Q_CHUNK, s)

        @jax.checkpoint
        def block(qb, kb, vb, start=start, stop=stop):
            scores = _mm("bqhd,bkhd->bhqk", qb, kb, precision) / math.sqrt(hd)
            keep = jnp.arange(start, stop)[:, None] >= jnp.arange(stop)[None, :]
            scores = jnp.where(keep, scores, -jnp.inf)
            return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), vb, precision)

        out.append(block(q[:, start:stop], k[:, :stop], v[:, :stop]))
    return jnp.concatenate(out, axis=1)


def nll_sum(params, tokens, cfg, precision="f32", targets=None):
    """Sum over the rows of ``tokens`` of the next-token negative log-likelihood
    of the first ``targets`` positions (all by default); ``params`` is the
    float32 tree of ``chipbench.weights``."""
    s = dims(cfg)
    eps = _eps(cfg)
    rot = int(s["hd"] * cfg.get("partial_rotary_factor", 1.0))
    seq = tokens.shape[1]
    targets = targets or seq - 1
    positions = jnp.arange(seq)
    x = params["embed"]["tok"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["w"], eps)
        q = _mm("bsd,dhk->bshk", h, a["wq"], precision)
        k = _mm("bsd,dhk->bshk", h, a["wk"], precision)
        v = _mm("bsd,dhk->bshk", h, a["wv"], precision)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q, positions, cfg["rope_theta"], rot)
        k = _rope(k, positions, cfg["rope_theta"], rot)
        x = x + _mm("bshk,hkd->bsd", _attention(q, k, v, precision), a["wo"], precision)
        m = lp["mlp"]
        h = _rms(x, lp["ln2"]["w"], eps)
        gate = _mm("bsd,df->bsf", h, m["wg"], precision)
        up = _mm("bsd,df->bsf", h, m["wi"], precision)
        return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["wo"], precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[:, :targets], params["final_norm"]["w"], eps)
    head = params["embed"]["tok"].T if cfg["tie_word_embeddings"] else params["head"]["w"]
    logp = jax.nn.log_softmax(_mm("bsd,dv->bsv", x, head, precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:targets + 1, None], axis=-1))


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul: every projection of every layer and the
    head (a tied head once); the embedding lookup, norms and biases are not
    matmuls."""
    s = dims(cfg)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["k"])
    mlp = 3 * s["d"] * s["ff"]
    return s["L"] * (attn + mlp) + s["d"] * s["v"]


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token: 6 per matmul parameter (forward and backward)
    plus causal attention, 6 * layers * seq_len * (heads * head_dim): its
    scores and weighted sum take 2 * 2 * (seq_len / 2) * heads * head_dim per
    layer forward, and the backward twice that. Recomputation is not counted."""
    s = dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * s["L"] * seq_len * s["h"] * s["hd"]


def program_config(cfg: dict):
    """The registry's ModelConfig with every model key of the file applied."""
    from repro.configs import get_config

    if cfg.get("partial_rotary_factor", 1.0) != 1.0 or cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: the program has no path for this configuration")
    fields = {f: cfg[k] for k, f in _FIELDS.items() if k in cfg}
    dtype = cfg["torch_dtype"]
    return get_config(cfg["registry"]).with_(
        **fields, norm_eps=_eps(cfg), qkv_bias=_qkv_bias(cfg),
        param_dtype=dtype, activation_dtype=dtype, optimizer=cfg["optimizer"]["name"])
