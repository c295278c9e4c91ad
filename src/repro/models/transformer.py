"""TransformerLM: scan-over-layers decoder covering dense / moe / ssm / hybrid
/ vlm families. Functional: ``init`` builds the parameter pytree (stacked
layer weights for lax.scan), ``forward`` / ``prefill`` / ``decode_step`` are
pure functions.

Layer stacking: per-layer parameters are created under vmap so every leaf has
a leading (L, ...) axis and the layer loop is a single `lax.scan` — keeps HLO
size and compile time flat in depth (95-layer deepseek compiles like 24-layer
qwen) and is what makes the 512-device dry-run tractable.

Hybrid (zamba2): `hybrid_attn_every` mamba layers alternate with ONE shared
full transformer block (weights reused at every application, per-application
KV cache) — the Zamba2 pattern. Remainder mamba layers run after the last
shared-block application. (Zamba2's concat-with-embedding input to the shared
block is simplified to a plain residual input; noted in DESIGN.md.)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro import trace
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.sharding import hints
from repro.models.layers import (
    AxesRecorder,
    apply_mlp,
    embed,
    init_embedding,
    init_lm_head,
    init_mlp,
    init_rms_norm,
    param,
    rms_norm,
)

_REC = AxesRecorder()  # logical axes resolved post-hoc by sharding/rules.py


def _remat(f, cfg):
    if cfg.remat == "none":
        return f
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(f, policy=pol)
    return jax.checkpoint(f)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_dense_layer(key, cfg):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": init_rms_norm(cfg.d_model, jnp.dtype(cfg.param_dtype), _REC, "ln1"),
        "attn": attn.init_attention(ks[0], cfg, _REC, "attn"),
        "ln2": init_rms_norm(cfg.d_model, jnp.dtype(cfg.param_dtype), _REC, "ln2"),
    }
    if cfg.family == "moe":
        p["moe"] = moe.init_moe(ks[1], cfg, _REC, "moe")
        if cfg.moe_dense_ff:
            p["dense_mlp"] = init_mlp(ks[2], cfg, _REC, "dense_mlp", d_ff=cfg.moe_dense_ff)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, _REC, "mlp")
    return p


def _init_mamba_layer(key, cfg):
    return {
        "ln1": init_rms_norm(cfg.d_model, jnp.dtype(cfg.param_dtype), _REC, "ln1"),
        "mamba": mamba2.init_mamba2(key, cfg, _REC, "mamba"),
    }


def init_lm(key, cfg):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": init_embedding(ks[0], cfg, _REC, "embed")}

    if cfg.family in ("dense", "moe", "vlm"):
        layer_keys = jax.random.split(ks[1], cfg.num_layers)
        params["layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg))(layer_keys)
    elif cfg.family == "ssm":
        layer_keys = jax.random.split(ks[1], cfg.num_layers)
        params["layers"] = jax.vmap(lambda k: _init_mamba_layer(k, cfg))(layer_keys)
    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        ngroups = cfg.num_layers // every
        grouped = ngroups * every
        layer_keys = jax.random.split(ks[1], cfg.num_layers)
        stacked = jax.vmap(lambda k: _init_mamba_layer(k, cfg))(layer_keys)
        params["layers"] = jax.tree.map(
            lambda x: x[:grouped].reshape(ngroups, every, *x.shape[1:]), stacked
        )
        params["tail_layers"] = jax.tree.map(lambda x: x[grouped:], stacked)
        params["shared"] = _init_dense_layer(ks[2], cfg.with_(family="dense"))
    else:
        raise ValueError(cfg.family)

    params["final_norm"] = init_rms_norm(cfg.d_model, jnp.dtype(cfg.param_dtype), _REC, "fn")
    params["head"] = init_lm_head(ks[3], cfg, _REC, "head")
    if cfg.family == "vlm":
        params["vlm_proj"] = {
            "w": param(ks[4], (cfg.d_model, cfg.d_model), ("embed", "embed2"),
                       jnp.dtype(cfg.param_dtype), _REC, "vlm_proj/w")
        }
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _sp(x, cfg):
    """Sequence parallelism (Megatron SP): between the TP einsum segments the
    residual stream shards its seq axis over 'model', so norms/residuals/
    elementwise ops touch 1/TP of the activation bytes. XLA converts the
    attention-out all-reduce into reduce-scatter + all-gather (same wire)."""
    if not cfg.seq_parallel:
        return x
    return hints.constrain(x, "batch", "model", None)


def _dense_block(lp, x, cfg, positions):
    with trace.scope("model.attn"):
        h = attn.attention_train(lp["attn"], rms_norm(x, lp["ln1"]["w"], cfg.norm_eps), cfg, positions)
        x = _sp(x + h, cfg)
    with trace.scope("model.mlp"):
        y = rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
        aux = jnp.float32(0)
        if "moe" in lp:
            out, aux = moe.apply_moe(lp["moe"], y, cfg)
            if "dense_mlp" in lp:
                out = out + apply_mlp(lp["dense_mlp"], y, cfg)
        else:
            out = apply_mlp(lp["mlp"], y, cfg)
        return _sp(x + out, cfg), aux


def _mamba_block(lp, x, cfg):
    h, _, _ = mamba2.apply_mamba2(lp["mamba"], rms_norm(x, lp["ln1"]["w"], cfg.norm_eps), cfg)
    return x + h


# ---------------------------------------------------------------------------
# forward (train)
# ---------------------------------------------------------------------------


def _input_embeds(params, batch, cfg):
    with trace.scope("model.embed"):
        toks = batch["tokens"]
        x = embed(params["embed"], toks)
        if cfg.family == "vlm":
            patches = jnp.einsum("bpd,de->bpe", batch["patch_embeds"].astype(x.dtype),
                                 params["vlm_proj"]["w"])
            x = jnp.concatenate([patches, x], axis=1)
        # anchor the activation layout: batch over replica axes, d_model
        # replicated (TP reshards at the einsums). Without this anchor the
        # vocab-sharded embedding gather can leave the batch axis replicated
        # (kimi dry-run: 107 GB/device saved-activation stacks).
        x = hints.constrain(x, "batch", None, None)
        return x.astype(jnp.dtype(cfg.activation_dtype))


def forward(params, batch, cfg):
    """Returns (logits (B, S, V), aux_loss)."""
    x = _input_embeds(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    aux_total = jnp.float32(0)
    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            y, aux = _dense_block(lp, carry, cfg, positions)
            return y, aux

        x, auxes = jax.lax.scan(_remat(body, cfg), x, params["layers"])
        aux_total = auxes.sum()
    elif cfg.family == "ssm":
        def body(carry, lp):
            return _mamba_block(lp, carry, cfg), None

        x, _ = jax.lax.scan(_remat(body, cfg), x, params["layers"])
    elif cfg.family == "hybrid":
        shared = params["shared"]

        def group(carry, glp):
            def inner(c, lp):
                return _mamba_block(lp, c, cfg), None

            y, _ = jax.lax.scan(inner, carry, glp)
            y, _ = _dense_block(shared, y, cfg, positions)
            return y, None

        x, _ = jax.lax.scan(_remat(group, cfg), x, params["layers"])

        def tail(carry, lp):
            return _mamba_block(lp, carry, cfg), None

        x, _ = jax.lax.scan(_remat(tail, cfg), x, params["tail_layers"])

    with trace.scope("model.head"):
        x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
        w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
        logits = jnp.einsum("bsd,dv->bsv", x, w)
        logits = hints.constrain(logits, "batch", None, "model")
    return logits, aux_total


def loss_fn(params, batch, cfg):
    logits, aux = forward(params, batch, cfg)
    toks = batch["tokens"]
    with trace.scope("model.head"):
        if cfg.family == "vlm":
            npatch = batch["patch_embeds"].shape[1]
            logits = logits[:, npatch:, :]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = toks[:, 1:]
        nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
        return nll.mean() + 0.01 * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


class LMCache(NamedTuple):
    kv: Any  # dense: KVCache stacked (L, ...); hybrid: (ngroups, ...)
    ssm: Any  # (L, B, H, P, N) or None
    conv: Any
    pos: jax.Array  # scalar int32 — number of tokens already in cache


def init_cache(cfg, batch: int, max_len: int):
    dt = jnp.dtype(cfg.activation_dtype)
    kv = ssm = conv = None
    if cfg.family in ("dense", "moe", "vlm"):
        kv = attn.KVCache(
            k=jnp.zeros((cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim), dt),
            v=jnp.zeros((cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim), dt),
        )
    elif cfg.family == "ssm":
        s0, c0 = mamba2.init_ssm_state(batch, cfg)
        ssm = jnp.broadcast_to(s0, (cfg.num_layers, *s0.shape))
        conv = jnp.broadcast_to(c0, (cfg.num_layers, *c0.shape))
    elif cfg.family == "hybrid":
        ng = cfg.num_layers // cfg.hybrid_attn_every
        kv = attn.KVCache(
            k=jnp.zeros((ng, batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim), dt),
            v=jnp.zeros((ng, batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim), dt),
        )
        s0, c0 = mamba2.init_ssm_state(batch, cfg)
        ssm = jnp.broadcast_to(s0, (cfg.num_layers, *s0.shape))
        conv = jnp.broadcast_to(c0, (cfg.num_layers, *c0.shape))
    return LMCache(kv=kv, ssm=ssm, conv=conv, pos=jnp.int32(0))


def _dense_block_decode(lp, x, cfg, cache_l, pos):
    h, cache_l = attn.attention_decode(
        lp["attn"], rms_norm(x, lp["ln1"]["w"], cfg.norm_eps), cfg, cache_l, pos
    )
    x = x + h
    y = rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
    if "moe" in lp:
        out, _ = moe.apply_moe(lp["moe"], y, cfg)
        if "dense_mlp" in lp:
            out = out + apply_mlp(lp["dense_mlp"], y, cfg)
    else:
        out = apply_mlp(lp["mlp"], y, cfg)
    return x + out, cache_l


def _mamba_block_decode(lp, x, cfg, ssm_l, conv_l):
    y = rms_norm(x, lp["ln1"]["w"], cfg.norm_eps)
    h, ssm_l, conv_l = mamba2.apply_mamba2(lp["mamba"], y, cfg, ssm_l, conv_l, decode=True)
    return x + h, ssm_l, conv_l


def decode_step(params, tokens, cache: LMCache, cfg):
    """tokens: (B, 1) int32. Returns (logits (B, 1, V), new cache)."""
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.activation_dtype))
    pos = cache.pos

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, xs):
            lp, k_l, v_l = xs
            y, new_c = _dense_block_decode(lp, carry, cfg, attn.KVCache(k_l, v_l), pos)
            return y, (new_c.k, new_c.v)

        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache.kv.k, cache.kv.v))
        new_cache = LMCache(kv=attn.KVCache(ks, vs), ssm=None, conv=None, pos=pos + 1)
    elif cfg.family == "ssm":
        def body(carry, xs):
            lp, s_l, c_l = xs
            y, s_l, c_l = _mamba_block_decode(lp, carry, cfg, s_l, c_l)
            return y, (s_l, c_l)

        x, (ss, cs) = jax.lax.scan(body, x, (params["layers"], cache.ssm, cache.conv))
        new_cache = LMCache(kv=None, ssm=ss, conv=cs, pos=pos + 1)
    elif cfg.family == "hybrid":
        shared = params["shared"]
        every = cfg.hybrid_attn_every
        ng = cfg.num_layers // every
        grouped = ng * every
        ssm_g = jax.tree.map(lambda a: a[:grouped].reshape(ng, every, *a.shape[1:]),
                             (cache.ssm, cache.conv))

        def group(carry, xs):
            glp, (s_g, c_g), k_g, v_g = xs

            def inner(c, ys):
                lp, s_l, c_l = ys
                y, s_l, c_l = _mamba_block_decode(lp, c, cfg, s_l, c_l)
                return y, (s_l, c_l)

            y, (s_new, c_new) = jax.lax.scan(inner, carry, (glp, s_g, c_g))
            y, kv_new = _dense_block_decode(shared, y, cfg, attn.KVCache(k_g, v_g), pos)
            return y, (s_new, c_new, kv_new.k, kv_new.v)

        x, (ss, cs, ks, vs) = jax.lax.scan(
            group, x, (params["layers"], ssm_g, cache.kv.k, cache.kv.v)
        )

        def tail(carry, ys):
            lp, s_l, c_l = ys
            y, s_l, c_l = _mamba_block_decode(lp, carry, cfg, s_l, c_l)
            return y, (s_l, c_l)

        x, (ts, tc) = jax.lax.scan(
            tail, x, (params["tail_layers"], cache.ssm[grouped:], cache.conv[grouped:])
        )
        new_ssm = jnp.concatenate([ss.reshape(grouped, *ss.shape[2:]), ts], axis=0)
        new_conv = jnp.concatenate([cs.reshape(grouped, *cs.shape[2:]), tc], axis=0)
        new_cache = LMCache(kv=attn.KVCache(ks, vs), ssm=new_ssm, conv=new_conv, pos=pos + 1)

    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return logits, new_cache


def _dense_block_decode_paged(lp, x, cfg, k_pool, v_pool, page_table, lens):
    h, k_pool, v_pool = attn.attention_decode_paged(
        lp["attn"], rms_norm(x, lp["ln1"]["w"], cfg.norm_eps), cfg,
        k_pool, v_pool, page_table, lens,
    )
    x = x + h
    y = rms_norm(x, lp["ln2"]["w"], cfg.norm_eps)
    if "moe" in lp:
        out, _ = moe.apply_moe(lp["moe"], y, cfg)
        if "dense_mlp" in lp:
            out = out + apply_mlp(lp["dense_mlp"], y, cfg)
    else:
        out = apply_mlp(lp["mlp"], y, cfg)
    return x + out, k_pool, v_pool


def decode_step_paged(params, tokens, k_pools, v_pools, page_table, lens, cfg):
    """Per-slot decode through a paged KV pool (continuous-batching serving).

    tokens: (B, 1) int32; k_pools/v_pools: (L, NP, page, K, hd) global page
    pools; page_table: (B, MP) int32; lens: (B,) int32 per-slot cache
    lengths. Unlike :func:`decode_step` (one shared scalar position), every
    slot advances at its OWN position — the shape continuous batching needs,
    where slots hold requests admitted at different times. Only attention-KV
    families page (dense/moe/vlm); ssm/hybrid keep per-slot recurrent state
    that has no sequence axis to page.

    Returns (logits (B, 1, V), new k_pools, new v_pools).
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"decode_step_paged supports dense/moe/vlm families, got "
            f"{cfg.family!r} — use the static engine for ssm/hybrid")
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.activation_dtype))

    def body(carry, xs):
        lp, k_l, v_l = xs
        y, k_l, v_l = _dense_block_decode_paged(
            lp, carry, cfg, k_l, v_l, page_table, lens)
        return y, (k_l, v_l)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], k_pools, v_pools))
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return logits, ks, vs


def prefill(params, batch, cache: LMCache, cfg):
    """Run the full prompt through the model, filling caches.

    Returns (last-position logits (B, 1, V), cache)."""
    x = _input_embeds(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, xs):
            lp, k_l, v_l = xs
            h, new_c = attn.attention_prefill(
                lp["attn"], rms_norm(carry, lp["ln1"]["w"], cfg.norm_eps), cfg, positions,
                attn.KVCache(k_l, v_l),
            )
            y = carry + h
            z = rms_norm(y, lp["ln2"]["w"], cfg.norm_eps)
            if "moe" in lp:
                out, _ = moe.apply_moe(lp["moe"], z, cfg)
                if "dense_mlp" in lp:
                    out = out + apply_mlp(lp["dense_mlp"], z, cfg)
            else:
                out = apply_mlp(lp["mlp"], z, cfg)
            return y + out, (new_c.k, new_c.v)

        x, (ks, vs) = jax.lax.scan(_remat(body, cfg), x, (params["layers"], cache.kv.k, cache.kv.v))
        new_cache = LMCache(kv=attn.KVCache(ks, vs), ssm=None, conv=None, pos=jnp.int32(s))
    elif cfg.family == "ssm":
        def body(carry, xs):
            lp, _s, _c = xs
            y = rms_norm(carry, lp["ln1"]["w"], cfg.norm_eps)
            h, s_new, c_new = mamba2.apply_mamba2(lp["mamba"], y, cfg)
            return carry + h, (s_new, c_new)

        x, (ss, cs) = jax.lax.scan(_remat(body, cfg), x, (params["layers"], cache.ssm, cache.conv))
        new_cache = LMCache(kv=None, ssm=ss, conv=cs, pos=jnp.int32(s))
    elif cfg.family == "hybrid":
        shared = params["shared"]
        every = cfg.hybrid_attn_every
        ng = cfg.num_layers // every
        grouped = ng * every

        def group(carry, xs):
            glp, k_g, v_g = xs

            def inner(c, lp):
                y = rms_norm(c, lp["ln1"]["w"], cfg.norm_eps)
                h, s_new, c_new = mamba2.apply_mamba2(lp["mamba"], y, cfg)
                return c + h, (s_new, c_new)

            y, (s_g, c_g) = jax.lax.scan(inner, carry, glp)
            h, kv_new = attn.attention_prefill(
                shared["attn"], rms_norm(y, shared["ln1"]["w"], cfg.norm_eps), cfg, positions,
                attn.KVCache(k_g, v_g),
            )
            y = y + h
            z = rms_norm(y, shared["ln2"]["w"], cfg.norm_eps)
            y = y + apply_mlp(shared["mlp"], z, cfg)
            return y, (s_g, c_g, kv_new.k, kv_new.v)

        x, (ss, cs, ks, vs) = jax.lax.scan(
            _remat(group, cfg), x, (params["layers"], cache.kv.k, cache.kv.v)
        )

        def tail(carry, lp):
            y = rms_norm(carry, lp["ln1"]["w"], cfg.norm_eps)
            h, s_new, c_new = mamba2.apply_mamba2(lp["mamba"], y, cfg)
            return carry + h, (s_new, c_new)

        x, (ts, tc) = jax.lax.scan(_remat(tail, cfg), x, params["tail_layers"])
        new_ssm = jnp.concatenate([ss.reshape(grouped, *ss.shape[2:]), ts], axis=0)
        new_conv = jnp.concatenate([cs.reshape(grouped, *cs.shape[2:]), tc], axis=0)
        new_cache = LMCache(kv=attn.KVCache(ks, vs), ssm=new_ssm, conv=new_conv, pos=jnp.int32(s))

    x = rms_norm(x[:, -1:, :], params["final_norm"]["w"], cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return logits, new_cache
