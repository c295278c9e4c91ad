"""Attention: flash-style chunked GQA (train/prefill), decode, cross-attn.

The chunked path enumerates only the (q-chunk, kv-chunk) pairs that the mask
allows (causal: lower triangle of chunks), scanning over a *static* pair list
with online-softmax state — so HLO FLOPs equal the true causal FLOPs (no
wasted upper-triangle work) and peak memory is O(B*H*Cq*Ck) per step instead
of O(B*H*S^2). This matters for prefill_32k roofline numbers and is the
standard TPU adaptation of flash attention in pure JAX.

Causal self-attention over two kernel blocks or more (``flash_applies``)
runs instead in a Pallas flash kernel (``flash_attention``, jax's splash
attention): its score tiles stay in VMEM, where the chunked path writes
each (q-chunk, kv-chunk) tile to HBM. Shorter sequences, non-causal
attention and tensor-parallel heads keep the chunked path.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import AxisType

from repro import trace
from repro.kernels import ops
from repro.models.layers import apply_rope, param

NEG_INF = -1e30

# Query and key rows per flash-kernel block; a sequence takes the kernel from
# two blocks up. Timed on a TPU v5e at stablelm-3b's shape (PERF.md §6):
# 1024-row blocks (the forward over 512-key slices) with one fused dq/dkv
# backward kernel beat 512-row blocks and separate dq and dkv kernels.
FLASH_BLOCK = 1024
_FLASH_BLOCKS = splash.BlockSizes(
    block_q=FLASH_BLOCK, block_kv=FLASH_BLOCK, block_kv_compute=512,
    block_q_dkv=FLASH_BLOCK, block_kv_dkv=FLASH_BLOCK, block_kv_dkv_compute=FLASH_BLOCK,
    use_fused_bwd_kernel=True,
)


def init_attention(key, cfg, rec, path, cross: bool = False):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "wq": param(ks[0], (d, h, hd), ("embed", "heads", "head_dim"), dt, rec, path + "/wq"),
        "wk": param(ks[1], (d, k, hd), ("embed", "kv_heads", "head_dim"), dt, rec, path + "/wk"),
        "wv": param(ks[2], (d, k, hd), ("embed", "kv_heads", "head_dim"), dt, rec, path + "/wv"),
        "wo": param(ks[3], (h, hd, d), ("heads", "head_dim", "embed"), dt, rec, path + "/wo",
                    scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = param(ks[4], (h, hd), ("heads", "head_dim"), dt, rec, path + "/bq", scale=0.0)
        p["bk"] = param(ks[4], (k, hd), ("kv_heads", "head_dim"), dt, rec, path + "/bk", scale=0.0)
        p["bv"] = param(ks[4], (k, hd), ("kv_heads", "head_dim"), dt, rec, path + "/bv", scale=0.0)
    return p


def _qkv(p, x, cfg, positions=None, rope: bool = True, heads_first: bool = False):
    """q, k, v (B, S, H, hd), or (B, H, S, hd) with ``heads_first``, the
    flash kernel's layout, straight from the projections."""
    spec = "bsd,dhk->bhsk" if heads_first else "bsd,dhk->bshk"
    q = jnp.einsum(spec, x, p["wq"])
    k = jnp.einsum(spec, x, p["wk"])
    v = jnp.einsum(spec, x, p["wv"])
    if "bq" in p:  # (H, hd); over (H, S, hd) in the kernel's layout
        bq, bk, bv = (p[n][:, None] if heads_first else p[n] for n in ("bq", "bk", "bv"))
        q, k, v = q + bq, k + bk, v + bv
    if rope and positions is not None:
        from repro.models.layers import rope_angles

        cos, sin = rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin, heads_first)
        k = apply_rope(k, cos, sin, heads_first)
    return q, k, v


def _pair_list(nq: int, nk: int, causal: bool):
    if causal:
        assert nq == nk
        pairs = [(i, j) for i in range(nq) for j in range(i + 1)]
    else:
        pairs = [(i, j) for i in range(nq) for j in range(nk)]
    return jnp.asarray(pairs, jnp.int32)


def chunked_attention(q, k, v, *, causal: bool, q_chunk: int, num_kv_heads: int,
                      remat_step: bool = True):
    """q: (B,S,H,hd); k,v: (B,Sk,K,hd). Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    sk = k.shape[1]
    kvh = num_kv_heads
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)

    cq = min(q_chunk, s)
    ck = min(q_chunk, sk)
    # fall back to exact divisibility (shapes here are powers of two)
    while s % cq:
        cq //= 2
    while sk % ck:
        ck //= 2
    nq, nk = s // cq, sk // ck

    if nq == 1 and nk == 1:
        qf = q.reshape(b, s, kvh, g, hd)
        scores = jnp.einsum("bqkgh,bckh->bkgqc", qf, k).astype(jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((s, sk), bool))
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqc,bckh->bqkgh", w.astype(v.dtype), v)
        return out.reshape(b, s, h, hd)

    pairs = _pair_list(nq, nk, causal)

    qc = q.reshape(b, nq, cq, kvh, g, hd)
    kc = k.reshape(b, nk, ck, kvh, hd)
    vc = v.reshape(b, nk, ck, kvh, hd)

    o0 = jnp.zeros((nq, b, cq, kvh, g, hd), jnp.float32)
    m0 = jnp.full((nq, b, cq, kvh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, b, cq, kvh, g), jnp.float32)

    def step(state, pair):
        o, m, l = state
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qc, i, axis=1, keepdims=False)  # (b,cq,K,g,hd)
        kj = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)  # (b,ck,K,hd)
        vj = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
        scores = jnp.einsum("bqkgh,bckh->bqkgc", qi, kj).astype(jnp.float32) * scale
        if causal:
            # global-position causal mask, loop-variant through (i, j) so XLA
            # fuses it into the scores computation instead of hoisting a
            # materialized mask out of the scan (off-diagonal pairs are
            # all-true and fold away)
            rows = i * cq + jnp.arange(cq)
            cols = j * ck + jnp.arange(ck)
            keep = rows[:, None] >= cols[None, :]
            scores = jnp.where(keep[None, :, None, None, :], scores, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, axis=0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, axis=0, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, axis=0, keepdims=False)
        m_new = jnp.maximum(mi, scores.max(axis=-1).transpose(0, 1, 2, 3))
        # scores: (b,cq,K,g,ck); m/l/o rows are (b,cq,K,g[,hd])
        p_ = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + p_.sum(axis=-1)
        o_new = oi * corr[..., None] + jnp.einsum(
            "bqkgc,bckh->bqkgh", p_.astype(vj.dtype), vj
        ).astype(jnp.float32)
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, axis=0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=0)
        return (o, m, l), None

    # remat each pair step: backward recomputes the (cq, ck) score tile
    # instead of saving a stacked (n_pairs, B, cq, ck) f32 score tensor per
    # layer — the dominant HBM-traffic term in train/prefill cells. Disabled
    # for hdim-TP archs (cfg.flash_remat=False): their scores carry an
    # all-reduce that recompute would re-run.
    step_fn = jax.checkpoint(step) if remat_step else step
    (o, m, l), _ = jax.lax.scan(step_fn, (o0, m0, l0), pairs)
    out = o / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def flash_applies(s: int, causal: bool) -> bool:
    """Whether self-attention over ``s`` positions runs in the flash kernel:
    causal, over two kernel blocks or more, and traced where a Mosaic kernel
    lowers. Mosaic kernels cannot be partitioned automatically, so every
    mesh axis has to be manual (the train step's ``shard_map``), or the
    program has to span one device."""
    if not causal or s % FLASH_BLOCK or s < 2 * FLASH_BLOCK:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    manual = [t == AxisType.Manual for t in mesh.axis_types]
    return all(manual) or (not any(manual) and mesh.size == 1)


def flash_attention(q, k, v):
    """Causal softmax attention in a Pallas flash kernel: q, k, v
    (B, H, S, hd) -> (B, H, S, hd), S a multiple of ``FLASH_BLOCK``.

    jax's splash attention, one kernel a batch row: the score tile of each
    (q-block, kv-block) pair lives in VMEM, blocks above the diagonal are
    skipped, and the backward pass (one fused dq/dkv kernel) recomputes the
    scores. float32 scores, max, sum and accumulators, as on the chunked
    path; the forward's PV matmul takes float32 probabilities, and dq is
    summed over key blocks from bf16 partials, as the chunked path's scan
    sums it over chunk pairs."""
    h, s, hd = q.shape[1:]
    q = q * jnp.asarray(1.0 / math.sqrt(hd), q.dtype)
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * h)
    kernel = splash.make_splash_mha(mask, block_sizes=_FLASH_BLOCKS, head_shards=1,
                                    q_seq_shards=1, interpret=ops.interpret())
    with trace.scope("attn.kernel"):
        return jax.vmap(kernel)(q, k, v)


def _repeat_kv(k, v, cfg, axis: int = 2):
    """Materialize GQA KV to the full head count for train/prefill einsums.

    Keeps SPMD sharding propagation trivial (q and k/v share the same H axis
    layout) at the cost of a transient g-times larger KV activation — the
    standard Megatron-style duplication; decode keeps the grouped form.
    ``axis`` is the heads axis: 1 in the flash kernel's layout."""
    g = cfg.num_heads // cfg.num_kv_heads
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=axis), jnp.repeat(v, g, axis=axis)


def _self_attention(p, x, cfg, positions, causal: bool = True, rope: bool = True):
    """x (B, S, d) attending over itself: the output (B, S, d), and k, v
    (B, S, K, hd) as projected, before the GQA repeat."""
    if flash_applies(x.shape[1], causal):
        q, k, v = _qkv(p, x, cfg, positions, rope=rope, heads_first=True)
        out = flash_attention(q, *_repeat_kv(k, v, cfg, axis=1))
        return (jnp.einsum("bhsk,hkd->bsd", out, p["wo"]),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    q, k, v = _qkv(p, x, cfg, positions, rope=rope)
    out = chunked_attention(
        q, *_repeat_kv(k, v, cfg), causal=causal, q_chunk=cfg.attn_q_chunk,
        num_kv_heads=cfg.num_heads, remat_step=cfg.flash_remat,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k, v


def attention_train(p, x, cfg, positions, causal: bool = True, rope: bool = True):
    return _self_attention(p, x, cfg, positions, causal, rope)[0]


class KVCache(NamedTuple):
    k: jax.Array  # (B, Smax, K, hd)
    v: jax.Array


def init_kv_cache(batch, max_len, cfg, dtype):
    shape = (batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def attention_prefill(p, x, cfg, positions, cache: KVCache):
    """Run full-sequence attention and write k/v into the cache at [0, S)."""
    out, k, v = _self_attention(p, x, cfg, positions)
    cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), 0, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), 0, axis=1),
    )
    return out, cache


def attention_decode(p, x, cfg, cache: KVCache, pos):
    """x: (B, 1, d); pos: scalar int32 — index of the new token. Attends over
    cache[0..pos]. Returns (out (B,1,d), updated cache)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads
    g = cfg.num_heads // kvh
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), pos, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), pos, axis=1),
    )
    qf = q.reshape(b, kvh, g, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qf, cache.k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    valid = jnp.arange(cache.k.shape[1]) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(cache.v.dtype), cache.v)
    out = out.reshape(b, 1, cfg.num_heads, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


def attention_decode_paged(p, x, cfg, k_pool, v_pool, page_table, lens):
    """Paged-cache decode step for ONE layer.

    x: (B, 1, d); k_pool/v_pool: (NP, page, K, hd) — this layer's slice of the
    global page pool (page id 0 is reserved scratch); page_table: (B, MP)
    int32 page ids per slot; lens: (B,) int32 tokens already cached per slot
    (the position the new token is written at).

    Per-slot generalization of :func:`attention_decode`: slot j writes its
    new K/V at logical position ``lens[j]`` — physically page
    ``page_table[j, lens[j] // page]`` offset ``lens[j] % page`` — then
    attends over the gathered ``(MP * page,)`` view of its own pages, masked
    at ``<= lens[j]``. With ``MP * page == max_len`` this is bit-identical to
    the dense-cache decode: gathered allocated positions hold the same
    values a dense cache would, and masked lanes exp-underflow to exactly
    0.0 regardless of the (stale/foreign) garbage they hold. Distinct live
    slots own disjoint pages (allocator invariant), so the scatter below has
    no cross-slot index collisions; idle slots all target scratch page 0,
    which no live slot ever reads.

    Returns (out (B, 1, d), new k_pool, new v_pool).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads
    g = cfg.num_heads // kvh
    page = k_pool.shape[1]
    positions = lens[:, None].astype(jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    pidx = jnp.take_along_axis(page_table, (lens // page)[:, None], axis=1)[:, 0]
    off = lens % page
    k_pool = k_pool.at[pidx, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[pidx, off].set(v[:, 0].astype(v_pool.dtype))
    kg = k_pool[page_table].reshape(b, -1, kvh, hd)  # (B, MP*page, K, hd)
    vg = v_pool[page_table].reshape(b, -1, kvh, hd)
    qf = q.reshape(b, kvh, g, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qf, kg).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    valid = jnp.arange(kg.shape[1])[None, :] <= lens[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(vg.dtype), vg)
    out = out.reshape(b, 1, cfg.num_heads, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_pool, v_pool


# --- cross attention (whisper decoder) ---


def init_cross_attention(key, cfg, rec, path):
    return init_attention(key, cfg, rec, path)


def cross_attention(p, x, enc_kv, cfg):
    """x: (B,S,d) decoder states; enc_kv: (k,v) each (B,F,K,hd) precomputed."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = _repeat_kv(enc_kv[0], enc_kv[1], cfg)
    out = chunked_attention(
        q, k, v, causal=False, q_chunk=cfg.attn_q_chunk, num_kv_heads=cfg.num_heads,
        remat_step=cfg.flash_remat,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_cross_kv(p, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return (k, v)
