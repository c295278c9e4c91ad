"""The flash-attention kernel path of ``models/attention`` (Pallas interpret
mode on the CPU): its output and q/k/v gradients against the chunked jnp
path and a float32 reference, the two layouts of ``attention_train``, and
the dispatch rule, read off the lowered program."""
import contextlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs import get_smoke_config
from repro.models import attention

FB = attention.FLASH_BLOCK
B, H, S = 2, 2, 2 * FB
KERNEL_CALL = "call @_splash_attention"  # the kernel's jitted entry in the lowered program


def _reference(q, k, v):
    """Causal softmax attention in float32 at the highest matmul precision;
    q, k, v (B, H, S, hd)."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / math.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision="highest")


def _chunked(q, k, v):
    """Today's jnp path on the kernel's layout: the multi-pair causal scan."""
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return t(attention.chunked_attention(t(q), t(k), t(v), causal=True, q_chunk=512,
                                         num_kv_heads=q.shape[1]))


def _gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("hd", [80, 64])
def test_kernel_matches_chunked_path_and_f32_reference(hd):
    """Output and q/k/v gradients over two kernel blocks in bf16. The tolerances come
    from the chunked path's own gap to the float32 reference: the kernel
    keeps float32 scores, max and sum, so it is no further from the
    reference than that, and from the chunked path no further than half as
    much again."""
    ks = jax.random.split(jax.random.PRNGKey(hd), 4)
    q, k, v = (jax.random.normal(key, (B, H, S, hd)).astype(jnp.bfloat16) for key in ks[:3])
    cotangent = jax.random.normal(ks[3], (B, H, S, hd)).astype(jnp.bfloat16)
    got = {}
    for name, fn in (("kernel", attention.flash_attention), ("chunked", _chunked),
                     ("reference", _reference)):
        out, vjp = jax.jit(lambda q, k, v, fn=fn: jax.vjp(fn, q, k, v))(q, k, v)
        got[name] = (out, *vjp(cotangent.astype(out.dtype)))
    for i, what in enumerate(("out", "dq", "dk", "dv")):
        own = _gap(got["chunked"][i], got["reference"][i])
        assert 0 < own < 1e-2, (what, own)
        assert _gap(got["kernel"][i], got["reference"][i]) <= own, what
        assert _gap(got["kernel"][i], got["chunked"][i]) <= 1.5 * own, what


def _layer(cfg):
    from repro.models import layers

    rec = layers.AxesRecorder()
    p = attention.init_attention(jax.random.PRNGKey(1), cfg, rec, "attn")
    if "bq" in p:  # biases drawn, not zero, so that the kernel's layout adds them right
        p.update({n: 0.1 * jax.random.normal(jax.random.PRNGKey(i), p[n].shape, p[n].dtype)
                  for i, n in enumerate(("bq", "bk", "bv"))})
    return p


def _lowered(cfg, s, causal=True, mesh=None):
    """Whether the kernel applies to ``attention_train`` over ``s`` positions,
    and the lowered program's text, both traced under ``mesh`` where one is
    given."""
    p = _layer(cfg)
    x = jax.ShapeDtypeStruct((B, s, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (B, s))
    fn = jax.jit(lambda p, x: attention.attention_train(p, x, cfg, pos, causal=causal))
    with jax.sharding.use_abstract_mesh(mesh) if mesh else contextlib.nullcontext():
        return attention.flash_applies(s, causal), fn.trace(p, x).lower().as_text()


@pytest.mark.parametrize("s,causal,mesh,calls", [
    (2 * FB, True, None, 1),                                             # two blocks: the kernel
    (2 * FB, True, compat.abstract_mesh((1, 1), ("data", "model")), 1),  # every axis of size 1
    (FB, True, None, 0),                                                 # one block
    (2 * FB - 48, True, None, 0),                                        # not a multiple of the block
    (2 * FB, False, None, 0),                                            # not causal
    (2 * FB, True, compat.abstract_mesh((1, 2), ("data", "model")), 0),  # an auto 'model' axis of 2
])
def test_dispatch(s, causal, mesh, calls):
    """The kernel runs for causal self-attention over two blocks or more,
    traced where no mesh axis is left to automatic sharding; every other
    case lowers the chunked path, with no kernel call."""
    applies, text = _lowered(get_smoke_config("stablelm-3b"), s, causal, mesh)
    assert applies == bool(calls)
    assert text.count(KERNEL_CALL) == calls


def _in_shard_map(fn, manual, *args):
    """``fn(*args)`` in a ``shard_map`` over a one-device (data, model) mesh
    whose ``manual`` axes are manual and the rest automatic."""
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                                    axis_names=manual))(*args)


def test_dispatch_inside_a_shard_map():
    """Inside ``shard_map`` the kernel runs where every axis is manual (the
    train step's own region), and not where an axis is left automatic: a
    Mosaic kernel lowers in neither a partly automatic region nor on more
    than one device without a manual region."""
    seen = {}
    for manual in ({"data", "model"}, {"data"}):
        def probe(x, manual=frozenset(manual)):
            seen[manual] = attention.flash_applies(S, True)
            return x
        _in_shard_map(probe, manual, jnp.zeros(2))
    assert seen == {frozenset({"data", "model"}): True, frozenset({"data"}): False}


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "stablelm-3b"])
def test_attention_train_layouts_agree(arch):
    """``attention_train`` on the kernel path (q, k, v projected straight to
    (B, H, S, hd), biases and rotary in that layout) equals the chunked path
    on (B, S, H, hd), which a region with an automatic axis takes."""
    cfg = get_smoke_config(arch)
    p = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    layer = lambda p, x: attention.attention_train(p, x, cfg, pos)  # noqa: E731
    kernel = _in_shard_map(layer, {"data", "model"}, p, x)
    chunked = _in_shard_map(layer, {"data"}, p, x)
    assert KERNEL_CALL in jax.jit(layer).lower(p, x).as_text()
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(chunked), rtol=2e-5, atol=2e-5)


def test_attention_prefill_on_either_path():
    """``attention_prefill`` on the kernel path writes the cache in its own
    (B, S, K, hd) layout and returns what the chunked path returns."""
    cfg = get_smoke_config("qwen1.5-0.5b")
    p = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = attention.init_kv_cache(B, S + 16, cfg, jnp.float32)
    prefill = lambda p, x: attention.attention_prefill(p, x, cfg, pos, cache)  # noqa: E731
    kernel = _in_shard_map(prefill, {"data", "model"}, p, x)
    chunked = _in_shard_map(prefill, {"data"}, p, x)
    assert KERNEL_CALL in jax.jit(prefill).lower(p, x).as_text()
    for a, b in zip(jax.tree.leaves(kernel), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
