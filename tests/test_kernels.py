"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracles,
swept over shapes and dtypes, asserting bit-exact agreement."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _data(r, b, scale_exp=True):
    x = RNG.standard_normal((r, b)).astype(np.float32)
    if scale_exp:
        x = x * np.exp2(RNG.integers(-12, 12, (r, b))).astype(np.float32)
    return x


SHAPES = [(8, 128), (256, 256), (300, 256), (1024, 128), (64, 512), (1, 256)]


@pytest.mark.parametrize("shape", SHAPES)
def test_extract_kernel_matches_ref(shape):
    x = _data(*shape)
    e_k, m_k, b_k = ops.extract(x)
    e_r, m_r, b_r = ref.extract_ref(jnp.asarray(x))
    assert np.array_equal(e_k, e_r)
    assert np.array_equal(m_k, m_r)
    assert np.array_equal(b_k, b_r)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("preshift", [0, 2])
def test_align_kernel_matches_ref(shape, preshift):
    x = _data(*shape)
    e, m, b = ref.extract_ref(jnp.asarray(x))
    a_k = ops.align(e, m, b, preshift=preshift)
    a_r = ref.align_ref(e, m, b, preshift)
    assert np.array_equal(a_k, a_r)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("preshift", [0, 2])
def test_decode_kernel_matches_ref(shape, preshift):
    x = _data(*shape)
    e, m, b = ref.extract_ref(jnp.asarray(x))
    a = ref.align_ref(e, m, b, preshift)
    d_k = ops.decode(a, b, preshift=preshift)
    d_r = ref.decode_ref(a, b, preshift)
    assert np.array_equal(np.asarray(d_k).view(np.int32), np.asarray(d_r).view(np.int32))


@pytest.mark.parametrize("w", [2, 8, 17])
@pytest.mark.parametrize("variant", ["fpisa_a", "full"])
def test_accum_kernel_matches_ref(w, variant):
    x = (RNG.standard_normal((w, 64, 256)) * 0.01).astype(np.float32)
    a_k = ops.accum(x, variant=variant)
    a_r = ref.accum_ref(jnp.asarray(x), variant=variant)
    assert np.array_equal(np.asarray(a_k).view(np.int32), np.asarray(a_r).view(np.int32))


def test_extract_fp16_format():
    x = _data(128, 256, scale_exp=False)
    e_k, m_k, b_k = ops.extract(x.astype(np.float16), fmt_name="fp16")
    e_r, m_r, b_r = ref.extract_ref(jnp.asarray(x, jnp.float16), __import__("repro.core.fpisa", fromlist=["FP16"]).FP16)
    assert np.array_equal(e_k, e_r)
    assert np.array_equal(m_k, m_r)


def test_kernel_pipeline_equals_core_block_path():
    """extract -> align -> decode chained == fpisa.block_encode/decode."""
    from repro.core import fpisa as F

    x = _data(64, 256)
    e, m, b = ops.extract(x)
    a = ops.align(e, m, b, preshift=1)
    out = ops.decode(a, b, preshift=1)

    flat = jnp.asarray(x)
    p = F.encode(flat)
    be = F.block_max_exponent(p.exp, 256)
    man = F.block_encode(flat, be, 256, 1)
    expect = F.block_decode(man, be, 256, 1)
    assert np.array_equal(np.asarray(out).view(np.int32), np.asarray(expect).view(np.int32))


@pytest.mark.parametrize("fmt_name", ["fp16", "bf16"])
def test_accum_kernel_16bit_formats_match_ref(fmt_name):
    from repro.core import fpisa

    fmt = fpisa.FORMATS[fmt_name]
    x = jnp.asarray(RNG.standard_normal((4, 64, 256)) * 0.01, fpisa.PACKED_DTYPE[fmt_name])
    a_k = ops.accum(x, fmt_name=fmt_name)
    a_r = ref.accum_ref(x, fmt=fmt).astype(jnp.float32)
    assert a_k.dtype == jnp.float32
    assert np.array_equal(np.asarray(a_k).view(np.int32), np.asarray(a_r).view(np.int32))
