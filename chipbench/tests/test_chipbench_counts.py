"""The FLOP and byte counters against counts made by hand."""
import json
import os

import pytest

from chipbench import flops, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


# stablelm-3b at 8 layers: per layer 2560 * 80 * (32 + 32 + 32 + 32) attention
# + 3 * 2560 * 6912 MLP = 79,298,560; x 8 + the untied 2560 x 50304 head.
# qwen1.5-0.5b: per layer 1024 * 64 * 64 + 3 * 1024 * 2816 = 12,845,056; x 24
# + the tied 151936 x 1024 embedding counted once as the head.
@pytest.mark.parametrize("name, params, per_token, elements", [
    ("stablelm-3b", 8 * 79_298_560 + 128_778_240,
     6 * 763_166_720 + 6 * 8 * 4096 * 2560, 763_166_720 + 128_778_240 + 8 * 2 * 2560 + 2560),
    ("qwen1.5-0.5b", 24 * 12_845_056 + 155_582_464,
     6 * 463_863_808 + 6 * 24 * 512 * 1024, 463_987_712),
])
def test_counts_by_hand(name, params, per_token, elements):
    cfg = _cfg(name)
    seq = {"stablelm-3b": 4096, "qwen1.5-0.5b": 512}[name]
    assert flops.matmul_params(cfg) == params
    assert flops.model_flops_per_token(cfg, seq) == per_token
    assert sum(weights.element_counts(cfg).values()) == elements
    # bf16 gradient in, int32 plane out, one int32 exponent a block of 256;
    # the decode moves the same bytes the other way
    assert flops.fpisa_bytes_per_step(cfg) == 2 * (elements * (2 + 4) + 4 * elements / 256)
    assert flops.fpisa_bytes_per_step(cfg, wire_bits=16) == 2 * (elements * (2 + 2) + 4 * elements / 256)
