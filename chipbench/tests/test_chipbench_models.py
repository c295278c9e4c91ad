"""The dense model module (``chipbench/models/dense.py``) gives, bit for bit,
the numbers the benchmark's dense cells read: at its ``SMALL`` sizes, the
weights drawn for two seeds, the reference's first three losses and leaf
norms, and the FLOP counts equal recorded values (``data/dense_golden.json``).
The program's ModelConfig is the registry's with the file's sizes."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from chipbench import flops, models, program, reference, weights
from chipbench.corpus import Corpus

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "tests", "data", "dense_golden.json")) as f:
    GOLDEN = json.load(f)
CONFIGS = ("stablelm-3b", "qwen1.5-0.5b")
SEEDS = (7, 2**35 + 17)


def _small(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return {**cfg, **models.of(cfg).SMALL}


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_recorded_ones(name):
    cfg = _small(name)
    for seed in SEEDS:
        h = hashlib.sha256()
        for path, x in sorted(weights.flatten(weights.maker(cfg)(seed)).items()):
            h.update(path.encode())
            h.update(np.asarray(x).tobytes())
        assert h.hexdigest()[:16] == GOLDEN[name][f"weights_{seed}"], seed


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_steps_are_the_recorded_ones(name):
    cfg = _small(name)
    corpus = Corpus(cfg["vocab_size"], 11, 1.3, 8)
    ref = reference.Trainer(cfg, 11, jax.devices()[:1], rows_per_block=2)
    losses = [ref.step(corpus.batch(step, 0, 2, 64)) for step in range(3)]
    want = GOLDEN[name]
    assert losses == want["losses"]
    assert ref.first_grad_norms() == want["grad_norms"]
    assert ref.change_norms() == want["change_norms"]


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_the_recorded_ones(name):
    cfg = _small(name)
    assert flops.matmul_params(cfg) == GOLDEN[name]["matmul_params"]
    assert flops.model_flops_per_token(cfg, 64) == GOLDEN[name]["flops_per_token"]


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_takes_the_files_sizes(name):
    cfg = _small(name)
    mcfg = program.model_config(cfg)
    assert (mcfg.d_model, mcfg.num_heads, mcfg.num_kv_heads, mcfg.d_ff, mcfg.vocab_size,
            mcfg.num_layers) == (64, 4, 4, 128, 512, 2)
    assert mcfg.qkv_bias == (name == "qwen1.5-0.5b")
    assert mcfg.tie_embeddings == cfg["tie_word_embeddings"]


def test_a_configuration_without_a_model_is_refused():
    cfg = _small("qwen1.5-0.5b")
    del cfg["model"]
    with pytest.raises(KeyError, match="names no model"):
        weights.leaf_specs(cfg)
