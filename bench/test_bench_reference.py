"""The plain float32 reference agrees with the program's own full-sequence
forward pass (``transformer.forward``) in float32 at the highest matmul
precision, on the benchmark's seeded weights at a tiny size; and its gap
arithmetic reads 0 for the reference's own choices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.families import family
from bench.reference import qwen2 as reference

from repro.configs.base import ModelConfig
from repro.models import build_model, transformer

CFG = {"hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_hidden_layers": 3,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
       "rope_theta": 1_000_000.0, "tie_word_embeddings": True,
       "vocab_size": 512, "model_type": "qwen2"}


def program_cfg(dtype):
    return ModelConfig(name="tiny", family="dense", num_layers=3,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=512, head_dim=16, qkv_bias=True,
                       rope_theta=1_000_000.0, tie_embeddings=True,
                       dtype=dtype)


@pytest.fixture(scope="module")
def setup():
    mcfg = program_cfg("float32")
    harness.check_program_config(mcfg, CFG)
    w = weights.make_weights(CFG, 2**31 + 5, "float32")
    model = build_model(mcfg)
    params = harness.program_params(CFG, w, model, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    return mcfg, w, params, toks


def test_reference_matches_transformer_forward_float32(setup):
    mcfg, w, params, toks = setup
    with jax.default_matmul_precision("highest"):
        want = transformer.forward(params, mcfg, jnp.asarray(toks)[None])[0]
    got = reference.logits(w, CFG, toks)
    want = np.asarray(want[0], np.float64)
    got = np.asarray(got, np.float64)
    assert np.abs(want).max() > 1.0          # logits of a real spread
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(
        want).max())


def test_gaps_are_zero_for_the_references_own_tokens(setup):
    _, w, _, toks = setup
    prompt, seq = toks[:10], list(toks[:10])
    for _ in range(8):                             # greedy decode
        seq.append(int(np.asarray(reference.logits(w, CFG, seq))[-1]
                       .argmax()))
    served = np.asarray(seq[10:], np.int32)
    g = reference.token_gaps(w, CFG, prompt, served)
    assert g.shape == (len(served),)
    assert np.abs(g).max() < 1e-4
    other = (served + 1) % 512                     # never the best
    assert reference.token_gaps(w, CFG, prompt, other).min() > 0


def test_control_precision_departs_from_the_reference(setup):
    _, w, _, toks = setup
    full = np.asarray(reference.logits(w, CFG, toks))
    low = np.asarray(reference.logits(w, CFG, toks, "int8"))
    assert 0 < np.abs(low - full).max() < 0.5 * np.abs(full).max()
    with pytest.raises(ValueError):
        reference.logits(w, CFG, toks, "fp4")


def test_weights_depend_on_the_seed_only():
    a = weights.make_weights(CFG, 7, "bfloat16")
    b = weights.make_weights(CFG, 7, "bfloat16")
    c = weights.make_weights(CFG, 8, "bfloat16")
    assert all((np.asarray(a[k], np.float32) == np.asarray(b[k], np.float32))
               .all() for k in a)
    assert not (np.asarray(a["wq"], np.float32)
                == np.asarray(c["wq"], np.float32)).all()
    assert a["embed"].dtype == jnp.bfloat16
    assert family(CFG).param_count(CFG) == sum(int(np.prod(v.shape))
                                               for v in a.values())
