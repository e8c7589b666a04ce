"""A whole run of the harness on the CPU at a small size, past its look
for a chip: sound, it comes out correct; with the timed path broken
underneath, or with the lower-precision control in the program's place,
``correct`` comes out false.

The limit here is this size's own, set like a cell's from readings of
this file's configuration (bf16 program on the CPU, 4 layers, d 256,
vocabulary 2048, seeds 1-5, ``tiny-b1`` traffic, 3 s windows): the
program's widest gap read 0.131-0.186 and the control computed in int8
0.636-1.084; 0.42 lies between, nearer the control.
The faults are those a batch-1 cell can have on one chip: a decode step
that leaves the KV state as it was, and a token altered where it is
produced.  One slot leaves no half of a batch to drop, and no cell runs
on several chips, so no exchange between chips can be left out.
"""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness

import repro.serving.paging as paging
from repro.configs.base import ModelConfig

ROOT = Path(__file__).resolve().parent
LIMIT = 0.42
SEED = 3
CFG = {"hidden_size": 256, "intermediate_size": 512,
       "num_attention_heads": 4, "num_hidden_layers": 4,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
       "rope_theta": 1_000_000.0, "tie_word_embeddings": True,
       "vocab_size": 2048, "model_type": "qwen2"}
MCFG = ModelConfig(name="small", family="dense", num_layers=4, d_model=256,
                   num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=2048,
                   head_dim=64, qkv_bias=True, rope_theta=1_000_000.0,
                   tie_embeddings=True, dtype="bfloat16")


TRAFFIC = json.loads((ROOT / "testdata" / "tiny-b1.json").read_text())
SPEC = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
ITL = [m for m in SPEC["end_to_end"] if m["name"] == "itl_p95_ms"]


def run():
    return harness.run(cfg=CFG, traffic=TRAFFIC, mcfg=MCFG, limit=LIMIT,
                       seed=SEED, seconds=3.0, trace=False, metrics=ITL,
                       t_process=time.perf_counter(), require_tpu=False)


def test_sound_run_is_correct_and_the_control_is_not():
    s = harness.serve(cfg=CFG, traffic=TRAFFIC, mcfg=MCFG, seed=SEED,
                      seconds=3.0, trace=False, metrics=ITL,
                      t_process=time.perf_counter(), require_tpu=False)
    assert s.line["attempted"] > 0 and s.line["failed"] == 0
    assert "itl_p95_ms" in s.line["metrics"]
    kw = dict(cfg=CFG, traffic=TRAFFIC, seed=SEED, dtype="bfloat16",
              limit=LIMIT)
    sound = harness.judge(s, **kw)
    assert sound["correct"], sound["checks"]
    control = harness.judge(s, quant="int8", **kw)
    assert not control["correct"]
    assert control["checks"]["logit_gap"]["value"] > LIMIT


def test_result_line_puts_the_checks_last():
    out = run()
    assert out["correct"] and list(out)[0] == "correct"
    assert list(out)[-1] == "checks"
    assert out["checks"]["unfinished"] == {"value": 0, "limit": 0}


def _state_unchanged(orig):
    def step(params, cfg, ak, av, table, pos, tokens):
        _, _, logits, nxt = orig(params, cfg, ak, av, table, pos, tokens)
        return ak, av, logits, nxt
    return step


def _token_altered(orig):
    def step(params, cfg, ak, av, table, pos, tokens):
        ak, av, logits, nxt = orig(params, cfg, ak, av, table, pos, tokens)
        return ak, av, logits, (nxt + 1) % jnp.int32(logits.shape[-1])
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_decode_step_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(paging, "decode_step_paged",
                        fault(paging.decode_step_paged))
    out = run()
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT
