"""Model families are found by ``model_type``: Qwen2's weights, shapes and
costs are pinned to the values they had before the family was split out
of the shared modules, and a family that is not in ``bench/`` is served
and judged through the same harness once its two modules are importable."""
import dataclasses
import hashlib
import json
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs, harness, weights
from bench.families import family, qwen2
from bench.reference import qwen2 as qwen2_reference
from bench.test_bench_faults import CFG, ITL, LIMIT, MCFG, SEED, TRAFFIC
from bench.test_bench_reference import CFG as TINY

ROOT = Path(__file__).resolve().parent

# sha256 over (name, bytes) of every leaf in name order, make_weights(TINY,
# seed 7), read before the family moved out of bench/weights.py
DIGESTS = {
    "float32": "c6e293ed9801fa43fae2225374b02d71f6f8d885ff4a2f4ee38de7bec1929d69",
    "bfloat16": "f499167f7c71f91c7c34dd2b4a732e42db2c3f072ecb942fbfbf59b332ddcf1e",
}


def _layout(L, d, q, kv, F, V=151_936):
    return {"embed": (V, d), "attn_norm": (L, d), "wq": (L, d, q),
            "bq": (L, q), "wk": (L, d, kv), "bk": (L, kv), "wv": (L, d, kv),
            "bv": (L, kv), "wo": (L, q, d), "mlp_norm": (L, d),
            "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
            "final_norm": (d,)}


# read before the move: shapes, param_count, decode_step at contexts [1],
# [256], [256, 100], [2048], prefill_chunk at (pos0, valid) (0, 1),
# (0, 512), (512, 256), (1536, 512)
PARENT = {
    "qwen2.5-0.5b": {
        "shapes": _layout(24, 896, 896, 128, 4864),
        "param_count": 494_032_768,
        "decode": [(988008448, 988077824), (1009942528, 991211264),
                   (2006466560, 992440064), (1164083200, 1013231360)],
        "prefill": [(988008448, 988077824), (377982976000, 994356992),
                    (197583339520, 997502720), (445628710912, 1013231360)],
    },
    "qwen2.5-1.5b": {
        "shapes": _layout(28, 1536, 1536, 256, 8960),
        "param_count": 1_543_714_304,
        "decode": [(3087310848, 3087457280), (3131179008, 3094768640),
                   (6235521024, 3097635840), (3439460352, 3146148864)],
        "prefill": [(3087310848, 3087457280), (1364699774976, 3102108672),
                    (699494694912, 3109448704),
                    (1499991244800, 3146148864)],
    },
}
CONTEXTS = ([1], [256], [256, 100], [2048])
CHUNKS = ((0, 1), (0, 512), (512, 256), (1536, 512))


def _config(name):
    return json.loads((ROOT / "configs" / f"{name}.json").read_text())[
        "config"]


@pytest.mark.parametrize("dtype", sorted(DIGESTS))
def test_qwen2_weights_are_bit_identical_to_before(dtype):
    w = weights.make_weights(TINY, 7, dtype)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == DIGESTS[dtype]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_qwen2_shapes_and_costs_are_as_before(name):
    cfg, want = _config(name), PARENT[name]
    assert family(cfg) is qwen2
    assert qwen2.shapes(cfg) == want["shapes"]
    assert qwen2.param_count(cfg) == want["param_count"]
    assert [costs.decode_step(cfg, c) for c in CONTEXTS] == want["decode"]
    assert [costs.prefill_chunk(cfg, p, v) for p, v in CHUNKS] \
        == want["prefill"]


# -- a family that bench/ does not have: Qwen2's layout without q/k/v biases

NOBIAS = "gqa_nobias"
BIASES = ("bq", "bk", "bv")


def _nobias_family():
    m = types.ModuleType(f"bench.families.{NOBIAS}")
    m.dims, m.draw = qwen2.dims, qwen2.draw

    def shapes(cfg):
        return {k: v for k, v in qwen2.shapes(cfg).items()
                if k not in BIASES}

    def program_params(w):
        p = qwen2.program_params({**w, **dict.fromkeys(BIASES)})
        for b in BIASES:
            del p["blocks"]["attn"][b]
        return p
    m.shapes, m.program_params = shapes, program_params
    m.param_count = lambda cfg: int(sum(np.prod(s)
                                        for s in shapes(cfg).values()))
    m.program_config = lambda cfg: {**qwen2.program_config(cfg),
                                    "qkv_bias": False}
    return m


def _nobias_reference():
    m = types.ModuleType(f"bench.reference.{NOBIAS}")

    def zero_biases(w, cfg):
        sh = qwen2.shapes(cfg)
        return {**w, **{b: jnp.zeros(sh[b], w["wq"].dtype) for b in BIASES}}
    m.logits = lambda w, cfg, tokens, quant=None: qwen2_reference.logits(
        zero_biases(w, cfg), cfg, tokens, quant)
    m.token_gaps = lambda w, cfg, prompt, served, quant=None: \
        qwen2_reference.token_gaps(zero_biases(w, cfg), cfg, prompt, served,
                                   quant)
    return m


def test_a_family_from_two_new_modules_is_served_and_judged(monkeypatch):
    monkeypatch.setitem(sys.modules, f"bench.families.{NOBIAS}",
                        _nobias_family())
    monkeypatch.setitem(sys.modules, f"bench.reference.{NOBIAS}",
                        _nobias_reference())
    cfg = {**CFG, "model_type": NOBIAS}
    mcfg = dataclasses.replace(MCFG, name="small-nobias", qkv_bias=False)
    with pytest.raises(SystemExit, match="qkv_bias"):
        harness.check_program_config(MCFG, cfg)
    assert "bq" not in weights.make_weights(cfg, SEED, "bfloat16")
    s = harness.serve(cfg=cfg, traffic=TRAFFIC, mcfg=mcfg, seed=SEED,
                      seconds=3.0, trace=False, metrics=ITL,
                      t_process=time.perf_counter(), require_tpu=False)
    assert s.line["attempted"] > 0 and s.line["failed"] == 0
    kw = dict(cfg=cfg, traffic=TRAFFIC, seed=SEED, dtype="bfloat16",
              limit=LIMIT)
    sound = harness.judge(s, **kw)
    assert sound["correct"], sound["checks"]
    assert not harness.judge(s, quant="int8", **kw)["correct"]


@pytest.mark.parametrize("model_type", ["no_such_family", None],
                         ids=["unknown", "missing"])
def test_an_unknown_model_type_names_both_files(model_type):
    cfg = {k: v for k, v in CFG.items() if k != "model_type"}
    if model_type is not None:
        cfg["model_type"] = model_type
    with pytest.raises(LookupError) as e:
        harness.serve(cfg=cfg, traffic=TRAFFIC, mcfg=MCFG, seed=SEED,
                      seconds=1.0, trace=False, metrics=ITL,
                      t_process=time.perf_counter(), require_tpu=False)
    t = model_type or "<model_type>"
    assert f"bench/families/{t}.py" in str(e.value)
    assert f"bench/reference/{t}.py" in str(e.value)


def test_drain_finds_every_live_array_of_any_cache_kind():
    class Pool:
        pass
    pool, state = Pool(), jnp.ones((3,))
    pool.arena, pool.host, pool.owner = jnp.zeros((2, 4)), np.zeros(4), pool
    gone = jnp.ones((5,))
    gone.delete()
    bstate = {"paged": pool, "meta": {0: {"prompt": np.arange(3)}},
              "slots": [state, (gone, None)], "jit": jax.jit(jnp.sin)}
    found = harness.device_arrays(bstate)
    assert sorted(a.shape for a in found) == [(2, 4), (3,)]
    harness.drain(bstate)
