"""Cross-cutting integration tests added with the §Perf work: the Pallas
fused-op backend, the activation-sharding policy, and the analysis
report/reanalysis pipeline."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import opgraph
from repro.core.graphs import LEVELS, build_decode_graph
from repro.core.opgraph import run_graph_pure
from repro.models import build_model


def _decode_inputs(cfg, model, b=2, max_len=16):
    cache = model.init_cache(b, max_len)
    inp = {"tokens": jnp.ones((b, 1), jnp.int32), "pos": jnp.int32(0)}
    for i in range(cfg.num_layers):
        inp[f"k_cache_{i}"] = cache["k"][i]
        inp[f"v_cache_{i}"] = cache["v"][i]
    return inp


def test_pallas_fused_backend_matches_xla():
    """Engine fused ops can run on the hand-written TPU kernels
    (interpret mode on CPU) with identical numerics — the production TPU
    integration path."""
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    inp = _decode_inputs(cfg, model)
    g = build_decode_graph(params, cfg, batch=2, max_len=16,
                           fusion=LEVELS["F3"])
    ref = run_graph_pure(g, dict(inp))
    opgraph.set_fused_backend("pallas")
    try:
        out = run_graph_pure(g, dict(inp))
    finally:
        opgraph.set_fused_backend("xla")
    np.testing.assert_allclose(np.asarray(out["logits"]),
                               np.asarray(ref["logits"]), atol=1e-3)


def test_fused_backend_switch_retraces(monkeypatch):
    """A per-op executable traced under "xla" must not be reused after a
    switch to "pallas": jit shares traces across wrappers of one function,
    so the switch has to drop them."""
    from repro.kernels.fused_kv_proj import ops as kv_ops
    from repro.serving import create_backend
    calls = []
    real = kv_ops.kv_proj
    monkeypatch.setattr(kv_ops, "kv_proj",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    cfg = get_smoke_config("qwen2-1.5b", layers=1)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = np.array([[3, 5, 7, 9]], np.int32)
    try:
        for fused in ("xla", "pallas"):
            opgraph.set_fused_backend(fused)
            create_backend("F3", model, params, batch=1,
                           max_len=16).prefill(prompt)
            assert bool(calls) == (fused == "pallas")
    finally:
        opgraph.set_fused_backend("xla")


@pytest.mark.parametrize("mode", ["F3", "FULL"])
def test_graph_backend_serves_bf16(mode):
    """The op-graph backends run a bf16 model (the published dtype) and
    agree with the jitted model backend within bf16 rounding."""
    from repro.serving import create_backend
    cfg = get_smoke_config("qwen2-1.5b", layers=2).replace(dtype="bfloat16")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = np.array([[3, 5, 7, 9, 11]], np.int32)
    logits = {}
    for m in (mode, "model"):
        backend = create_backend(m, model, params, batch=1, max_len=16)
        state, out = backend.prefill(prompt)
        _, out = backend.decode_step(state, out.next_token)
        logits[m] = np.asarray(out.logits, np.float32).reshape(-1)
    ref = logits["model"]
    rel = np.linalg.norm(logits[mode] - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel


def test_activation_policy_is_noop_without_mesh():
    """constrain_hidden under a policy but outside a mesh must not alter
    values (smoke-test safety)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.activation import activation_policy, constrain_hidden
    x = jnp.ones((2, 4, 8))
    with activation_policy(P(None, None, None)):
        y = constrain_hidden(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # policy cleared on exit
    from repro.sharding import activation as A
    assert A._POLICY is None


def test_forward_unchanged_under_policy():
    from jax.sharding import PartitionSpec as P

    from repro.sharding.activation import activation_policy
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    ref, _ = model.forward(params, batch)
    with activation_policy(P(None, None, None)):
        out, _ = model.forward(params, batch)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=0)


def test_report_renders_dryrun_records(tmp_path):
    from repro.analysis.report import dryrun_table, load, roofline_table
    rec = {
        "status": "ok", "lower_s": 0.1, "compile_s": 1.0,
        "compute_s": 0.5, "memory_s": 1.5, "collective_s": 0.2,
        "dominant": "memory", "step_bound_s": 1.5, "mfu_at_bound": 0.25,
        "useful_flops_ratio": 0.8,
        "memory": {"argument_size_in_bytes": 2.0**30,
                   "temp_size_in_bytes": 2.0**31},
        "collective_counts": {"all-reduce": 3},
    }
    p = tmp_path / "archx__train_4k__single.json"
    p.write_text(json.dumps(rec))
    rows = load(str(tmp_path))
    assert rows[0]["arch"] == "archx"
    t1 = dryrun_table(rows)
    assert "archx__train_4k__single" in t1 and "all-reduce×3" in t1
    t2 = roofline_table(rows, "single")
    assert "**memory**" in t2 and "0.250" in t2


def test_moe_chunking_consistent_across_token_counts():
    """Chunked dispatch (nc>1) must agree with single-chunk routing on the
    same tokens (same per-token expert choices at ample capacity)."""
    from repro.models import moe as M
    cfg = get_smoke_config("granite-moe-1b-a400m", layers=1)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ffn = jax.tree.map(lambda a: a[0], params["blocks"])["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y1, _ = M.moe_ffn(ffn, cfg, x)
    # force 2 chunks by halving CHUNK_TOKENS
    old = M.CHUNK_TOKENS
    try:
        M.CHUNK_TOKENS = 16
        y2, _ = M.moe_ffn(ffn, cfg, x)
    finally:
        M.CHUNK_TOKENS = old
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)


def test_dryrun_best_records_exist():
    """The per-cell best-config selection is part of the §Perf deliverable."""
    import glob
    import os
    if not os.path.isdir("results/dryrun_best"):
        pytest.skip("dry-run results not present in this checkout")
    files = glob.glob("results/dryrun_best/*__single.json")
    assert len(files) >= 30
    ok = [json.load(open(f)) for f in files]
    assert all(r["status"] in ("ok", "skipped") for r in ok)
