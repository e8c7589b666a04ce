"""Multi-device distribution tests.

These run in SUBPROCESSES with ``--xla_force_host_platform_device_count=8``
because the main pytest process must keep seeing one device.  Each body
asserts inside the subprocess; failure propagates via exit code + stderr.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 480) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The production sharded step computes the same loss as 1-device."""
    _run("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.sharding import rules
        from repro.launch import steps as S
        from repro.train.trainer import init_state
        from repro.train.optimizer import adamw, AdamWConfig

        cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                               d_ff=128, vocab=256)
        model = build_model(cfg)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        fn = S.train_step_fn(model)
        rng = jax.random.PRNGKey(0)
        state = init_state(model, rng, adamw(AdamWConfig()))
        batch = {"tokens": jax.random.randint(rng, (4, 16), 0, 256, jnp.int32),
                 "labels": jax.random.randint(rng, (4, 16), 0, 256, jnp.int32)}
        # single-device reference
        _, m_ref = jax.jit(fn)(state, batch)
        with mesh:
            shapes = jax.eval_shape(lambda: state)
            st_sh = rules.state_shardings(shapes, mesh, fsdp=True)
            b_sh = rules.batch_shardings(batch, mesh)
            state_d = jax.device_put(state, st_sh)
            batch_d = jax.device_put(batch, b_sh)
            new_state, m = jax.jit(fn, in_shardings=(st_sh, b_sh),
                                   out_shardings=(st_sh, None))(state_d, batch_d)
        np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]),
                                   rtol=1e-4)
        print("OK sharded==single:", float(m["loss"]))
    """)


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    _run("""
        from repro.dist.pipeline import pipeline_apply
        mesh = jax.make_mesh((8,), ("stage",))
        n_stages, n_micro, b, d = 8, 16, 4, 32
        rng = jax.random.PRNGKey(0)
        w = jax.random.normal(rng, (n_stages, d, d)) / np.sqrt(d)
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, b, d))
        stage_fn = lambda wi, h: jnp.tanh(h @ wi)
        out = pipeline_apply(w, x, mesh=mesh, stage_fn=stage_fn)
        # sequential reference
        ref = x
        for i in range(n_stages):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        print("OK pipeline")
    """)


@pytest.mark.slow
def test_compressed_psum_error_bounded():
    _run("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import (compressed_psum_mean,
                                            uncompressed_psum_mean)
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        err0 = jnp.zeros((1, 64))

        def body(g, e):
            mean, e2 = compressed_psum_mean(g, e)
            exact = uncompressed_psum_mean(g)
            return mean, exact, e2

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(("pod", "data")), P()),
                           out_specs=(P(("pod", "data")), P(("pod", "data")),
                                      P()),
                           check_vma=False)
        mean, exact, e2 = fn(g, err0)
        rel = float(jnp.max(jnp.abs(mean - exact)) / jnp.max(jnp.abs(exact)))
        assert rel < 0.05, f"int8 hop error too large: {rel}"
        # error feedback state is the quantization residual, bounded by scale
        assert float(jnp.max(jnp.abs(e2))) < float(jnp.max(jnp.abs(g)))
        print("OK compression, rel err", rel)
    """)


@pytest.mark.slow
def test_dryrun_cell_on_test_mesh():
    """End-to-end dry-run path (lower+compile+roofline) on 8 devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["REPRO_DRYRUN_DEVICES"] = "8"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-1.5b",
         "--shapes", "decode_32k", "--mesh", "test8", "--out",
         "/tmp/dryrun_pytest", "--no-resume"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=480)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


@pytest.mark.slow
def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint under an 8-device mesh, restore onto a 4-device mesh."""
    _run(f"""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.dist.elastic import restore_on_mesh, state_shardings_for
        from repro.train import checkpoint as ckpt
        from repro.train.trainer import init_state
        from repro.train.optimizer import adamw, AdamWConfig
        from repro.launch.mesh import make_mesh

        cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                               d_ff=128, vocab=256)
        model = build_model(cfg)
        state = init_state(model, jax.random.PRNGKey(0),
                           adamw(AdamWConfig()))
        mesh_a = make_mesh((2, 4), ("data", "model"))
        shapes, sh_a = state_shardings_for(model, mesh_a)
        state_a = jax.device_put(state, sh_a)
        ckpt.save("{tmp_path}/ck", 3, state_a)

        # "pod loss": resume on half the fleet
        mesh_b = make_mesh((2, 2), ("data", "model"))
        step, state_b = restore_on_mesh("{tmp_path}/ck", model, mesh_b)
        assert step == 3
        a = np.asarray(jax.tree.leaves(state["params"])[0])
        b = np.asarray(jax.tree.leaves(state_b["params"])[0])
        np.testing.assert_allclose(a, b, atol=0)
        print("OK elastic restore")
    """)


@pytest.mark.slow
def test_multipod_mesh_axes():
    _run("""
        from repro.launch.mesh import make_mesh
        from repro.sharding.rules import data_axes
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert data_axes(mesh) == ("pod", "data")
        print("OK", mesh.shape)
    """)


@pytest.mark.slow
def test_dist_backend_multi_device_parity():
    """The "dist" pipeline backend decodes the same greedy stream as the
    single-executable "model" backend when the layers really are spread
    across a multi-device ("stage",) mesh."""
    _run("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.serving import InferenceSession, ServeRequest, create_backend
        from repro.serving.backends import get_backend
        from repro.serving.backends.dist import DistBackend

        assert get_backend("dist") is DistBackend
        cfg = get_smoke_config("qwen2-1.5b", layers=4, d_model=64, heads=4,
                               d_ff=128, vocab=256)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        prompt = np.array([[11, 23, 37, 41]], np.int32)
        streams = {}
        for mode in ("model", "dist"):
            backend = create_backend(mode, model, params, batch=1, max_len=16)
            r = InferenceSession(backend).run(
                ServeRequest(prompt=prompt, max_new_tokens=6))
            streams[mode] = r.tokens
        b = create_backend("dist", model, params, batch=1, max_len=16)
        assert b.stages == 4  # one layer per stage on the 8-device host
        assert b.pipeline_stats().row()["bubble_pct"] == 75.0
        np.testing.assert_array_equal(streams["model"], streams["dist"])
        print("OK dist backend parity on", len(jax.devices()), "devices")
    """)


@pytest.mark.slow
def test_dist_backend_paged_decode_multi_stage_parity():
    """Paged serving on a REAL multi-stage mesh: per-stage layer-slice
    arenas under shard_map, one pipelined decode cycle for every active
    slot, chunked prefill through block tables, radix warm hits — greedy
    streams byte-identical to independent dense runs."""
    _run("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.serving import (InferenceSession, Scheduler, ServeRequest,
                                   create_backend)

        cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                               d_ff=128, vocab=256)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        backend = create_backend("dist", model, params, batch=1, max_len=32,
                                 stages=2)
        assert backend.stages == 2 and backend.capabilities.paged_kv
        session = InferenceSession(backend)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, cfg.vocab_size, size=(1, n))
                   .astype(np.int32) for n in (9, 4, 13)]
        refs = [session.run(ServeRequest(prompt=p, max_new_tokens=5)).tokens
                for p in prompts]
        sched = Scheduler(session, num_slots=2, kv_layout="paged",
                          prefill_chunk=4, block_size=4)
        ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=5,
                                         request_id=f"d{i}"))
               for i, p in enumerate(prompts)]
        results = sched.run()
        for i, rid in enumerate(ids):
            np.testing.assert_array_equal(results[rid].tokens, refs[i])
        st = sched.last_stats
        assert st.mean_occupancy > 1.0      # slots genuinely overlapped
        # ONE pipelined dispatch per cycle (vs one per slot in the dense
        # per-slot-loop fallback) — the arena's layer axis is stage-sharded
        assert st.dispatches_per_token < 2.0
        # warm hit on a repeated prompt reuses the cached chain
        rid = sched.submit(ServeRequest(prompt=prompts[0], max_new_tokens=5,
                                        request_id="warm"))
        np.testing.assert_array_equal(sched.run()[rid].tokens, refs[0])
        assert sched.last_stats.prefix_hit_tokens > 0
        print("OK dist paged parity,", backend.stages, "stages,",
              "disp/tok", st.dispatches_per_token)
    """, devices=2)
