"""Stable names for the parts of the paged step: ``repro.obs.scopes``
reads them back from compiled HLO, and ``ModelBackend.compiled_programs``
hands over the text of the programs a traced run executed."""
import collections

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.obs import Tracer
from repro.obs.scopes import SCOPES, _parse, op_scopes, scope_of
from repro.serving import (InferenceSession, SamplerConfig, Scheduler,
                           ServeRequest, create_backend)

MOE_SCOPES = ("moe_route", "moe_experts")

HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(step)/while/body/mlp/mul"}
  %add.1 = f32[8]{0} add(f32[8]{0} %mul.1, f32[8]{0} %param_0), metadata={op_name="jit(step)/while/body/mlp/add"}
  ROOT %sub.1 = f32[8]{0} subtract(f32[8]{0} %add.1, f32[8]{0} %param_0), metadata={op_name="jit(step)/while/body/attn/sub"}
}

ENTRY %main.2 (arena: f32[8], /*index=1*/w: f32[8]) -> f32[8] {
  %arena = f32[8]{0} parameter(0), metadata={op_name="arena"}
  %w = f32[8]{0} parameter(1), metadata={op_name="w"}
  %copy.41 = f32[8]{0} copy(f32[8]{0} %arena), metadata={op_name="arena"}
  %gather.3 = f32[8]{0} gather(f32[8]{0} %copy.41), metadata={op_name="jit(step)/kv_gather/gather"}
  %copy.43 = f32[8]{0} copy(f32[8]{0} %gather.3)
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %copy.43), kind=kLoop, calls=%fused_computation.1
  %dot.9 = f32[8]{0} multiply(f32[8]{0} %fusion.7, f32[8]{0} %w), metadata={op_name="jit(step)/unembed/mul"}
  ROOT %neg.2 = f32[8]{0} negate(f32[8]{0} %dot.9), metadata={op_name="jit(step)/jit(helper)/neg"}
}
"""


def test_scope_of_takes_the_innermost_scope():
    assert scope_of("jit(f)/while/body/closed_call/attn/mul") == "attn"
    assert scope_of("jit(f)/kv_write/jit(helper)/mlp/add") == "mlp"
    assert scope_of("jit(f)/while/body/dynamic_slice") == ""


def test_op_scopes_rules_on_hand_written_hlo():
    m = op_scopes(HLO)
    assert m["gather.3"] == "kv_gather"          # 1: its own op_name
    assert m["dot.9"] == "unembed"
    assert m["fusion.7"] == "mlp"                # 2: most of its body
    assert m["copy.43"] == "kv_gather"           # 3: from its operand
    assert m["copy.41"] == "kv_gather"           # 3: from its user
    assert m["neg.2"] == "unembed"               # 3: operand before user
    # parameters run no op; fused bodies are not device ops of their own
    assert not {"arena", "w", "mul.1", "add.1", "sub.1"} & set(m)


@pytest.fixture(scope="module")
def traced_backend():
    """A tiny paged run with a live tracer, traced by the profiler."""
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    backend = create_backend("model", model, params, batch=1, max_len=64)
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      tracer=Tracer())
    rng = np.random.default_rng(4)
    for _ in range(2):
        p = rng.integers(0, cfg.vocab_size, (1, 12)).astype(np.int32)
        sched.submit(ServeRequest(prompt=p, max_new_tokens=4))
    sched.run()
    return backend


def test_compiled_paged_programs_name_all_five_scopes(traced_backend):
    progs = traced_backend.compiled_programs()
    assert set(progs) == {"jit__decode_paged", "jit__extend_paged"}
    assert all(len(texts) == 1 for texts in progs.values())
    for name, (text,) in progs.items():
        m = op_scopes(text)
        # a dense model's steps: every scope but the MoE layer's two
        assert set(m.values()) == set(SCOPES) - set(MOE_SCOPES), name
        # the fused-computation fallback carries some of the step
        comps = _parse(text)
        nested = {i[4] for insts in comps.values() for i in insts if i[4]}
        own = {i[0]: scope_of(i[2]) for c, insts in comps.items()
               if c not in nested for i in insts}
        assert any(i[1] == "fusion" and not own[i[0]] and i[0] in m
                   for c, insts in comps.items() if c not in nested
                   for i in insts), name


def test_programs_are_noted_only_while_traced():
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    backend = create_backend("model", model, params, batch=1, max_len=64)
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8)
    sched.submit(ServeRequest(prompt=np.arange(1, 13, dtype=np.int32)
                              .reshape(1, -1), max_new_tokens=3))
    sched.run()
    assert backend.compiled_programs() == {}


def test_compiled_text_names_the_ops_a_trace_shows(traced_backend,
                                                   tmp_path):
    """The text ``compiled_programs`` returns names each op as the
    profiler does (on the CPU the ops are events on XLA's threads)."""
    from jax.profiler import ProfileData
    backend = traced_backend
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      tracer=backend.tracer)
    prompt = np.arange(3, 15, dtype=np.int32).reshape(1, -1)
    sched.submit(ServeRequest(prompt=prompt, max_new_tokens=3))
    sched.run()                                  # same shapes: no compile
    with jax.profiler.trace(str(tmp_path)):
        sched.submit(ServeRequest(prompt=prompt + 1, max_new_tokens=5))
        sched.run()
    names = {prog: {i[0] for insts in _parse(text).values() for i in insts}
             for prog, (text,) in backend.compiled_programs().items()}
    (path,) = tmp_path.rglob("*.xplane.pb")
    ops = collections.defaultdict(set)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                try:
                    st = dict(e.stats)
                except (TypeError, ValueError):
                    continue
                if st.get("hlo_module") in names and "hlo_op" in st:
                    ops[st["hlo_module"]].add(st["hlo_op"])
    assert set(ops) == set(names)
    for prog, seen in ops.items():
        assert seen <= names[prog], prog


# -- an MoE layer's two scopes and its routing counters ----------------------

def _moe_sched(tracer=None):
    cfg = get_smoke_config("mellum2-12b-a2.5b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    backend = create_backend("model", model, params, batch=1, max_len=64)
    kw = {"tracer": tracer} if tracer is not None else {}
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8, **kw)
    return cfg, backend, sched


@pytest.fixture(scope="module")
def traced_moe():
    tracer = Tracer()
    cfg, backend, sched = _moe_sched(tracer)
    rng = np.random.default_rng(5)
    for _ in range(2):
        p = rng.integers(0, cfg.vocab_size, (1, 12)).astype(np.int32)
        sched.submit(ServeRequest(prompt=p, max_new_tokens=4))
    sched.run()
    return cfg, backend, tracer, sched


def test_moe_programs_name_the_moe_scopes(traced_moe):
    """Decode and extend of the mixed sliding/full MoE config: every
    scope, the router's ``moe_route`` and the experts' ``moe_experts``
    among them, inside the one program each."""
    _, backend, _, _ = traced_moe
    progs = backend.compiled_programs()
    assert set(progs) == {"jit__decode_paged", "jit__extend_paged"}
    for name, (text,) in progs.items():
        assert set(op_scopes(text).values()) == set(SCOPES), name


def test_moe_counters_count_each_decode_steps_experts(traced_moe):
    """One row routes to k distinct experts in each layer: every decode
    step reads L·k experts, and no expert takes more than the one row."""
    cfg, _, tracer, _ = traced_moe
    touched = [e.args["value"] for e in tracer.events()
               if e.name == "moe.experts_touched"]
    most = [e.args["value"] for e in tracer.events()
            if e.name == "moe.max_expert_rows"]
    cycles = tracer.count("decode_cycle")
    assert cycles and len(touched) == len(most) == cycles
    assert set(touched) == {cfg.num_layers * cfg.moe.top_k}
    assert set(most) == {1}


def test_moe_ids_cross_only_for_a_live_tracer(monkeypatch):
    """Without a tracer the scheduler reads back the tokens alone: no
    ``device_get`` of the expert ids, and no counter."""
    import repro.serving.session as session

    def refuse(*_):
        raise AssertionError("expert ids read back without a tracer")
    monkeypatch.setattr(session.jax, "device_get", refuse)
    cfg, _, sched = _moe_sched()
    sched.submit(ServeRequest(prompt=np.arange(1, 11, dtype=np.int32)
                              .reshape(1, -1), max_new_tokens=4,
                              request_id="r"))
    assert sched.run()["r"].n_new == 4


# -- the scheduler's host-only retire counter --------------------------------

class _Observing(InferenceSession):
    """Reads every row's logits while it is retired."""

    def step_row(self, a, out):
        np.asarray(out.logits)
        return super().step_row(a, out)


@pytest.mark.parametrize("kind", ["greedy", "temperature", "greedy-observed"])
def test_retire_counter_counts_tokens_retired_on_the_host(kind):
    """``retire.host_only`` is each cycle's count of tokens retired with
    host numpy alone: every row of a greedy cycle, so the cycle's
    occupancy, and none of a sampled one, whose rows need a key and
    their logits, nor of a greedy one whose session reads the logits."""
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    backend = create_backend("model", model, params, batch=1, max_len=64)
    tracer = Tracer()
    session = (_Observing if kind == "greedy-observed"
               else InferenceSession)(backend)
    sched = Scheduler(session, num_slots=2,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      tracer=tracer)
    rng = np.random.default_rng(6)
    for n_new in (6, 9, 4):
        p = rng.integers(0, cfg.vocab_size, (1, 10)).astype(np.int32)
        sched.submit(ServeRequest(prompt=p, max_new_tokens=n_new,
                                  sampler=SamplerConfig(kind.split("-")[0])))
    sched.run()
    events = tracer.events()
    host_only = [e.args["value"] for e in events
                 if e.name == "retire.host_only"]
    cycles = sorted((e for e in events if e.name == "decode_cycle"),
                    key=lambda e: e.args["cycle"])
    occupancy = [e.args["occupancy"] for e in cycles]
    assert len(cycles) == sched.last_stats.cycles and 2 in occupancy
    if kind == "greedy":
        assert host_only == occupancy
        assert sched.last_stats.overlap_cycles > 0
    else:
        assert host_only == [0] * len(occupancy)
