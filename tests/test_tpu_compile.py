"""Ahead-of-time compiles for a TPU v5e chip: every Pallas kernel, and
the paged model programs at the benchmark's shapes.

The TPU compiler is installed alongside JAX, and it compiles for a chip
that is described rather than attached.  Interpret mode (what every other
kernel test runs) cannot see Mosaic's tiling and VMEM rules; these
compiles can, at Qwen2.5-0.5B widths in bf16: d_model 896, d_ff 4864,
14 query heads over 2 KV heads of head_dim 64 (the routed-expert kernel
at Mellum2's: 64 experts of 2304 x 896).  The same compiles show the
layout copies XLA puts around the paged KV arena.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite's workers all
import this file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.fused_kv_proj.kernel import kv_proj_pallas
from repro.kernels.fused_mlp.kernel import fused_mlp_pallas
from repro.kernels.fused_rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.fused_softmax.kernel import softmax_pallas
from repro.kernels.routed_experts.kernel import routed_experts_pallas
from repro.kernels.tiled_matmul.kernel import matmul_pallas
from repro.models import build_model
from repro.serving.backends.dist import DistBackend
from repro.serving.paging import (arena_shape, decode_step_paged,
                                  extend_step_paged)

D, F, H, KV, HD = 896, 4864, 14, 2, 64
ME, MD, MF = 64, 2304, 896       # Mellum2: experts, d_model, expert width
ROWS = 128          # one MXU row block of tokens
SLOTS, S = 4, 2048  # decode: scheduler slots × cache length

# kernel → (fn, [(shape, dtype), ...]) in the kernel's own argument layout
CASES = {
    "tiled_matmul": (matmul_pallas, [((ROWS, D), "bf16"), ((D, F), "bf16")]),
    "fused_rmsnorm": (rmsnorm_pallas, [((ROWS, D), "bf16"), ((D,), "bf16")]),
    "fused_mlp": (fused_mlp_pallas, [((ROWS, D), "bf16"), ((D, F), "bf16"),
                                     ((D, F), "bf16")]),
    "fused_kv_proj": (kv_proj_pallas, [((ROWS, D), "bf16"),
                                       ((D, 2 * KV * HD), "bf16"),
                                       ((2 * KV * HD,), "bf16")]),
    "fused_softmax": (softmax_pallas, [((8, 151936), "bf16")]),
    "decode_attention": (decode_attention_pallas, [
        ((SLOTS, KV, H // KV, HD), "bf16"), ((SLOTS, KV, S, HD), "bf16"),
        ((SLOTS, KV, S, HD), "bf16"), ((1,), "i32")]),
    # one decode row's 8 routed experts (rows padded to 8), of one layer
    # of the 8-layer stacks
    "routed_experts": (routed_experts_pallas, [
        ((1,), "i32"), ((8,), "i32"), ((1,), "i32"), ((8, MD), "bf16"),
        ((8, 8, 1), "f32"), ((8, ME, MD, MF), "bf16"),
        ((8, ME, MD, MF), "bf16"), ((8, ME, MF, MD), "bf16")]),
}
_DTYPES = {"bf16": jnp.bfloat16, "i32": jnp.int32, "f32": jnp.float32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, _DTYPES[dt], sharding=one_chip)
            for shape, dt in specs]
    compiled = (jax.jit(functools.partial(fn, interpret=False))
                .lower(*args).compile())
    # Mosaic kernel present, i.e. not lowered as plain XLA ops
    assert "tpu_custom_call" in compiled.as_text()


# -- paged programs: the benchmark's shapes (one slot, block 16, prefill
# chunk 512, max_len 2048: a 160-block table, an arena of one slot's table
# plus two spare chains and the trash block) -------------------------------
ARENA_BLOCKS, TABLE_W, BLOCK, CHUNK = 481, 160, 16, 512
STAGES = 4          # the dist backend's pipeline over the v5e:2x2's chips
# an instruction's output (an async copy's first tuple element: where it
# writes), its layout and its opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\]"
                    r"\{([^}]*)\}.*?\s([a-z][\w-]*)\(", re.M)
_ARENA = re.compile(r"= (\w+)\[([\d,]*)\]\{([^}]*)\} parameter\(\d+\)"
                    r".*op_name=\"ak\"")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4}
_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
                "collective-permute")


def _dims(text):
    return tuple(int(d) for d in text.split(",") if d)


def _tiles(layout):
    """A layout's tiling, without its memory space."""
    return re.sub(r"S\(\d+\)", "", layout.partition(":")[2])


def _row_major(layout):
    order = _dims(layout.partition(":")[0])
    return list(order) == sorted(order, reverse=True)


def _params(cfg, sharding, blocks_sharding=None):
    shapes = jax.eval_shape(build_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    return {k: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=(
                    blocks_sharding if k == "blocks" and blocks_sharding
                    else sharding)), v)
            for k, v in shapes.items()}


def _paged_args(cfg, program, sharding, arena_sharding):
    arena = jax.ShapeDtypeStruct(arena_shape(cfg, ARENA_BLOCKS, BLOCK),
                                 jnp.dtype(cfg.dtype),
                                 sharding=arena_sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    rest = ((i32(1, TABLE_W), i32(1), i32(1, 1)) if program == "decode"
            else (i32(1, TABLE_W), i32(), i32(), i32(1, CHUNK)))
    return (arena, arena) + rest


def _paged_program(arch, program, one_chip):
    cfg = get_config(arch)
    fn = {"decode": decode_step_paged, "extend": extend_step_paged}[program]
    step = jax.jit(lambda p, ak, av, *a: fn(p, cfg, ak, av, *a),
                   donate_argnums=(1, 2))
    args = _paged_args(cfg, program, one_chip, one_chip)
    return cfg, step.lower(_params(cfg, one_chip),
                           *args).compile().as_text()


def _dist_paged_program(arch, program, topo):
    """The dist backend's paged program, pipelined over the described
    chips: the backend's own method, on a mesh of chips that are not
    attached (the constructor would place weights on them)."""
    cfg = get_config(arch)
    mesh = Mesh(np.array(topo.devices[:STAGES]), ("stage",),
                axis_types=(AxisType.Auto,))
    backend = object.__new__(DistBackend)
    backend.cfg, backend.stages, backend.mesh = cfg, STAGES, mesh
    fn = {"decode": backend._sharded_decode_paged,
          "extend": backend._sharded_extend_paged}[program]
    repl = NamedSharding(mesh, P())
    args = _paged_args(cfg, program, repl, NamedSharding(mesh, P(None,
                                                                 "stage")))
    params = _params(cfg, repl, NamedSharding(mesh, P("stage")))
    return cfg, (jax.jit(fn, donate_argnums=(1, 2)).lower(params, *args)
                 .compile().as_text())


def _layout_copies(text):
    """Every copy in a compiled module, synchronous or async, as (dims,
    bytes, whole, relaid): ``whole`` where it moves as many bytes as one
    whole arena (as one device holds it), ``relaid`` where such a copy also
    changes the arena's layout (an order that is not row-major, or other
    tiles than the stored arena's), not only its memory space."""
    arena_dt, arena_dims, stored = _ARENA.search(text).groups()
    arena = math.prod(_dims(arena_dims)) * _BYTES[arena_dt]
    out = []
    for dt, dims, layout, op in _INSTR.findall(text):
        if op not in ("copy", "copy-start"):
            continue
        shape = _dims(dims)
        nbytes = math.prod(shape) * _BYTES.get(dt, 4)
        whole = nbytes == arena
        relaid = whole and (not _row_major(layout)
                            or _tiles(layout) != _tiles(stored))
        out.append((shape, nbytes, whole, relaid))
    return out


# The one relayout of a whole arena that XLA keeps where head_dim is
# lane-aligned: the 1.5B extend relays V's arena out for the chunk's
# attention, on the split layout that this arena keeps (and had before the
# layout rule), one chip or pipelined.
_ARENA_RELAYOUTS = {("qwen2.5-1.5b", "extend"): 1}


def _check_arena_copies(arch, program, text):
    """No relayout of the arena but those listed above, and a decode
    moves no whole arena at all.  An extend may move an arena whole into
    another memory space and back around its scatter, in its stored
    layout.  Returns the module's copies."""
    copies = _layout_copies(text)
    relaid = [c for c in copies if c[3]]
    assert len(relaid) <= _ARENA_RELAYOUTS.get((arch, program), 0), relaid
    if program == "decode":
        assert not [c for c in copies if c[2]], copies
    return copies


@pytest.mark.parametrize("program", ["decode", "extend"])
@pytest.mark.parametrize("arch", ["qwen2.5-0.5b", "qwen2.5-1.5b"])
def test_paged_program_keeps_the_arena_in_place(arch, program, one_chip):
    """The paged programs read and write the arena as stored: no copy
    that relays all its blocks out (the 0.5B's head_dim 64 arena is stored
    lane-dense), and no decode copy of a whole arena or larger than the
    gathered per-layer view."""
    cfg, text = _paged_program(arch, program, one_chip)
    copies = _check_arena_copies(arch, program, text)
    if program == "decode":
        view = (cfg.num_layers * TABLE_W * BLOCK * cfg.num_kv_heads
                * cfg.resolved_head_dim * jnp.dtype(cfg.dtype).itemsize)
        assert max(b for _, b, _, _ in copies) <= view, copies


@pytest.mark.parametrize("program", ["decode", "extend"])
@pytest.mark.parametrize("arch", ["qwen2.5-0.5b", "qwen2.5-1.5b"])
def test_dist_paged_program_keeps_the_arena_in_place(arch, program, topo):
    """The dist backend's paged programs over four stages: each stage
    reads and writes its layer slice of the arena as stored (the same
    arena copies as on one chip), and the stages exchange only
    activations: no all-gather, and no collective as large as a stage's
    arena."""
    cfg, text = _dist_paged_program(arch, program, topo)
    _check_arena_copies(arch, program, text)
    dt, dims, _ = _ARENA.search(text).groups()
    stored = _dims(dims)
    assert stored[1] == cfg.num_layers // STAGES     # the stage's slice
    arena = math.prod(stored) * _BYTES[dt]
    coll = [(op, math.prod(_dims(dims)) * _BYTES.get(dt, 4))
            for dt, dims, _, op in _INSTR.findall(text)
            if op.startswith(_COLLECTIVES)]
    assert coll and not [c for c in coll if c[0].startswith("all-gather")]
    assert max(b for _, b in coll) < arena, coll


# bytes of every copy in each Qwen decode program before per-layer
# attention settings and MoE layers joined the scans (62.9 and 73.4 MB)
_QWEN_DECODE_COPY_BYTES = {"qwen2.5-0.5b": 62_917_388,
                           "qwen2.5-1.5b": 73_404_188}


@pytest.mark.parametrize("arch", sorted(_QWEN_DECODE_COPY_BYTES))
def test_qwen_decode_copies_are_unchanged(arch, one_chip):
    """A config whose layers are all alike compiles to the program it
    did: the same copies, to the byte."""
    _, text = _paged_program(arch, "decode", one_chip)
    assert sum(b for _, b, _, _ in _layout_copies(text)) \
        == _QWEN_DECODE_COPY_BYTES[arch]


def test_mellum_decode_reads_experts_in_place(one_chip, monkeypatch):
    """The Mellum2 decode step at 2 layers (one sliding, one full YaRN),
    over a 64-block table (1,024 positions, the window): the
    routed-expert kernel is in it, no copy is as large as one expert's
    matrix (2304 x 896 bf16, 4.1 MB), and no op but the loop's plumbing
    holds an array of expert matrices (the scan's slice of a layer's
    stack, 0.79 GB at 64 experts, would be one), so no expert weight is
    gathered or copied before the kernel reads it.  The kernel's
    interpret switch follows the CPU this compiles on, so the test sets
    it for the chip."""
    from repro.kernels.routed_experts import ops
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    cfg = get_config("mellum2-12b-a2.5b").replace(
        num_layers=2, layer_types=("sliding", "full"))
    ak, av, _, pos, tok = _paged_args(cfg, "decode", one_chip, one_chip)
    table = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, ak, av, *a: decode_step_paged(p, cfg, ak, av,
                                                           *a),
                   donate_argnums=(1, 2))
    text = step.lower(_params(cfg, one_chip), ak, av, table, pos,
                      tok).compile().as_text()
    assert text.count("tpu_custom_call") == 1       # the scan body's one
    copies = _check_arena_copies("mellum2-12b-a2.5b", "decode", text)
    assert max(b for _, b, _, _ in copies) < MD * MF * 2, copies
    held = [(op, dims) for _, dims, _, op in _INSTR.findall(text)
            if _dims(dims)[-2:] in ((MD, MF), (MF, MD))
            and op not in ("parameter", "get-tuple-element", "tuple",
                           "bitcast", "while")]
    assert not held, held


def test_mellum_extend_reads_experts_in_place(one_chip):
    """The Mellum2 prefill chunk at 2 layers: its grouped products run
    over the expert stacks flattened across layers, so no op but the
    loop's plumbing holds an array of expert matrices (a slice of one
    layer's stack, 0.79 GB at 64 experts, would be one)."""
    cfg = get_config("mellum2-12b-a2.5b").replace(
        num_layers=2, layer_types=("sliding", "full"))
    step = jax.jit(lambda p, ak, av, *a: extend_step_paged(p, cfg, ak, av,
                                                           *a),
                   donate_argnums=(1, 2))
    text = step.lower(_params(cfg, one_chip), *_paged_args(
        cfg, "extend", one_chip, one_chip)).compile().as_text()
    assert "ragged" in text
    held = [(op, dims) for _, dims, _, op in _INSTR.findall(text)
            if _dims(dims)[-2:] in ((MD, MF), (MF, MD))
            and op not in ("parameter", "get-tuple-element", "tuple",
                           "bitcast", "while")]
    assert not held, held
