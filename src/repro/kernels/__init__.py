"""Pallas TPU kernels for the paper's compute hot-spots, adapted from the
WGSL shaders to the TPU memory hierarchy (HBM→VMEM→MXU):

* ``tiled_matmul``     — the paper's 16×16-tile WGSL matmul, re-tiled to
                         128×128×128 MXU-aligned VMEM blocks (Table 8)
* ``fused_rmsnorm``    — the 6-dispatch RMSNorm chain in one kernel (Table 7)
* ``fused_mlp``        — gate/up/SiLU in one kernel, two accumulators
                         sharing the x block (Table 5's MLP fusion)
* ``fused_kv_proj``    — K+V in one tiled matmul w/ bias epilogue (Table 5)
* ``fused_softmax``    — one-pass row softmax (the paper's 84× §5.1 fix)
* ``decode_attention`` — flash-style single-token GQA attention against a
                         long KV cache (the batch-1 decode hot loop)
* ``routed_experts``   — a decode step's routed SwiGLU experts, each read
                         once in place by scalar-prefetched id (MoE)

Each kernel ships ``kernel.py`` (pallas_call + BlockSpec), ``ops.py``
(jitted public entry point; interpret=True on CPU), ``ref.py`` (pure-jnp
oracle used by the allclose test sweeps).
"""
from repro.kernels.tiled_matmul.ops import tiled_matmul
from repro.kernels.fused_rmsnorm.ops import fused_rmsnorm
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.fused_kv_proj.ops import fused_kv_proj
from repro.kernels.fused_softmax.ops import fused_softmax
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.routed_experts.ops import routed_experts

__all__ = ["tiled_matmul", "fused_rmsnorm", "fused_mlp", "fused_kv_proj",
           "fused_softmax", "decode_attention", "routed_experts"]
