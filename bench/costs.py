"""Operations and bytes that a serving step needs, from shapes alone.

These are what the algorithm requires, not what the program happens to
move: a decode step reads every weight once, reads each active row's live
KV once and writes the new token's K/V; it does not count the gathered
block table or any padding.  The shares built on them (roofline, MFU)
therefore stay at or under 100% by construction, and a share above it
means the time left out part of the work.

Sizes are a configuration's ``config`` dict (Hugging Face key names);
weights and KV are stored at ``itemsize`` bytes (2: bfloat16).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

from bench.weights import dims, param_count

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one layer that multiply every token (q, k, v, o, MLP)."""
    n = dims(cfg)
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return n["d"] * (q + 2 * kv) + q * n["d"] + 3 * n["d"] * n["F"]


def weight_bytes(cfg: Dict, itemsize: int = 2) -> int:
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg: Dict, itemsize: int = 2) -> int:
    """K and V of one position over every layer."""
    n = dims(cfg)
    return n["L"] * 2 * n["KV"] * n["hd"] * itemsize


def _attn_flops(cfg: Dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, every layer."""
    n = dims(cfg)
    return n["L"] * 4 * n["H"] * n["hd"] * keys


def decode_step(cfg: Dict, ctx: Iterable[int], itemsize: int = 2
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one batched decode step; ``ctx[r]`` is the number
    of positions row r attends over, its new token included."""
    ctx = [int(c) for c in ctx]
    n = dims(cfg)
    per_row = 2 * n["L"] * layer_matmul_params(cfg) + 2 * n["d"] * n["V"]
    flops = len(ctx) * per_row + sum(_attn_flops(cfg, c) for c in ctx)
    nbytes = weight_bytes(cfg, itemsize) \
        + kv_bytes_per_token(cfg, itemsize) * sum(ctx)
    return flops, nbytes


def prefill_chunk(cfg: Dict, pos0: int, valid: int, itemsize: int = 2
                  ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill chunk of ``valid`` tokens starting at
    position ``pos0``: causal attention over the prefix, logits of the
    last position only."""
    n = dims(cfg)
    keys = valid * pos0 + valid * (valid + 1) // 2
    flops = (2 * n["L"] * layer_matmul_params(cfg) * valid
             + _attn_flops(cfg, keys) + 2 * n["d"] * n["V"])
    nbytes = weight_bytes(cfg, itemsize) \
        + kv_bytes_per_token(cfg, itemsize) * (pos0 + valid)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
