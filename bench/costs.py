"""Operations and bytes that a serving step needs, from shapes alone.

These are what the algorithm requires, not what the program happens to
move: a decode step reads each weight it must read once (every weight of
a dense model; of an expert stack, only the experts its rows are routed
to), reads each active row's live KV once and writes the new token's
K/V; it does not count the gathered block table or any padding.  The
shares built on them (roofline, MFU) therefore stay at or under 100% by
construction, and a share above it means the time left out part of the
work.

The counts are the model family's (``bench.families``), looked up by the
``model_type`` of a configuration's ``config`` dict (Hugging Face key
names); weights and KV are stored at ``itemsize`` bytes (2: bfloat16).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

from bench.families import family

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def weight_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """Every weight the configuration stores."""
    return family(cfg).param_count(cfg) * itemsize


def kv_bytes_per_token(cfg: Dict, itemsize: int = 2) -> int:
    """The KV cache of one position over every layer."""
    return family(cfg).kv_bytes_per_token(cfg, itemsize)


def decode_step(cfg: Dict, ctx: Iterable[int], itemsize: int = 2
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one batched decode step; ``ctx[r]`` is the number
    of positions row r attends over, its new token included."""
    return family(cfg).decode_step(cfg, ctx, itemsize)


def prefill_chunk(cfg: Dict, pos0: int, valid: int, itemsize: int = 2
                  ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill chunk of ``valid`` tokens starting at
    position ``pos0``."""
    return family(cfg).prefill_chunk(cfg, pos0, valid, itemsize)


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
