"""The work a serving cell requires, counted from live lengths: what
any implementation has to read, write and compute, whatever tiles the
kernel of the day happens to touch. Roofline and utilization shares
divide the least time this work can take on the chip by measured time.
"""
from __future__ import annotations

from typing import Dict

F32 = 4


def decode_attention(live_tokens: int, lanes: int, cfg: Dict) -> Dict:
    """One decode-attention call: each live KV row is read once (K and
    V, every KV head), each lane's queries are read and its output
    written; two matmul passes of 2 FLOPs per multiply-add."""
    hkv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    hq = int(cfg["num_attention_heads"])
    return {"bytes": live_tokens * hkv * d * F32 * 2 + lanes * hq * d * F32 * 2,
            "flops": live_tokens * hq * d * 2 * 2}


def kv_rows_written(rows: int, cfg: Dict) -> Dict:
    """Writing ``rows`` new KV rows (K and V, every KV head)."""
    hkv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    return {"bytes": rows * hkv * d * F32 * 2, "flops": 0}


def least_seconds(work: Dict, peaks: Dict) -> float:
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
