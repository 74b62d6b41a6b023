"""Work counts from shapes, and the chip peaks they are held against.

Every roofline and utilization share the benchmark reports divides one of
these counts by a device time read from a trace.  The counts are of the
algorithm's work, not of one implementation's: a kernel that reads less or
computes less than counted here reads above 100%, which is a fault in the
count.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Mapping, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's entry for ``device_kind`` (as JAX reports it).  A
    kind missing from the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: Mapping[str, float]
                 ) -> Tuple[float, str]:
    """Least time the chip could take for ``flops`` operations and
    ``nbytes`` bytes of HBM traffic, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


# ---------------------------------------------------------------------------
# Aggregate Risk Analysis (arXiv:1606.04473, Algorithm 3)
# ---------------------------------------------------------------------------
def risk_step_work(trials: int, events: int, elts: int, catalog: int
                   ) -> Tuple[float, float]:
    """(operations, bytes) one analysis step over ``trials`` trials needs.

    Bytes: the year event table's ids once (trials x events x 4), the
    event-loss tables once ((catalog + 1) x elts x 4, row 0 the pad event)
    and the year-loss table written once (trials x 4).  Operations: per
    event and ELT a subtract, a clip, a min and an add (4 x trials x events
    x elts).  Whatever implements the lookup (a row DMA per event, an XLA
    gather, a sort), it cannot need less."""
    flops = 4.0 * trials * events * elts
    nbytes = 4.0 * (trials * events + (catalog + 1) * elts + trials)
    return flops, nbytes


# ---------------------------------------------------------------------------
# dense decoder-only transformers (GQA, SwiGLU, untied embeddings)
# ---------------------------------------------------------------------------
def lm_matmul_params(cfg: Mapping[str, int]) -> int:
    """Parameters that take part in a matrix product for each token: per
    layer the q/k/v/o projections and the three SwiGLU matrices, plus the
    output head.  The input embedding is a lookup and is not counted."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def lm_attention_flops(cfg: Mapping[str, int], context: int) -> float:
    """Attention-score and value products of one query token over
    ``context`` keys, over all layers: 2 x 2 x heads x head_dim x context."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def lm_prefill_flops(cfg: Mapping[str, int], prompt_len: int) -> float:
    """Operations to prefill one prompt of ``prompt_len`` real tokens with
    causal attention (token i attends to i + 1 keys).  Padding to a bucket
    does not count."""
    n = prompt_len
    causal_keys = n * (n + 1) / 2
    return 2.0 * lm_matmul_params(cfg) * n + lm_attention_flops(cfg, 1) * causal_keys


def lm_decode_flops(cfg: Mapping[str, int], context: int) -> float:
    """Operations of one live decode row-step whose new token attends to
    ``context`` keys (itself included)."""
    return 2.0 * lm_matmul_params(cfg) + lm_attention_flops(cfg, context)
