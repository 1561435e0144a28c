"""Operations the algorithm needs, from shapes. Nothing here runs a model.

Counted: multiply-adds of matrix multiplications as 2 operations each. A
parameter counts when a token is multiplied by it: the projections of every
layer and the output head. The embedding table is a lookup and counts
nothing (``utils/metrics.py:compute_mfu`` counts it, and counts 6N for a
LoRA step; both are wrong, which is why the benchmark owns this function).
Recomputation (remat) is not needed work and is never counted.

Training, per token: forward 2N. A full fine-tune's backward needs the
gradient of every activation (2N) and of every weight (2N): 6N. LoRA's
backward still needs every activation gradient to reach the adapters of the
first layer, but no base weight gradient: 4N, plus 6 per adapter parameter.
Attention has no weights: its forward is two matmuls over the keys a query
sees (the causal band inside its document, cut by the sliding window), its
backward four.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. A device not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(table)}")
    return table[device_kind]


def _dims(model: dict) -> tuple:
    h = model["hidden_size"]
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // n_q
    return h, n_q, n_kv, hd, model["intermediate_size"], model["vocab_size"]


def matmul_params(model: dict) -> int:
    """Parameters a token is multiplied by: projections and the head."""
    h, n_q, n_kv, hd, m, v = _dims(model)
    per_layer = h * n_q * hd + 2 * h * n_kv * hd + n_q * hd * h + 3 * h * m
    return model["num_hidden_layers"] * per_layer + h * v


def lora_params(model: dict, r: int) -> int:
    """Adapter parameters of rank ``r`` on q, k, v and o of every layer."""
    h, n_q, n_kv, hd, _, _ = _dims(model)
    per_layer = (r * (h + n_q * hd) + 2 * r * (h + n_kv * hd)
                 + r * (n_q * hd + h))
    return model["num_hidden_layers"] * per_layer


def mean_keys_seen(doc_lengths: list, window: int | None) -> float:
    """Keys an average query position attends to, over packed documents:
    position i of a document sees min(i, window) keys (itself included)."""
    total = seen = 0
    for n in doc_lengths:
        w = min(n, window) if window else n
        # 1 + 2 + ... + w, then w for each of the n - w positions after
        seen += w * (w + 1) // 2 + (n - w) * w
        total += n
    return seen / total


def attention_flops_per_token(model: dict, keys_seen: float,
                              backward: bool) -> float:
    _, n_q, _, hd, _, _ = _dims(model)
    forward = 2 * 2 * n_q * hd * keys_seen * model["num_hidden_layers"]
    return forward * (3 if backward else 1)


def train_flops_per_token(model: dict, lora_r: int, keys_seen: float) -> float:
    n = matmul_params(model)
    weights = 4 * n + 6 * lora_params(model, lora_r) if lora_r else 6 * n
    return weights + attention_flops_per_token(model, keys_seen, True)
