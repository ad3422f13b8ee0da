"""Work of one decode tick of the Qwen2.5 decoder (``Qwen2ForCausalLM``).

A tick decodes one token for every row of the batch: per row, every
layer's query, key, value and output projections (the key and value
ones over the key-value heads alone) and its three MLP matrices, then
the output head over the vocabulary; and attention over the row's live
context (each query head's scores and its weighted sum of values).  Two
operations to a multiply-add.  Gathers, biases, norms, rotary positions
and the softmax are not counted: they are not matrix work.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable


def padded_vocab(model: Dict[str, Any]) -> int:
    pad = int(model["vocab_pad_to"])
    return -(-int(model["vocab_size"]) // pad) * pad


def matmul_flops(model: Dict[str, Any], rows: int) -> float:
    """The weight matrices' FLOPs for ``rows`` tokens."""
    d, f, n = int(model["d_model"]), int(model["d_ff"]), int(model["n_layers"])
    hd = d // int(model["n_heads"])
    q = int(model["n_heads"]) * hd
    kv = int(model["n_kv_heads"]) * hd
    per_layer = 2 * d * (2 * q + 2 * kv) + 2 * 3 * d * f
    return float(rows * (n * per_layer + 2 * d * padded_vocab(model)))


def attention_flops(model: Dict[str, Any], contexts: Iterable[int]) -> float:
    """Scores and weighted values over each row's context (the positions
    it attends to, the new token included), for every query head."""
    d, n = int(model["d_model"]), int(model["n_layers"])
    return float(n * 2 * 2 * d * sum(int(c) for c in contexts))


def decode_flops(model: Dict[str, Any], contexts: Iterable[int]) -> float:
    """One tick over rows with these contexts."""
    contexts = list(contexts)
    return matmul_flops(model, len(contexts)) + attention_flops(model, contexts)
