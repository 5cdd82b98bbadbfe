"""The work the served requests need, computed from the configuration.

These count what a request needs, whoever implements it: the label
owner's weights read once per flush; for each served row, the KV of its
own positions read (its actual length, not the arena's `max_len`) and one
position written; the FLOPs of the active rows only. A program that steps
more rows or reads more cache than this does more than the requests need,
and its roofline share says so.
"""
from __future__ import annotations

import numpy as np


def _top_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["cut_layer"]


def layer_params(conf: dict) -> int:
    """Matmul parameters of one decoder layer."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def dtype_bytes(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def top_weight_bytes(conf: dict) -> int:
    """Weights the label owner reads once per flush: its layers, their
    norms, the final norm and the unembedding (published vocabulary)."""
    d, n = conf["hidden_size"], _top_layers(conf)
    per_layer = layer_params(conf) + 2 * d + (
        2 * conf["head_dim"] if conf["qk_norm"] else 0)
    elems = n * per_layer + d + d * conf["vocab_size"]
    return elems * dtype_bytes(conf)


def kv_bytes_per_position(conf: dict) -> int:
    """K and V of one position over the label owner's layers."""
    return (_top_layers(conf) * 2 * conf["num_key_value_heads"]
            * conf["head_dim"] * dtype_bytes(conf))


def row_flops(conf: dict, pos: np.ndarray) -> np.ndarray:
    """FLOPs of one served row at 0-based position `pos` (it attends over
    pos + 1 positions): the top layers' matmuls, attention at its actual
    length, and the head."""
    pos = np.asarray(pos, np.float64)
    attn = (4.0 * conf["num_attention_heads"] * conf["head_dim"]
            * (pos + 1) * _top_layers(conf))
    dense = 2.0 * (_top_layers(conf) * layer_params(conf)
                   + conf["hidden_size"] * conf["vocab_size"])
    return dense + attn


def row_bytes(conf: dict, pos: np.ndarray) -> np.ndarray:
    """Bytes of one served row beyond the weights: its KV read at its
    actual length (pos + 1 positions), one position written, its cut
    activation in and its token out."""
    pos = np.asarray(pos, np.float64)
    kv = kv_bytes_per_position(conf)
    return kv * (pos + 1) + kv + conf["hidden_size"] * dtype_bytes(conf) + 4


def step_least_seconds(conf: dict, rows_per_flush: np.ndarray,
                       positions: np.ndarray, peaks: dict):
    """Least device seconds for flushes of the given row counts serving
    the given positions, and which bound sets it ("bytes" or "flops").
    Rows are spread over the flushes in order."""
    rows = np.asarray(rows_per_flush, np.int64)
    pos = np.asarray(positions, np.float64)
    ends = np.cumsum(rows)
    f = np.add.reduceat(row_flops(conf, pos), ends - rows) if len(pos) else 0
    b = np.add.reduceat(row_bytes(conf, pos), ends - rows) if len(pos) else 0
    t_f = f / peaks["bf16_flops_per_s"]
    t_b = (top_weight_bytes(conf) + b) / peaks["hbm_bytes_per_s"]
    least = np.maximum(t_f, t_b)
    bound = "bytes" if float(np.sum(t_b)) >= float(np.sum(t_f)) else "flops"
    return float(np.sum(least)), bound


def decode_bytes(conf: dict, payload_bytes: float, rows: int) -> float:
    """Bytes the slot decode needs: the payloads in, dense rows out."""
    return payload_bytes + rows * conf["hidden_size"] * dtype_bytes(conf)
