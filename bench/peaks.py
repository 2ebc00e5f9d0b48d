"""Published peaks of each chip, keyed by JAX's ``device_kind``, and the
operations and bytes a decoder's prefill and decode need, from shapes.

Source of the v5e numbers: Google Cloud documentation, "TPU v5e" (per
chip: 197 TFLOP/s bfloat16, 16 GB of HBM at 819 GB/s). A kind not in the
table is an error, never a default.
"""

from __future__ import annotations

from typing import Any, Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _vocab_rows(vocab: int) -> int:
    return -(-vocab // 256) * 256


def matmul_params(m: Dict[str, Any]) -> int:
    """Weights a token multiplies by, per layer and in the output head."""
    d, h, kv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return m["n_layers"] * per_layer + d * _vocab_rows(m["vocab"])


def weight_bytes(m: Dict[str, Any]) -> int:
    """Bytes of the weights one decode step has to read, in their served
    type (norm scales in float32): every matrix a token multiplies by,
    the output head whole. An untied input embedding is only gathered, one
    row a token, and is left out."""
    norms = (2 * m["n_layers"] + 1) * m["d_model"] * 4
    return matmul_params(m) * _DTYPE_BYTES[m["dtype"]] + norms


def kv_bytes_per_position(m: Dict[str, Any]) -> int:
    """Key and value bytes one position holds over all layers."""
    return (m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"]
            * _DTYPE_BYTES[m["dtype"]])


def _attended(m: Dict[str, Any], position: int) -> int:
    window = m.get("window")
    ctx = position + 1
    return min(ctx, window) if window else ctx


def decode_flops(m: Dict[str, Any], position: int) -> float:
    """Operations of one decode step for the token at ``position``:
    2 per multiply-add of the weights, plus scores and values over the
    positions it attends to."""
    attn = 4 * m["n_heads"] * m["head_dim"] * _attended(m, position)
    return 2.0 * matmul_params(m) + m["n_layers"] * attn


def decode_bytes(m: Dict[str, Any], position: int) -> float:
    """Least bytes one decode step moves: every weight once, the keys and
    values of the positions it attends to, and its own new position."""
    return float(weight_bytes(m)
                 + kv_bytes_per_position(m) * (_attended(m, position) + 1))


def prefill_flops(m: Dict[str, Any], length: int) -> float:
    """Operations of a prefill of ``length`` tokens (causal attention: each
    position attends to those before it, within the window)."""
    attn = sum(4 * m["n_heads"] * m["head_dim"] * _attended(m, p)
               for p in range(length))
    body = length * 2.0 * (matmul_params(m) - m["d_model"]
                           * _vocab_rows(m["vocab"]))
    head = 2.0 * m["d_model"] * _vocab_rows(m["vocab"])   # last position only
    return body + head + m["n_layers"] * attn
