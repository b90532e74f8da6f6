"""Operations and bytes the algorithm needs, from a configuration's shapes.

Nothing here reads the program's tree or XLA's ``cost_analysis()``: the
counts are the same whatever implements the step, so a change to the
program moves the measured time and never the yardstick.

* Weights are counted in the declared QMC format: per matrix, the inlier
  subtiles at ``bits_in`` and the outlier subtiles at ``bits_out`` bits a
  weight, one tag bit and one int32 stream position per subtile, and two
  float32 scales per output column (what ``QTensor`` stores, at logical
  bit widths).
* Every other tensor a step reads (norms, biases, the embedding rows of
  the step's tokens) and the live KV cache are counted at ``kv_dtype``
  (bfloat16, the model's stated dtype).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def matmuls(model: dict) -> Tuple[List[Tuple[str, int, int]],
                                  Tuple[int, int]]:
    """(per-layer [(name, din, dout)], lm head (din, dout))."""
    d, ff = model["d_model"], model["d_ff"]
    q = model["n_heads"] * model["head_dim"]
    kv = model["n_kv_heads"] * model["head_dim"]
    layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]
    if model["gated_mlp"]:
        layer.append(("w_gate", d, ff))
    layer += [("w_up", d, ff), ("w_down", ff, d)]
    return layer, (d, model["vocab"])


def matmul_params(model: dict) -> Dict[str, int]:
    """Weights multiplied per token: ``layers`` (all of them) and ``head``."""
    layer, (hd_in, hd_out) = matmuls(model)
    per_layer = sum(i * o for _, i, o in layer)
    return {"layers": per_layer * model["n_layers"],
            "head": hd_in * hd_out}


def qmc_matrix_bytes(din: int, dout: int, weights: dict) -> float:
    """Bytes of one matrix in the QMC stream format at logical widths."""
    r, c = weights["subtile"]
    n_sub = (din // r) * (dout // c)
    k_out = int(round(weights["rho"] * n_sub))
    k_in = n_sub - k_out
    codes = (k_in * weights["bits_in"] + k_out * weights["bits_out"]) \
        * r * c / 8
    meta = n_sub / 8 + n_sub * 4          # outlier tag bit + stream position
    scales = 2 * dout * 4                 # inlier and outlier fp32 scales
    return codes + meta + scales


def qmc_weight_bytes(model: dict, weights: dict) -> float:
    """Every matrix a decode step multiplies by (all layers + LM head)."""
    layer, (hd_in, hd_out) = matmuls(model)
    per_layer = sum(qmc_matrix_bytes(i, o, weights) for _, i, o in layer)
    return per_layer * model["n_layers"] + qmc_matrix_bytes(hd_in, hd_out,
                                                            weights)


def avg_bits(weights: dict) -> float:
    """Logical code bits per weight: rho*bits_out + (1-rho)*bits_in."""
    return (weights["rho"] * weights["bits_out"]
            + (1 - weights["rho"]) * weights["bits_in"])


def other_bytes(model: dict, batch: float, dtype: str) -> float:
    """Norm scales and qkv biases of every layer, the final norm, and the
    embedding rows of ``batch`` tokens, at ``dtype``."""
    d = model["d_model"]
    per_layer = 2 * d
    if model["qkv_bias"]:
        per_layer += (model["n_heads"] + 2 * model["n_kv_heads"]) \
            * model["head_dim"]
    n = per_layer * model["n_layers"] + d + batch * d
    return n * DTYPE_BYTES[dtype]


def kv_bytes(model: dict, tokens: float, dtype: str) -> float:
    """K and V of ``tokens`` cached positions over all layers."""
    return (tokens * model["n_layers"] * 2 * model["n_kv_heads"]
            * model["head_dim"] * DTYPE_BYTES[dtype])


def attn_flops(model: dict, ctx_tokens: float) -> float:
    """QK^T and PV of one query over ``ctx_tokens`` keys, all layers
    (summed over queries when ``ctx_tokens`` is a sum of contexts)."""
    return (4 * ctx_tokens * model["n_heads"] * model["head_dim"]
            * model["n_layers"])


def decode_step(model: dict, weights: dict, batch: float, ctx_tokens: float,
                kv_dtype: str) -> Dict[str, float]:
    """FLOPs and bytes of one decode step of ``batch`` lanes attending to
    ``ctx_tokens`` cached positions in all (summed over lanes)."""
    p = matmul_params(model)
    flops = 2 * batch * (p["layers"] + p["head"]) \
        + attn_flops(model, ctx_tokens)
    nbytes = (qmc_weight_bytes(model, weights)
              + other_bytes(model, batch, kv_dtype)
              + kv_bytes(model, ctx_tokens, kv_dtype))
    return {"flops": flops, "bytes": nbytes}


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The roofline bound: the larger of compute and memory time, and
    which of the two it is."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def request_flops(model: dict, prompt_len: int, out_len: int) -> float:
    """Useful FLOPs of one served request: every layer's matmuls and
    causal attention at each processed position (the prompt, then every
    output token but the last, which is never fed back), and the LM head
    at the ``out_len`` positions that select a token. Padding is not
    counted."""
    n = prompt_len + out_len - 1
    p = matmul_params(model)
    ctx = n * (n + 1) / 2                 # position t attends to t + 1 keys
    return (2 * n * p["layers"] + 2 * out_len * p["head"]
            + attn_flops(model, ctx))
