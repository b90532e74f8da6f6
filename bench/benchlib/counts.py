"""Operations and bytes the algorithm needs, from a configuration's shapes.

The block decides which matrices a token multiplies by, which other
tensors and which cached state a step reads, and what attention costs:
those counts come from the configuration's module under
``bench/references/`` (``arch``; the interface is in ``reference.py``).
What is left here is the weight format and how the counts combine into a
decode step, a request and a least time.

Nothing here reads the program's tree or XLA's ``cost_analysis()``: the
counts are the same whatever implements the step, so a change to the
program moves the measured time and never the yardstick.

* Weights are counted in the declared QMC format: per matrix, the inlier
  subtiles at ``bits_in`` and the outlier subtiles at ``bits_out`` bits a
  weight, one tag bit and one int32 stream position per subtile, and two
  float32 scales per output column (what ``QTensor`` stores, at logical
  bit widths).
* Every other tensor a step reads (norms, biases, the embedding rows of
  the step's tokens) and the cached state are counted at ``kv_dtype``
  (bfloat16, the model's stated dtype).
"""
from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def matmul_params(arch, model: dict) -> Dict[str, int]:
    """Weights multiplied per token: ``layers`` (all of them) and ``head``."""
    layer, (hd_in, hd_out) = arch.matmuls(model)
    return {"layers": sum(i * o * n for _, i, o, n in layer),
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


def qmc_weight_bytes(arch, model: dict, weights: dict) -> float:
    """Every matrix a decode step multiplies by (all layers + LM head)."""
    layer, (hd_in, hd_out) = arch.matmuls(model)
    return (sum(qmc_matrix_bytes(i, o, weights) * n for _, i, o, n in layer)
            + qmc_matrix_bytes(hd_in, hd_out, weights))


def avg_bits(weights: dict) -> float:
    """Logical code bits per weight: rho*bits_out + (1-rho)*bits_in."""
    return (weights["rho"] * weights["bits_out"]
            + (1 - weights["rho"]) * weights["bits_in"])


def decode_step(arch, model: dict, weights: dict, batch: float,
                ctx_tokens: float, kv_dtype: str) -> Dict[str, float]:
    """FLOPs and bytes of one decode step of ``batch`` lanes attending to
    ``ctx_tokens`` cached positions in all (summed over lanes)."""
    p = matmul_params(arch, model)
    flops = 2 * batch * (p["layers"] + p["head"]) \
        + arch.attn_flops(model, ctx_tokens)
    nbytes = (qmc_weight_bytes(arch, model, weights)
              + arch.other_bytes(model, batch, kv_dtype)
              + arch.state_bytes(model, batch, ctx_tokens, kv_dtype))
    return {"flops": flops, "bytes": nbytes}


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The roofline bound: the larger of compute and memory time, and
    which of the two it is."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def request_flops(arch, model: dict, prompt_len: int,
                  out_len: int) -> float:
    """Useful FLOPs of one served request: every layer's matmuls and
    attention at each processed position (the prompt, then every output
    token but the last, which is never fed back), and the LM head at the
    ``out_len`` positions that select a token. Padding is not counted."""
    n = prompt_len + out_len - 1
    p = matmul_params(arch, model)
    return (2 * n * p["layers"] + 2 * out_len * p["head"]
            + arch.seq_attn_flops(model, n))
