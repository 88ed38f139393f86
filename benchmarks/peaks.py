"""Peaks of the chips the benchmark runs on, and the arithmetic that turns
shapes into the operations and bytes an algorithm REQUIRES.

Conventions (fixed here so that no PR that claims a gain can move them):

- Training FLOPs per token are `6*N + 6*L*d*s`: N counts the parameters
  that sit in a matmul (per layer 12*d^2, plus the vocabulary head V*d;
  embedding gathers, biases and norms are not matmuls), each costing 2
  FLOPs forward and 4 backward per token; causal attention needs, per
  token and layer, QK^T and PV over s/2 keys on average = 2*d*s FLOPs
  forward and twice that backward = 6*d*s. Recomputation (remat, the
  flash backward's second pass over the scores) is NOT counted: it is
  work the implementation chose, not work the algorithm requires.
  (`models/gpt2.py flops_per_token` counts attention non-causally and
  twice over; it is not used.)
- A flash kernel's required FLOPs are the causal halves of its matmuls
  at [b,h,s,d]; its required bytes are each operand read once and each
  result written once at the dtype it is passed in.
"""

from __future__ import annotations

from typing import Dict

# device_kind as jax reports it -> peaks. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    """Peaks of one chip. A device that is not in the table is an error,
    not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add it to "
            f"benchmarks/peaks.py with its source") from None


def gpt2_matmul_params(n_layer: int, n_embd: int, vocab_size: int) -> int:
    """Parameters that sit in a matmul: qkv 3d^2 + proj d^2 + mlp 8d^2 per
    layer, and the (tied) vocabulary head V*d."""
    return 12 * n_layer * n_embd ** 2 + vocab_size * n_embd


def gpt2_train_flops_per_token(n_layer: int, n_embd: int, vocab_size: int,
                               seq: int) -> float:
    """6*N + 6*L*d*s (module docstring)."""
    n = gpt2_matmul_params(n_layer, n_embd, vocab_size)
    return 6.0 * n + 6.0 * n_layer * n_embd * seq


def flash_required(b: int, h: int, s: int, d: int, itemsize: int = 2
                   ) -> Dict[str, Dict[str, float]]:
    """Required FLOPs and HBM bytes of the three causal flash kernels at
    q,k,v = [b,h,s,d] (one call each).

    fwd: QK^T and PV, causal half: 2 matmuls * 2*b*h*s*s*d / 2.
         reads q,k,v, writes o (+ the [b,h,s] f32 log-sum-exp).
    bwd_dq:  S=QK^T, dP=dO V^T, dQ=dS K: 3 matmuls (recomputing S is
         required by the algorithm: the scores are not stored).
         reads q,k,v,o|do,lse,delta, writes dq.
    bwd_dkv: S=QK^T, dP=dO V^T, dV=P^T dO, dK=dS^T Q: 4 matmuls.
         reads q,k,v,do,lse,delta, writes dk,dv.
    """
    mm = 2.0 * b * h * s * s * d / 2.0          # one causal matmul
    t = float(b * h * s * d * itemsize)         # one [b,h,s,d] operand
    row = float(b * h * s * 4)                  # one [b,h,s] f32 vector
    return {
        "flash_fwd": {"flops": 2 * mm, "bytes": 4 * t + row},
        "flash_bwd_dq": {"flops": 3 * mm, "bytes": 5 * t + 2 * row},
        "flash_bwd_dkv": {"flops": 4 * mm, "bytes": 6 * t + 2 * row},
    }


def roofline_floor_s(flops: float, nbytes: float, peaks: Dict[str, object]
                     ) -> Dict[str, object]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / float(peaks["flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return {"floor_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
