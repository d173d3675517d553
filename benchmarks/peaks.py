"""The table of peaks and the cost of the kernels that architectures share,
kept with the benchmark so that no later PR can move the yardstick. What a
model needs (parameters, FLOPs a token, bytes a step, which kernels with what
shapes) is its architecture module's, `benchmarks/arch/`.

Peaks of one chip, keyed by JAX's `device_kind`. Source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM). An unknown
device is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            "benchmarks/peaks.py with its source"
        ) from None


# ---------------------------------------------------------------- kernels
# One call of a flash kernel on q,k,v of [BH, S, Dh] (bf16), causal.
# Matmul FLOPs over the causal half (+ the diagonal blocks are counted as
# half too: a lower bound, so a share can only read low, never above 100).
def flash_fwd_cost(bh: int, seq: int, dh: int) -> dict:
    flops = 2 * 2 * bh * seq * seq * dh / 2          # QK^T, PV
    bytes_ = 2 * bh * seq * dh * 4 + 4 * bh * seq    # q,k,v in, o out (bf16) + lse
    return {"flops": flops, "bytes": bytes_}


def flash_bwd_dq_cost(bh: int, seq: int, dh: int) -> dict:
    flops = 3 * 2 * bh * seq * seq * dh / 2          # QK^T, dP=dO V^T, dQ=dS K
    bytes_ = 2 * bh * seq * dh * 5 + 8 * bh * seq    # q,k,v,do in, dq out + lse,delta
    return {"flops": flops, "bytes": bytes_}


def flash_bwd_dkv_cost(bh: int, seq: int, dh: int) -> dict:
    flops = 4 * 2 * bh * seq * seq * dh / 2          # QK^T, dP, dV=P^T dO, dK=dS^T Q
    bytes_ = 2 * bh * seq * dh * 6 + 8 * bh * seq    # q,k,v,do in, dk,dv out
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(cost: dict, device_kind: str) -> tuple:
    """(least seconds the chip could take, which bound applies)."""
    pk = peak(device_kind)
    t_f = cost["flops"] / pk["bf16_flops"]
    t_b = cost["bytes"] / pk["hbm_bytes_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
