"""Helpers the per-layer metric readers (`metrics/*.py`) share."""
from __future__ import annotations


def mean(xs):
    return sum(xs) / len(xs) if xs else None


def roofline(ops: float, nbytes: float, seconds: float, peaks: dict):
    """Least time the chip could take, as a share of ``seconds`` (%)."""
    if seconds <= 0 or (ops <= 0 and nbytes <= 0):
        return None
    least = max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def step_flops(dims: dict, lens: list[int]) -> float:
    """Model operations of one decode step over sequences whose cache
    holds ``lens`` tokens: 2 per matmul weight (LM head included) and
    4 * H * hd per token of context per layer."""
    L, D, H, Hkv, hd, F, V = (dims[k] for k in ("L", "D", "H", "Hkv", "hd",
                                                 "F", "V"))
    matmul = L * (D * (H + 2 * Hkv) * hd + H * hd * D + 3 * D * F) + D * V
    return float(sum(2 * matmul + 4 * H * hd * n * L for n in lens))
