"""Operations and bytes of the work the device does, from its sizes.

Each function counts what the algorithm needs, not what an implementation
pads to: a later change that pads less, or fuses more, reads closer to its
roofline without this file changing.  A least time is the larger of the
operations over the peak rate and the bytes over the HBM bandwidth of
``bench/peaks.json``.
"""

from __future__ import annotations


def fedavg(k: int, p: int) -> tuple[int, int]:
    """Weighted mean of a (K, P) float32 stack: read the stack and the K
    weights, write P values; a multiply and an add per element."""
    return 2 * k * p, 4 * k * p + 4 * k + 4 * p


def dequantize(items: int, blocks: int, block: int) -> tuple[int, int]:
    """int8 codes -> float32 for ``items`` payloads of ``blocks`` blocks:
    read a byte per code and a scale per block, write 4 bytes per code."""
    codes = items * blocks * block
    return codes, 5 * codes + 4 * items * blocks


def topk_scatter(items: int, k: int, n: int) -> tuple[int, int]:
    """Kept (index, value) pairs -> dense rows of ``n``: read 8 bytes per
    kept pair, write every element of the dense row."""
    return 0, items * (8 * k + 4 * n)


def decode(items: int, stages: list[tuple[str, dict]]) -> tuple[int, int]:
    """Work of the decode stages of ``items`` like payloads (stage sizes as
    ``bench/reference/wire.py``'s ``work`` gives them)."""
    flops = nbytes = 0
    for name, s in stages:
        if name == "int8":
            f, b = dequantize(items, s["blocks"], s["block"])
        elif name == "topk":
            f, b = topk_scatter(items, s["k"], s["n"])
        else:
            continue
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
