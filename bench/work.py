"""Least work of each measured layer, from the call shapes alone.

These are the numerators of the rooflines and of ``train_mfu``: what the
algorithm needs, not what today's kernels do (padding, unused lanes and
repeated passes belong to the implementation), so no share of a peak built
on them can pass 100%."""
from __future__ import annotations


def conv_out(size: int, stride: int) -> int:
    """Output side of a SAME-padded convolution."""
    return -(-size // stride)


def forward_macs(cfg: dict) -> list:
    """Multiply-accumulates per sample of each weight layer of the
    configuration's network, in order: 3x3 SAME convolutions (a 2x2
    max-pool after conv ``pool_after_conv``) then fully connected
    layers."""
    h, w, _ = cfg["input"]
    macs = []
    for i, (ci, co, stride) in enumerate(cfg["convs"]):
        h, w = conv_out(h, stride), conv_out(w, stride)
        macs.append(h * w * 9 * ci * co)
        if i == cfg.get("pool_after_conv"):
            h, w = h // 2, w // 2
    macs += [fi * fo for fi, fo in cfg["fcs"]]
    return macs


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward FLOPs of one training sample: 2 per MAC
    forward, 4 per MAC backward (input and weight gradients), except that
    the first layer needs no input gradient."""
    macs = forward_macs(cfg)
    return 6 * sum(macs) - 2 * macs[0]


def maghist_least(n: int, d: int) -> tuple:
    """(bytes, ops) of one batched magnitude histogram of an (n, d) float32
    matrix: every gradient read once, one bin update each."""
    return 4 * n * d, n * d


def sparse_aggregate_least(nk: int, d: int) -> tuple:
    """(bytes, ops) of one sparse aggregation of nk (int32 index, float32
    value) pairs into d dense float32 sums: the pairs read once, the sums
    written once, one add per pair."""
    return 8 * nk + 4 * d, nk


def least_seconds(bytes_: float, ops: float, peak: dict) -> float:
    """Roofline time of a call: the larger of its memory time and its
    compute time at the chip's peaks."""
    return max(bytes_ / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
