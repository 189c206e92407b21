"""Least work of one multispring update of every spring of one case, at the
configuration's fp32 (4-byte words).

Bytes, per spring: the history θ of 4 fp32 words (γ_rev, τ_rev, γ_prev,
γ_max) and two flags at one bit each, read and written: 2 × 16.25 B.  Per
point: the strain in (6 words), and out the stress (6), the symmetric
tangent (21) and the damping fraction (1).  Material constants are not
counted (an implementation may index them by layer).  Flops, per spring:
γ = n·ε (6 multiply-adds), σ += wτ n (6), D += w G_t n⊗n on the 21
symmetric entries (21); the backbone's powers and divisions are not
counted, so the compute bound under-reads this update."""
from __future__ import annotations

WORD = 4
THETA_BYTES = 4 * WORD + 2 / 8
POINT_WORDS = 6 + 6 + 21 + 1
MACS_PER_SPRING = 6 + 6 + 21


def count(n_elem: int, nspring: int, npoint: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one update of one case."""
    pts = npoint * n_elem
    flops = 2.0 * MACS_PER_SPRING * nspring * pts
    nbytes = pts * (2 * THETA_BYTES * nspring + WORD * POINT_WORDS)
    return flops, float(nbytes)
