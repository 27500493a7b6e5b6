"""Operations and bytes each piece of work needs, from real sizes only.

Padding (lanes past a job's spins, reads past its budget, PAD tokens,
packed neighbours) is never counted: these are the algorithm's needs, so a
roofline share says how close the kernel came to the least time the chip
could take for the useful work.
"""

from __future__ import annotations

F32 = 4


def encoder_flops(enc: dict, tokens: int) -> float:
    """Forward FLOPs of one document row of ``tokens`` real tokens: the
    q/k/v/o projections, the gated MLP's three matmuls, and causal
    attention over each token's prefix (scores and values)."""
    d, f, layers = enc["d_model"], enc["d_ff"], enc["n_layers"]
    hd = d // enc["n_heads"]
    kv = enc["n_kv_heads"] * hd
    proj = 2 * d * (d + 2 * kv) + 2 * d * d
    mlp = 2 * d * f * (3 if enc.get("gated_mlp", True) else 2)
    attn = 2 * 2 * d * tokens * (tokens + 1) / 2  # QK^T and PV, causal
    return float(layers * (tokens * (proj + mlp) + attn))


def cobi_flops(n: int, reads: int, steps: int) -> float:
    """One COBI job: per Euler step and read, the [cos; sin] rows times J
    (2 rows x 2n^2) plus about 12 elementwise ops per spin (two trig, the
    gradient, the SHIL term, the update); then one readout energy per read
    (2n^2 + 3n)."""
    per_step = 4.0 * n * n + 12.0 * n
    return float(reads * (steps * per_step + 2.0 * n * n + 3.0 * n))


def cobi_bytes(n: int, reads: int) -> float:
    """Scaled and original couplings and fields in, initial phases in, the
    best energy and spins out."""
    return float(F32 * (2 * n * n + 2 * n + reads * n + n + 1))


def mcmc_flops(n: int, reads: int, sweeps: int) -> float:
    """One MCMC job: per sweep, ``n`` proposals per replica, each reading one
    coupling row into the local fields (2n) plus about 10 scalar ops for the
    energy change and the Metropolis test; the initial fields (2n^2)."""
    return float(reads * (sweeps * n * (2.0 * n + 10.0) + 2.0 * n * n))


def mcmc_bytes(n: int, reads: int) -> float:
    return float(F32 * (n * n + n + reads * n + n + 1))
