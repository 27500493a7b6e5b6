"""Quantization / rounding of Ising coefficients (paper Sec. IV-A, C3).

COBI supports integer couplings ``h_i, J_ij in [-14, +14]``.  The paper
simulates b-bit fixed point by quantizing to ``[-(2^(b-1)-1), 2^(b-1)-1]``.
A single scale factor maps the joint (h, J) range onto the integer range --
this is exactly where the h-vs-J scale imbalance destroys coupling
resolution, and what the improved formulation (C2) mitigates.

Three rounding schemes (paper Sec. IV-A):
  * ``deterministic``      -- round to nearest.
  * ``stochastic_5050``    -- floor/ceil with probability 1/2 each.
  * ``stochastic``         -- floor + Bernoulli(frac)  (unbiased SR, [17]).

J is rounded on the upper triangle and mirrored so it stays symmetric, as on
the chip (one physical coupler per spin pair).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formulation import IsingProblem

Array = jax.Array

COBI_RANGE = 14  # native integer coupling range of the COBI chip
SCHEMES = ("deterministic", "stochastic_5050", "stochastic")


def int_range_for_bits(bits: int) -> int:
    """Symmetric integer range for a b-bit signed fixed-point format."""
    if bits < 2:
        raise ValueError(f"need >=2 bits, got {bits}")
    return 2 ** (bits - 1) - 1


@dataclasses.dataclass(frozen=True)
class QuantizedIsing:
    """An integer-coefficient Ising instance plus its scale back to FP."""

    ising: IsingProblem  # integer-valued h, J (stored as float32)
    scale: float  # fp_coeff ~= int_coeff / scale


def joint_scale(ising: IsingProblem, int_range: int) -> float:
    """Single scale mapping max(|h|, |J|) onto the integer range."""
    m = max(np.abs(np.asarray(ising.h)).max(), np.abs(np.asarray(ising.j)).max())
    return float(int_range / max(m, 1e-12))


# Smallest draw buckets: every chip-sized instance (n <= 64) shares one
# draw program per key count.
DRAW_MIN_H = 64
DRAW_MIN_J = 64 * 64


def _bucket(size: int, floor: int) -> int:
    b = floor
    while b < size:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("bh", "bj"))
def _uniform_draws(keys, *, bh: int, bj: int):
    """Each key's rounding uniforms: split into (kh, kj), then flat draws
    of ``bh`` and ``bj``.  Flat threefry draws are prefix-stable, so
    ``[:n]`` and ``[:n*n].reshape(n, n)`` are bit for bit the draws of
    shape ``(n,)`` and ``(n, n)`` -- one program per key count and
    bucket, not one per instance size."""

    def one(key):
        kh, kj = jax.random.split(key)
        return jax.random.uniform(kh, (bh,)), jax.random.uniform(kj, (bj,))

    return jax.vmap(one)(jnp.stack(keys))


def _draws(keys, n: int):
    """(K, n) and (K, n, n) host uniforms for a sequence of K keys."""
    uh, uj = jax.device_get(_uniform_draws(
        tuple(keys), bh=_bucket(n, DRAW_MIN_H), bj=_bucket(n * n, DRAW_MIN_J)))
    return uh[:, :n], uj[:, :n * n].reshape(-1, n, n)


def _round(v: np.ndarray, scheme: str, u: Optional[np.ndarray]) -> np.ndarray:
    if scheme == "deterministic":
        return np.round(v)
    lo = np.floor(v)
    frac = v - lo
    if scheme == "stochastic_5050":
        # Integer-valued entries stay put; otherwise 50/50 floor vs ceil.
        p_up = np.where(frac > 0.0, np.float32(0.5), np.float32(0.0))
    else:  # "stochastic"
        p_up = frac
    return lo + (u < p_up).astype(v.dtype)


def _quantize(h, j, u_h, u_j, *, scheme: str, int_range: int):
    """Scale + round + mirror in host float32, over any leading dims of the
    draws: the strict upper triangle of J is rounded once and mirrored."""
    h = np.asarray(h, np.float32)
    j = np.asarray(j, np.float32)
    m = max(np.abs(h).max(), np.abs(j).max())
    scale = np.float32(int_range) / max(m, np.float32(1e-12))
    h_q = np.clip(_round(h * scale, scheme, u_h), -int_range, int_range)
    j_up = np.triu(_round(j * scale, scheme, u_j), k=1)
    j_q = np.clip(j_up + np.swapaxes(j_up, -1, -2), -int_range, int_range)
    return h_q, j_q, float(scale)


def quantize_ising(
    ising: IsingProblem,
    scheme: str = "stochastic",
    *,
    int_range: int = COBI_RANGE,
    bits: Optional[int] = None,
    key: Optional[Array] = None,
) -> QuantizedIsing:
    """Quantize (h, J) to integers in [-R, R] with the given rounding scheme.

    ``bits`` overrides ``int_range`` with the b-bit fixed-point range.
    Returns integer-valued coefficients (host float32 arrays) and the scale
    used, so that ``H_int(s) / scale ~= H_fp(s)``.
    """
    if key is None and scheme != "deterministic":
        raise ValueError(f"scheme {scheme!r} needs a PRNG key")
    return quantize_ising_many(ising, [key], scheme, int_range=int_range,
                               bits=bits)[0]


def quantize_ising_many(
    ising: IsingProblem,
    keys: Sequence[Array],
    scheme: str = "stochastic",
    *,
    int_range: int = COBI_RANGE,
    bits: Optional[int] = None,
) -> list[QuantizedIsing]:
    """K independent roundings of ONE instance, one per key, with one draw
    launch for all K.

    The serving pipeline quantizes the same FP Ising once per
    stochastic-rounding iteration.  Bit-identical to ``[quantize_ising(
    ising, scheme, key=k) for k in keys]``: each row draws its own key's
    stream.
    """
    if bits is not None:
        int_range = int_range_for_bits(bits)
    if scheme not in SCHEMES:
        raise ValueError(f"unknown rounding scheme {scheme!r}; want one of {SCHEMES}")
    u_h = u_j = None
    if scheme != "deterministic":
        u_h, u_j = _draws(keys, int(np.shape(ising.h)[-1]))
    h_q, j_q, scale = _quantize(ising.h, ising.j, u_h, u_j, scheme=scheme,
                                int_range=int_range)
    if u_h is None:
        return [QuantizedIsing(ising=IsingProblem(h=h_q, j=j_q), scale=scale)] * len(keys)
    return [
        QuantizedIsing(ising=IsingProblem(h=h_q[i], j=j_q[i]), scale=scale)
        for i in range(len(keys))
    ]
