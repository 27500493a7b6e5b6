"""Pure-jnp reference oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the numerical ground truth the kernels are tested
against (tests/test_kernels_*.py sweep shapes and dtypes).  They are also the
CPU fallbacks used when Pallas interpret mode is not desired.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# Full f32 dots where the kernels ask for them (see kernels/cobi_dynamics.py).
_EXACT = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# COBI coupled-oscillator dynamics
# ---------------------------------------------------------------------------


def ref_cobi_trajectory(
    j_scaled: Array,  # (N, N) symmetric, zero diag, pre-scaled by 1/denom
    h_scaled: Array,  # (N,)   pre-scaled by 1/denom
    phi0: Array,  # (R, N) initial phases
    *,
    steps: int,
    dt: float,
    ks_max: float,
) -> Array:
    """Integrate the oscillator phase ODE; returns final phases (R, N).

    dphi_i/dt = [2 * sum_j J_ij sin(phi_i - phi_j) + h_i sin(phi_i)]
                - ks(t) * sin(2 phi_i)
    with  sum_j J_ij sin(phi_i-phi_j) = sin(phi_i)*(J cos(phi))_i
                                        - cos(phi_i)*(J sin(phi))_i.
    This is gradient descent on the phase relaxation of
    H = h.s + s^T J s  (s_i = cos phi_i), plus a ramped sub-harmonic
    injection-locking (SHIL) term that binarizes phases to {0, pi}.

    Op sequence matches the Pallas kernels' _anneal_loop exactly: the two J
    products are one stacked [cos; sin] @ (2 J) contraction (row-independent,
    and power-of-two scaling is FP-exact) and the SHIL term is the identity
    sin(2 phi) = 2 sin phi cos phi, so only 2 trig + 1 matmul per step.
    """
    j_scaled = j_scaled.astype(jnp.float32)
    h_scaled = h_scaled.astype(jnp.float32).reshape(1, -1)
    j2 = j_scaled + j_scaled  # exact: *2 only bumps exponents
    r = phi0.shape[0]

    def step(t, phi):
        s = jnp.sin(phi)
        c = jnp.cos(phi)
        m = jnp.concatenate([c, s], axis=0)  # (2R, N); J symmetric
        mj = m @ j2
        grad = (s * mj[:r] - c * mj[r:]) + h_scaled * s
        ks = ks_max * (t.astype(jnp.float32) + 1.0) / steps
        return phi + dt * (grad - ks * (2.0 * (s * c)))

    return jax.lax.fori_loop(0, steps, step, phi0.astype(jnp.float32))


def ref_cobi_spins(phi: Array) -> Array:
    """Read out spins s = sign(cos phi) in {-1, +1} (int8)."""
    return jnp.where(jnp.cos(phi) >= 0.0, 1, -1).astype(jnp.int8)


def ref_cobi_trajectory_batched(
    j_scaled: Array,  # (B, N, N)
    h_scaled: Array,  # (B, N)
    phi0: Array,  # (B, R, N)
    *,
    steps: int,
    dt: float,
    ks_max: float,
) -> Array:
    """vmap of :func:`ref_cobi_trajectory` over a stack of B instances."""
    traj = lambda j, h, p: ref_cobi_trajectory(j, h, p, steps=steps, dt=dt, ks_max=ks_max)
    return jax.vmap(traj)(j_scaled, h_scaled, phi0)


def ref_cobi_fused_best(
    phi: Array,  # (B, R, N) final phases
    j_orig: Array,  # (B, N, N) scoring couplings (original, unscaled)
    h_orig: Array,  # (B, N)
    mask: Array,  # (B, N, S) 0/1 lane->slot assignment
    reads: Array,  # (B, S) valid-read count per slot
) -> tuple[Array, Array]:
    """Oracle for the fused readout epilogue (kernels/cobi_dynamics.py).

    Signs phases into spins, scores per-lane energy densities against the
    original coefficients, folds them into per-slot energies through the lane
    mask, masks replicas past each slot's read budget to +inf, and keeps the
    FIRST replica attaining each slot's minimum (host ``np.argmin`` ties).
    Returns (best_energies (B, S) f32, best_spins (B, S, N) f32 in {-1,+1}).
    """
    s = jnp.where(jnp.cos(phi) >= 0.0, 1.0, -1.0).astype(jnp.float32)
    sj = jnp.einsum("brn,bnm->brm", s, j_orig.astype(jnp.float32))
    e_lanes = s * sj + h_orig.astype(jnp.float32)[:, None, :] * s
    e_slots = jnp.einsum("brn,bns->brs", e_lanes, mask.astype(jnp.float32))
    r = phi.shape[1]
    rep = jnp.arange(r, dtype=jnp.float32)[None, :, None]
    e_slots = jnp.where(rep < reads.astype(jnp.float32)[:, None, :], e_slots, jnp.inf)
    best_e = jnp.min(e_slots, axis=1)  # (B, S)
    hit = e_slots == best_e[:, None, :]
    first = jnp.min(jnp.where(hit, rep, jnp.float32(r)), axis=1).astype(jnp.int32)
    best_s = jax.vmap(lambda sb, fb: sb[fb])(s, first)  # (B, S, N)
    return best_e, best_s


# ---------------------------------------------------------------------------
# MCMC asynchronous Metropolis sweeps (counter-based randomness)
# ---------------------------------------------------------------------------

# Odd 32-bit constants decorrelating the (replica, sweep, proposal) counter
# axes before the avalanche mix.  Shared verbatim by the Pallas kernel
# (kernels/mcmc_dynamics.py): the randomness is a pure function of LOGICAL
# indices, never of how the grid or the chunk loop decomposes them, which is
# what makes the kernel bit-identical to this oracle at any decomposition.
MCMC_CTR_REP = 0x9E3779B1
MCMC_CTR_SWEEP = 0x85EBCA77
MCMC_CTR_POS = 0xC2B2AE3D


def mcmc_mix32(x: Array) -> Array:
    """lowbias32-style avalanche on uint32 (wrapping multiply is exact XLA
    semantics on every backend, so kernel and oracle agree bitwise)."""
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def mcmc_u01(seed: Array, rep: Array, sweep: Array, pos: Array) -> Array:
    """Uniform [0, 1) as a pure function of (seed, replica, sweep, proposal).

    Counter-based (no carried RNG state): every (replica, sweep, proposal)
    triple hashes independently, so any loop order / grid split that visits
    the same logical triples draws the same numbers.  24 mantissa bits.
    """
    x = (
        jnp.asarray(seed, jnp.uint32)
        + jnp.asarray(rep, jnp.uint32) * jnp.uint32(MCMC_CTR_REP)
        + jnp.asarray(sweep, jnp.uint32) * jnp.uint32(MCMC_CTR_SWEEP)
        + jnp.asarray(pos, jnp.uint32) * jnp.uint32(MCMC_CTR_POS)
    )
    bits = mcmc_mix32(x) >> jnp.uint32(8)
    # Below 2**24, so the int32 hop is exact; Mosaic has no uint32 -> f32 cast.
    return bits.astype(jnp.int32).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def mcmc_temperature(t_hi: Array, log_ratio: Array, ts: Array, denom: Array) -> Array:
    """Sweep ``ts`` of the geometric ladder t_hi * (t_lo/t_hi)^(ts/denom),
    written as exp(log_ratio * x) because Mosaic cannot lower ``powf``.
    Shared by the kernel and :func:`ref_mcmc_sweep` so both walk one ladder."""
    return t_hi * jnp.exp(log_ratio * (ts.astype(jnp.float32) / denom))


def mcmc_seeds(key: Array) -> Array:
    """(4,) uint32 seed words derived from a ``jax.random`` key: [init,
    pick, accept, spare].  The only place the key is consumed -- everything
    downstream is counter-based."""
    return jax.random.bits(key, (4,), jnp.uint32)


def mcmc_init_spins(seed_init: Array, replicas: int, n: int) -> Array:
    """(R, N) f32 +-1 initial spins from counters (sweep axis pinned to 0)."""
    rep = jnp.arange(replicas, dtype=jnp.uint32)[:, None]
    pos = jnp.arange(n, dtype=jnp.uint32)[None, :]
    u = mcmc_u01(seed_init, rep, jnp.uint32(0), pos)
    return jnp.where(u < 0.5, 1.0, -1.0).astype(jnp.float32)


def mcmc_t_hi(j: Array) -> Array:
    """Default hot temperature 2*max_i sum_j |J_ij| + eps (f32), matching the
    SA baseline's choice.  Compute on the UNPADDED couplings: zero-padding
    can reassociate the row sums and perturb the last mantissa bit."""
    return 2.0 * jnp.abs(jnp.asarray(j, jnp.float32)).sum(-1).max() + jnp.float32(1e-6)


def ref_mcmc_sweep(
    j: Array,  # (N, N) symmetric couplings (f32 or int; zero diag)
    h: Array,  # (N,) local fields
    key: Array,  # jax.random key -> 3 counter seeds via mcmc_seeds
    *,
    replicas: int,
    sweeps: int,
    mode: str = "sweep",  # "sweep" (in-order chunk sweep) | "random" proposals
    t_hi: Array | float | None = None,
    t_lo: float = 0.05,
    n_real: int | None = None,  # live positions (rest are padding no-ops)
) -> tuple[Array, Array]:
    """Asynchronous single-spin Metropolis sweeps; the MCMC kernel oracle.

    R replicas anneal independently down a geometric per-sweep temperature
    ladder T(t) = t_hi * (t_lo/t_hi)^(t/(sweeps-1)).  Each sweep makes one
    proposal per position: ``mode="sweep"`` updates spins strictly in order
    0..n-1 (every replica proposes the same position -- the Snowball-style
    sequential chunk sweep); ``mode="random"`` draws each replica's position
    uniformly from [0, n_real) (asynchronous uniform proposals).  The local
    field f = s @ J is maintained by rank-1 updates, so a proposal costs
    O(R*N); acceptance is the standard Metropolis rule on
    dE = -2 s_k (h_k + 2 f_k).  Proposals at positions >= n_real are exact
    no-ops (flip factor 0.0), so a padded call matches an unpadded one on
    the live lanes.  Returns (best spins (R, N) f32 +-1, best energies (R,)
    f32) -- the best state each replica VISITED, as in the SA baseline.
    """
    if mode not in ("sweep", "random"):
        raise ValueError(f"unknown mcmc mode {mode!r}")
    j = jnp.asarray(j, jnp.float32)
    n = j.shape[-1]
    hrow = jnp.asarray(h, jnp.float32).reshape(1, n)
    if t_hi is None:
        t_hi = mcmc_t_hi(j)
    t_hi = jnp.asarray(t_hi, jnp.float32)
    t_lo = jnp.asarray(t_lo, jnp.float32)
    n_live = jnp.float32(n if n_real is None else n_real)
    seeds = mcmc_seeds(key)
    rep = jnp.arange(replicas, dtype=jnp.uint32)[:, None]
    lanes = jnp.arange(n, dtype=jnp.float32)[None, :]
    s0 = mcmc_init_spins(seeds[0], replicas, n)
    f0 = jnp.dot(s0, j, preferred_element_type=jnp.float32, precision=_EXACT)
    e0 = jnp.sum(s0 * hrow + s0 * f0, axis=1, keepdims=True)
    log_ratio = jnp.log(t_lo / t_hi)
    denom = jnp.float32(max(sweeps - 1, 1))

    def sweep_body(ts, carry):
        temp = mcmc_temperature(t_hi, log_ratio, ts, denom)
        ts_u = ts.astype(jnp.uint32)

        def t_body(t, carry):
            s, f, e, best_e, best_s = carry
            tf = t.astype(jnp.float32)
            u_acc = mcmc_u01(seeds[2], rep, ts_u, t.astype(jnp.uint32))
            if mode == "random":
                u_pick = mcmc_u01(seeds[1], rep, ts_u, t.astype(jnp.uint32))
                k = jnp.floor(u_pick * n_live)  # (R, 1)
                onehot = (lanes == k).astype(jnp.float32)  # (R, N)
            else:
                onehot = (lanes == tf).astype(jnp.float32)  # (1, N)
            s_k = jnp.sum(s * onehot, axis=1, keepdims=True)
            f_k = jnp.sum(f * onehot, axis=1, keepdims=True)
            h_k = jnp.sum(hrow * onehot, axis=1, keepdims=True)
            j_k = jnp.dot(onehot, j, preferred_element_type=jnp.float32, precision=_EXACT)
            de = -2.0 * s_k * (h_k + 2.0 * f_k)
            accept = u_acc < jnp.exp(
                jnp.minimum(-de / jnp.maximum(temp, 1e-9), 0.0)
            )
            flip = jnp.where(accept & (tf < n_live), 1.0, 0.0)
            s_new = s * (1.0 - 2.0 * onehot * flip)
            f_new = f - 2.0 * (s_k * flip) * j_k
            e_new = e + de * flip
            better = e_new < best_e
            return (
                s_new,
                f_new,
                e_new,
                jnp.where(better, e_new, best_e),
                jnp.where(better, s_new, best_s),
            )

        return jax.lax.fori_loop(0, n, t_body, carry)

    _, _, _, best_e, best_s = jax.lax.fori_loop(
        0, sweeps, sweep_body, (s0, f0, e0, e0, s0)
    )
    return best_s, best_e[:, 0]


# ---------------------------------------------------------------------------
# Batched Ising energy
# ---------------------------------------------------------------------------


def ref_ising_energy(spins: Array, h: Array, j: Array) -> Array:
    """E_r = h . s_r + s_r^T J s_r  for a batch of spin vectors (R, N)."""
    s = spins.astype(jnp.float32)
    return s @ h.astype(jnp.float32) + jnp.einsum(
        "ri,ij,rj->r", s, j.astype(jnp.float32), s
    )


def ref_ising_energy_batched(spins: Array, h: Array, j: Array) -> Array:
    """E_br for (B, R, N) spins against per-instance (B, N) h, (B, N, N) J."""
    s = spins.astype(jnp.float32)
    lin = jnp.einsum("brn,bn->br", s, h.astype(jnp.float32))
    quad = jnp.einsum("bri,bij,brj->br", s, j.astype(jnp.float32), s)
    return lin + quad


# ---------------------------------------------------------------------------
# Flash attention (blocked online softmax), causal or full, with optional
# sliding window.  Reference = naive materialized attention.
# ---------------------------------------------------------------------------


def ref_attention(
    q: Array,  # (B, Sq, H, D)
    k: Array,  # (B, Skv, KH, D)
    v: Array,  # (B, Skv, KH, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> Array:
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    assert h % kh == 0
    rep = h // kh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / (d**0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned queries
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out.astype(q.dtype)
