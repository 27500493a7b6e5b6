"""Pallas TPU kernels: COBI coupled-oscillator annealing with a fused
anneal→readout→best-of epilogue.

TPU-native design (DESIGN.md sec. 2): the analog oscillator array is
re-expressed so that each Euler step of the phase ODE is two MXU matmuls
(via sin(phi_i - phi_j) = sin phi_i cos phi_j - cos phi_i sin phi_j).

Key VMEM decision: the coupling matrix J (N<=128 padded, f32, 64 KB) and the
local fields h stay **resident in VMEM for the entire trajectory** -- HBM
traffic is one J/h load plus one phases load per replica block, regardless
of the step count T.  The grid is over replica blocks, so independent
anneals (the paper's iterative stochastic-rounding replicas) fill the MXU.

Arithmetic intensity per block: T * 2 * (BR*N*N) MACs over ~(N*N + 2*BR*N)
f32 of traffic -> hundreds of FLOP/byte for T ~ 300: firmly compute-bound.

Fused readout epilogue
----------------------
The chip workflow is "anneal R reads, keep the best", so shipping the full
(R, N) phase trajectory to HBM -- and re-reading it in a second kernel just
to score energies, then shipping every replica's spins to the host for a
numpy argmin -- moves O(R*N) floats per anneal that nobody ever looks at.
The ``*_fused_best`` kernels keep the whole chain resident:

  1. after the Euler ``fori_loop``, phases are signed into spins
     s = sign(cos phi) in registers;
  2. Ising energies are computed against a second VMEM-resident copy of the
     *original* (unscaled) coefficients -- one extra (BR,N)@(N,N) MXU matmul
     on operands already on-chip;
  3. a lane-mask matmul folds per-lane energy densities into per-slot
     energies (a "slot" is one job of a block-diagonally packed
     super-instance; a solo instance is the 1-slot special case), with
     replicas beyond a slot's read budget masked to +inf;
  4. the running (best energy, best spins) per slot is carried across the
     innermost grid dimension by revisiting the same output block: replica
     block i reads what block i-1 left in VMEM and overwrites it only where
     it found a strictly lower energy (strict < keeps the earliest replica
     on ties, matching host ``np.argmin``).

HBM/VMEM accounting per replica block (BR rows, N lanes, S slots, f32):

  two-kernel path                      fused epilogue
  ---------------                      --------------
  in : J,h            (N*N+N)*4  (amortized over R/BR blocks)
       phi0           BR*N*4          in : J_dyn,J_score,h x2, mask
  out: phases         BR*N*4               (2*N*N + 2*N + N*S)*4 (amortized)
  in : phases (sign)  BR*N*4               phi0   BR*N*4
  out: spins          BR*N         out: best spins   S*N*4   (last block)
  in : spins, J again (BR*N+N*N)*4      best energy  S*128*4 (last block)
  out: energies       BR*4
  host: R*N spins + R energies     host: S*N spins + S energies

i.e. post-anneal traffic drops from O(R*N) phases+spins round-trips per
instance to O(S*N) once per instance -- independent of both T and R -- and
the second kernel launch (plus its host-side restacking) disappears.
``*_readout`` variants keep all R reads but still fuse sign+score into the
anneal launch (for ``reduce="topk"``/"none" callers that need every read).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANE = 128  # f32 lane tile on TPU
DEFAULT_REPLICA_BLOCK = 256
# Energy dots run at full f32 precision on the MXU so integer energies stay
# exact; the anneal's dynamics matmul keeps the backend default.
_EXACT = jax.lax.Precision.HIGHEST


def _anneal_loop(j, h, phi, *, steps: int, dt: float, ks_max: float):
    """Shared Euler loop: identical op sequence in the single and batched
    kernels (and kernels/ref.py) so a block-diagonal packed instance
    reproduces the solo math.

    Per-step op budget: the two J matmuls (against cos phi and sin phi) are
    one (2*BR, N) @ (N, N) contraction of the stacked [cos; sin] rows --
    row-independent GEMM, so each half is bitwise the separate product --
    against 2*J (power-of-two scaling commutes exactly with the FP dot), and
    the SHIL term uses sin(2 phi) = 2 sin phi cos phi to reuse the two trig
    evaluations already in registers.  2 trig + 1 matmul per step.
    """
    br = phi.shape[0]
    j2 = j + j  # exact: *2 only bumps exponents

    def step(t, phi):
        s = jnp.sin(phi)
        c = jnp.cos(phi)
        m = jnp.concatenate([c, s], axis=0)  # (2*BR, N)
        mj = jnp.dot(m, j2, preferred_element_type=jnp.float32)  # MXU
        grad = (s * mj[:br] - c * mj[br:]) + h * s
        ks = ks_max * (t.astype(jnp.float32) + 1.0) / steps
        return phi + dt * (grad - ks * (2.0 * (s * c)))

    return jax.lax.fori_loop(0, steps, step, phi)


def _sign_spins(phi):
    """Readout s = sign(cos phi) in {-1, +1} as f32 (same predicate as
    ref.ref_cobi_spins, so fused and two-kernel paths agree bitwise)."""
    return jnp.where(jnp.cos(phi) >= 0.0, 1.0, -1.0)


def _slot_energies(s, j_orig, h_orig, mask, reads, rep_base):
    """Per-slot Ising energies of one replica block, invalid reads -> +inf.

    Per-lane energy density e_i = s_i * (J s)_i + h_i * s_i sums to
    h.s + s^T J s within each block-diagonal slot, so one matmul with the
    0/1 lane->slot ``mask`` yields every slot's energy.  All partial sums
    are integers for chip-range instances, hence f32-exact and bit-identical
    to the standalone ising_energy kernel / einsum oracle.  Both dots ask
    for full f32 precision: a one-pass bf16 MXU product would round lane
    energies above 256.
    """
    sj = jnp.dot(s, j_orig, preferred_element_type=jnp.float32, precision=_EXACT)
    e_lanes = s * sj + h_orig * s  # (BR, N)
    e_slots = jnp.dot(e_lanes, mask, preferred_element_type=jnp.float32,
                      precision=_EXACT)  # (BR, S)
    local = _row_index(e_slots.shape)
    e_slots = jnp.where(local + rep_base < reads, e_slots, jnp.inf)
    return e_slots, local


def _row_index(shape):
    """f32 row index of a 2-D block.  Mosaic builds iotas in int32 only; the
    values are small integers, so the cast is exact."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.float32)


def _block_best(s, e_slots, local):
    """(min energy (S, 1), first-argmin spin rows (S, N)) per slot within one
    replica block.

    Every intermediate stays 2-D: Mosaic cannot lay out the 1-D per-slot
    vectors a plain ``min(axis=0)`` would give.  The (BR, S) one-hot of each
    slot's first minimum is contracted over BR, which picks exactly one
    {-1, +1} row per slot (an exact sum).
    """
    br = e_slots.shape[0]
    blk_min = jnp.min(e_slots, axis=0, keepdims=True)  # (1, S)
    hit = e_slots == blk_min
    first = jnp.min(
        jnp.where(hit, local, jnp.float32(br)), axis=0, keepdims=True
    )  # (1, S)
    onehot = (local == first).astype(jnp.float32)  # (BR, S)
    rows = jax.lax.dot_general(
        onehot, s, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (S, N)
    return blk_min.T, rows


def _carry_best(i, blk_min, rows, e_ref, s_ref):
    """Fold this block's winners into the revisited output block.

    The output BlockSpecs map every replica-block index to the same block, so
    its VMEM contents persist across the innermost grid dimension -- the
    standard Pallas accumulation-by-revisiting pattern.  ``blk_min`` is
    (S, 1); the running best is kept broadcast across the (S, LANE) block.
    """

    @pl.when(i == 0)
    def _():
        e_ref[...] = jnp.broadcast_to(blk_min, e_ref.shape)
        s_ref[...] = rows

    @pl.when(i != 0)
    def _():
        prev = e_ref[:, :1]  # (S, 1)
        better = blk_min < prev  # strict: earlier replica block wins ties
        e_ref[...] = jnp.broadcast_to(jnp.where(better, blk_min, prev), e_ref.shape)
        s_ref[...] = jnp.where(better, rows, s_ref[...])


def _cobi_kernel(j_ref, h_ref, phi_ref, out_ref, *, steps: int, dt: float, ks_max: float):
    j = j_ref[...]  # (N, N) resident across the time loop
    h = h_ref[...]  # (1, N)
    phi = phi_ref[...]  # (BR, N)
    out_ref[...] = _anneal_loop(j, h, phi, steps=steps, dt=dt, ks_max=ks_max)


def _cobi_batched_kernel(
    j_ref, h_ref, phi_ref, out_ref, *, steps: int, dt: float, ks_max: float
):
    j = j_ref[0]  # (N, N) — this instance's couplings, resident across replicas
    h = h_ref[0]  # (1, N)
    phi = phi_ref[0]  # (BR, N)
    out_ref[0] = _anneal_loop(j, h, phi, steps=steps, dt=dt, ks_max=ks_max)


def _cobi_fused_best_kernel(
    j_ref, h_ref, ju_ref, hu_ref, mask_ref, reads_ref, phi_ref,
    e_ref, s_ref, *, steps: int, dt: float, ks_max: float,
):
    """Solo fused kernel: grid (replica_blocks,), anneal ops == _cobi_kernel."""
    i = pl.program_id(0)
    br = phi_ref.shape[0]
    phi = _anneal_loop(
        j_ref[...], h_ref[...], phi_ref[...], steps=steps, dt=dt, ks_max=ks_max
    )
    s = _sign_spins(phi)
    e_slots, local = _slot_energies(
        s, ju_ref[...], hu_ref[...], mask_ref[...], reads_ref[...],
        (i * br).astype(jnp.float32),
    )
    blk_min, rows = _block_best(s, e_slots, local)
    _carry_best(i, blk_min, rows, e_ref, s_ref)


def _cobi_fused_best_batched_kernel(
    j_ref, h_ref, ju_ref, hu_ref, mask_ref, reads_ref, phi_ref,
    e_ref, s_ref, *, steps: int, dt: float, ks_max: float,
):
    """Batched fused kernel: grid (instance, replica_blocks), anneal ops ==
    _cobi_batched_kernel so packed trajectories match the unfused path."""
    i = pl.program_id(1)
    br = phi_ref.shape[1]
    phi = _anneal_loop(
        j_ref[0], h_ref[0], phi_ref[0], steps=steps, dt=dt, ks_max=ks_max
    )
    s = _sign_spins(phi)
    e_slots, local = _slot_energies(
        s, ju_ref[0], hu_ref[0], mask_ref[0], reads_ref[0],
        (i * br).astype(jnp.float32),
    )
    blk_min, rows = _block_best(s, e_slots, local)
    _carry_best(i, blk_min, rows, e_ref.at[0], s_ref.at[0])


def _cobi_readout_kernel(
    j_ref, h_ref, ju_ref, hu_ref, phi_ref, s_ref, e_ref,
    *, steps: int, dt: float, ks_max: float,
):
    """Solo anneal + fused sign/score, keeping every read (for topk/none)."""
    phi = _anneal_loop(
        j_ref[...], h_ref[...], phi_ref[...], steps=steps, dt=dt, ks_max=ks_max
    )
    s = _sign_spins(phi)
    sj = jnp.dot(s, ju_ref[...], preferred_element_type=jnp.float32, precision=_EXACT)
    e = jnp.sum(s * sj, axis=-1, keepdims=True) + jnp.sum(
        s * hu_ref[...], axis=-1, keepdims=True
    )
    s_ref[...] = s
    e_ref[...] = jnp.broadcast_to(e, e_ref.shape)


def cobi_trajectory_pallas(
    j_scaled: Array,  # (N, N) pre-scaled; N padded to LANE multiple by ops.py
    h_scaled: Array,  # (1, N)
    phi0: Array,  # (R, N) with R a multiple of the replica block
    *,
    steps: int,
    dt: float,
    ks_max: float,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> Array:
    r, n = phi0.shape
    assert n % LANE == 0 and n == j_scaled.shape[0] == j_scaled.shape[1]
    assert r % replica_block == 0, (r, replica_block)
    grid = (r // replica_block,)
    kernel = functools.partial(_cobi_kernel, steps=steps, dt=dt, ks_max=ks_max)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # J resident, same for all blocks
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        interpret=interpret,
    )(j_scaled.astype(jnp.float32), h_scaled.astype(jnp.float32), phi0.astype(jnp.float32))


def cobi_trajectory_batched_pallas(
    j_scaled: Array,  # (B, N, N) pre-scaled stack of instance couplings
    h_scaled: Array,  # (B, 1, N)
    phi0: Array,  # (B, R, N) with R a multiple of the replica block
    *,
    steps: int,
    dt: float,
    ks_max: float,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> Array:
    """Anneal a stack of B independent instances in one kernel launch.

    Grid is (instance, replica-block) with the replica dimension innermost, so
    each instance's J/h stay resident in VMEM while its replica blocks stream
    through — the chip-farm analogue of B physical COBI arrays annealing in
    parallel, each programmed once and executed R times.
    """
    b, r, n = phi0.shape
    assert n % LANE == 0 and (b, n, n) == j_scaled.shape, (phi0.shape, j_scaled.shape)
    assert h_scaled.shape == (b, 1, n), h_scaled.shape
    assert r % replica_block == 0, (r, replica_block)
    grid = (b, r // replica_block)
    kernel = functools.partial(_cobi_batched_kernel, steps=steps, dt=dt, ks_max=ks_max)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, n), lambda bi, i: (bi, 0, 0)),  # J resident per instance
            pl.BlockSpec((1, 1, n), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, replica_block, n), lambda bi, i: (bi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, replica_block, n), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, n), jnp.float32),
        interpret=interpret,
    )(j_scaled.astype(jnp.float32), h_scaled.astype(jnp.float32), phi0.astype(jnp.float32))


def cobi_fused_best_pallas(
    j_scaled: Array,  # (N, N) pre-scaled dynamics couplings
    h_scaled: Array,  # (1, N)
    j_orig: Array,  # (N, N) original (scoring) couplings
    h_orig: Array,  # (1, N)
    mask: Array,  # (N, S) 0/1 lane->slot assignment
    reads: Array,  # (1, S) f32 valid-read count per slot
    phi0: Array,  # (R, N) with R a multiple of the replica block
    *,
    steps: int,
    dt: float,
    ks_max: float,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused solo anneal: returns (best energies (S, LANE), best spins (S, N)).

    Energies are broadcast across the LANE dim (slice column 0); spins are the
    f32 {-1,+1} row of the first replica attaining each slot's minimum.
    """
    r, n = phi0.shape
    s_slots = mask.shape[-1]
    assert n % LANE == 0 and r % replica_block == 0, (phi0.shape, replica_block)
    assert mask.shape == (n, s_slots) and reads.shape == (1, s_slots)
    grid = (r // replica_block,)
    kernel = functools.partial(
        _cobi_fused_best_kernel, steps=steps, dt=dt, ks_max=ks_max
    )
    whole = lambda i: (0, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), whole),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((n, n), whole),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((n, s_slots), whole),
            pl.BlockSpec((1, s_slots), whole),
            pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_slots, LANE), whole),  # revisited: carry across blocks
            pl.BlockSpec((s_slots, n), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_slots, LANE), jnp.float32),
            jax.ShapeDtypeStruct((s_slots, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        j_scaled.astype(jnp.float32), h_scaled.astype(jnp.float32),
        j_orig.astype(jnp.float32), h_orig.astype(jnp.float32),
        mask.astype(jnp.float32), reads.astype(jnp.float32),
        phi0.astype(jnp.float32),
    )


def cobi_fused_best_batched_pallas(
    j_scaled: Array,  # (B, N, N)
    h_scaled: Array,  # (B, 1, N)
    j_orig: Array,  # (B, N, N)
    h_orig: Array,  # (B, 1, N)
    mask: Array,  # (B, N, S)
    reads: Array,  # (B, 1, S)
    phi0: Array,  # (B, R, N)
    *,
    steps: int,
    dt: float,
    ks_max: float,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused batched anneal over B (possibly packed) instances.

    Returns (best energies (B, S, LANE), best spins (B, S, N)) -- the farm
    drain's entire device output: O(S*N) per super-instance instead of the
    (B, R, N) phases + (B, R, N) spins round-trips of the two-kernel path.
    """
    b, r, n = phi0.shape
    s_slots = mask.shape[-1]
    assert n % LANE == 0 and r % replica_block == 0, (phi0.shape, replica_block)
    assert j_scaled.shape == j_orig.shape == (b, n, n)
    assert h_scaled.shape == h_orig.shape == (b, 1, n)
    assert mask.shape == (b, n, s_slots) and reads.shape == (b, 1, s_slots)
    grid = (b, r // replica_block)
    kernel = functools.partial(
        _cobi_fused_best_batched_kernel, steps=steps, dt=dt, ks_max=ks_max
    )
    per_inst = lambda bi, i: (bi, 0, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, n), per_inst),
            pl.BlockSpec((1, 1, n), per_inst),
            pl.BlockSpec((1, n, n), per_inst),
            pl.BlockSpec((1, 1, n), per_inst),
            pl.BlockSpec((1, n, s_slots), per_inst),
            pl.BlockSpec((1, 1, s_slots), per_inst),
            pl.BlockSpec((1, replica_block, n), lambda bi, i: (bi, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s_slots, LANE), per_inst),  # revisited across i
            pl.BlockSpec((1, s_slots, n), per_inst),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_slots, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, s_slots, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        j_scaled.astype(jnp.float32), h_scaled.astype(jnp.float32),
        j_orig.astype(jnp.float32), h_orig.astype(jnp.float32),
        mask.astype(jnp.float32), reads.astype(jnp.float32),
        phi0.astype(jnp.float32),
    )


def cobi_readout_pallas(
    j_scaled: Array,  # (N, N)
    h_scaled: Array,  # (1, N)
    j_orig: Array,  # (N, N)
    h_orig: Array,  # (1, N)
    phi0: Array,  # (R, N)
    *,
    steps: int,
    dt: float,
    ks_max: float,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Anneal + fused sign/score keeping all reads: (spins (R, N) f32,
    energies (R, LANE) broadcast).  One launch; phases never reach HBM."""
    r, n = phi0.shape
    assert n % LANE == 0 and r % replica_block == 0, (phi0.shape, replica_block)
    grid = (r // replica_block,)
    kernel = functools.partial(_cobi_readout_kernel, steps=steps, dt=dt, ks_max=ks_max)
    whole = lambda i: (0, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), whole),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((n, n), whole),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
            pl.BlockSpec((replica_block, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, n), jnp.float32),
            jax.ShapeDtypeStruct((r, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(
        j_scaled.astype(jnp.float32), h_scaled.astype(jnp.float32),
        j_orig.astype(jnp.float32), h_orig.astype(jnp.float32),
        phi0.astype(jnp.float32),
    )
