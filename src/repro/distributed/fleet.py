"""shard_map-explicit distributed Ising solving (complement to the GSPMD path).

launch/steps.make_ising_solve_step lets GSPMD partition the fleet solve; this
module is the explicit-collectives twin built on jax.shard_map: each device
anneals its own (docs x replicas) shard and the best-energy/selection
reduction crosses the mesh with hand-placed collectives:

  * replicas axis ('model'):  argmin via psum-of-masked (all-reduce);
  * docs axis ('data','pod'): no communication (embarrassingly parallel).

Explicit placement matters at 1000+ nodes: the reduction is two scalars per
doc (energy + index), so the collective payload is bytes, not tensors, and
the schedule is visible in the lowered HLO rather than left to the
partitioner.  Also the natural home for cross-pod gradient/energy
compression experiments.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref as kref

Array = jax.Array


def make_fleet_solver(mesh: Mesh, *, steps: int = 500, dt: float = 0.35,
                      ks_max: float = 1.2):
    """Returns solve(h, j, phi0) -> (best_spins, best_energy) per doc.

    h: (D, N), j: (D, N, N), phi0: (D, R, N); D shards over data axes,
    R over 'model'.
    """
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def local_anneal(h, j, phi0):
        # Shapes here are the PER-DEVICE shards.
        def one_doc(h_d, j_d, phi_d):
            phi = kref.ref_cobi_trajectory(
                j_d, h_d, phi_d, steps=steps, dt=dt, ks_max=ks_max
            )
            spins = jnp.where(jnp.cos(phi) >= 0.0, 1.0, -1.0)
            e = kref.ref_ising_energy(spins, h_d, j_d)
            i = jnp.argmin(e)
            return spins[i], e[i]

        spins, energy = jax.vmap(one_doc)(h, j, phi0)  # local best per doc

        # Cross-replica-shard reduction over 'model': find the global best
        # energy, then select that shard's spins with a masked psum -- two
        # small collectives instead of gathering every replica.
        best_e = jax.lax.pmin(energy, axis_name="model")
        am_best = (energy == best_e).astype(spins.dtype)
        # Break ties deterministically: only the lowest-index winner sends.
        idx = jax.lax.axis_index("model").astype(jnp.float32)
        winner = jax.lax.pmin(
            jnp.where(am_best > 0, idx, jnp.inf)[None], axis_name="model"
        )[0]
        send = (idx == winner).astype(spins.dtype)
        best_spins = jax.lax.psum(spins * (am_best * send)[:, None], axis_name="model")
        return best_spins.astype(jnp.int8), best_e

    in_specs = (P(dp, None), P(dp, None, None), P(dp, "model", None))
    out_specs = (P(dp, None), P(dp))
    return jax.shard_map(local_anneal, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def fleet_solve(mesh: Mesh, h: Array, j: Array, key: Array, *,
                replicas_per_device: int = 8, steps: int = 500):
    """Convenience wrapper for a batch of instances on the local mesh."""
    d, n = h.shape
    model = mesh.shape.get("model", 1)
    r = replicas_per_device * model
    phi0 = jax.random.uniform(key, (d, r, n), jnp.float32, 0.0, 2.0 * jnp.pi)
    solver = make_fleet_solver(mesh, steps=steps)
    # dynamics pre-scaling (same convention as kernels/ops.py)
    denom = (
        2.0 * jnp.max(jnp.sum(jnp.abs(j), axis=-1), axis=-1) + jnp.max(jnp.abs(h), axis=-1)
    )
    denom = jnp.maximum(denom, 1e-9)[:, None]
    h_s = h / denom
    j_s = j / denom[..., None]
    spins, energies = solver(h_s, j_s, phi0)
    # H is linear in (h, J): undo the dynamics pre-scaling on the energies.
    return spins, energies * denom[:, 0]
