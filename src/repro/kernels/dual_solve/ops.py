"""jit'd public wrapper around the dual_solve Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import (N_SCALARS, ROWS, S_BLO, S_BTOT, S_ETA, S_IBITS, S_LAM,
                     S_N0, S_SBITS, dual_solve_pallas, dual_solve_pallas_joint)
from .ref import joint_levels

BLOCK = 128            # lanes of one client row


def dual_solve(P: jnp.ndarray, h: jnp.ndarray, u_norms: jnp.ndarray,
               lam: jnp.ndarray, *, gamma_grid: tuple, eta, b_tot, s_bits,
               i_bits, n0, b_lo, newton_iters: int = 3, e_cmp=None,
               e_scale=None, bits_grid=None):
    """Same contract as ``ref.dual_solve_ref``: per-client
    ``(gamma*, b*, e*, phi*)`` at bandwidth price ``lam``. The gamma grid
    and Newton iteration count are static; every other scalar is traced
    (packed into the kernel's scalar-prefetch vector). ``e_cmp`` ([N],
    optional) is the additive per-client computation energy; ``e_scale``
    ([N], optional) the multiplicative outage pricing factor
    (``repro.core.link`` — None keeps the legacy 4-input kernel).
    ``bits_grid`` (static tuple, optional) routes to the joint
    (gamma, bits) kernel pair, which returns a fifth ``bits*`` output;
    ``None`` keeps the legacy gamma-only kernels and the 4-tuple. Pads
    the client axis to whole (8, 128) tiles and truncates the outputs
    back. Runs the kernel compiled on a TPU and interpreted on the CPU."""
    n = P.shape[0]
    if e_cmp is None:
        e_cmp = jnp.zeros((n,), jnp.float32)
    pad = (-n) % (ROWS * BLOCK)
    if pad:
        # padded lanes must stay finite through log/Newton: unit channel,
        # zero score/comp, unit pricing factor (it runs through a log).
        # They are sliced off before anything consumes them.
        one = jnp.ones((pad,), jnp.float32)
        zero = jnp.zeros((pad,), jnp.float32)
        P = jnp.concatenate([P, one])
        h = jnp.concatenate([h, one])
        u_norms = jnp.concatenate([u_norms, zero])
        e_cmp = jnp.concatenate([e_cmp, zero])
        if e_scale is not None:
            e_scale = jnp.concatenate([e_scale.astype(jnp.float32), one])
    sc = jnp.zeros((N_SCALARS,), jnp.float32)
    sc = sc.at[S_LAM].set(lam).at[S_ETA].set(eta).at[S_BTOT].set(b_tot)
    sc = sc.at[S_SBITS].set(s_bits).at[S_IBITS].set(i_bits)
    sc = sc.at[S_N0].set(n0).at[S_BLO].set(b_lo)
    es = None if e_scale is None else e_scale.astype(jnp.float32)
    args = (P.astype(jnp.float32), h.astype(jnp.float32),
            u_norms.astype(jnp.float32), e_cmp.astype(jnp.float32), sc, es)
    if bits_grid is None:
        gam, b, e, phi = dual_solve_pallas(
            *args, gamma_grid=tuple(gamma_grid), newton_iters=newton_iters,
            block=BLOCK, interpret=interpret_mode())
        return gam[:n], b[:n], e[:n], phi[:n]
    gam, b, e, phi, bits = dual_solve_pallas_joint(
        *args, levels=joint_levels(gamma_grid, bits_grid),
        newton_iters=newton_iters, block=BLOCK, interpret=interpret_mode())
    return gam[:n], b[:n], e[:n], phi[:n], bits[:n]
