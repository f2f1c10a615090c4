"""Jit'd wrappers around the Pallas kernels.

``level_update`` consumes the host-precomputed (D, R, C) segmented layout
(built once per plan in ``JaxFactorizer``): normalisation happens as a flat
XLA op (cheap), contributions are gathered on the (D, R) grid, the Pallas
kernel performs the per-destination-column accumulation, and the updated
segments scatter back (segments are disjoint, so the scatter is race-free).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..sparse.layout import pabs, pdiv, pmul
from ..spans import named
from .dense_lu import dense_lu
from .level_update import segmented_accumulate

__all__ = [
    "level_update",
    "level_update_body",
    "level_update_batched",
    "level_update_batched_body",
    "level_update_planar",
    "level_update_planar_body",
    "level_update_planar_batched",
    "level_update_planar_batched_body",
    "dense_lu",
    "spmv",
    "perturb_diags",
    "perturb_diags_batched",
    "perturb_diags_planar",
    "perturb_diags_planar_batched",
    "factor_stats",
    "factor_stats_batched",
    "factor_stats_planar",
    "factor_stats_planar_batched",
    "masked_correction",
]


# The ``*_body`` functions are the un-jitted step implementations: the
# whole-schedule executors (core/factorize.py) inline them inside ONE fused
# jitted program, while the jitted module-level wrappers below remain the
# per-group dispatch path (and keep their donation semantics).

def level_update_body(
    vals,
    norm_idx,
    norm_diag,
    lidx2d,
    uidx2d,
    didx_local,
    col_positions,
    *,
    interpret=None,
):
    """One GLU level via the segmented Pallas kernel.

    vals:          (nnz,) filled value array
    norm_idx/diag: (Pn,)  flat normalisation indices (padded with nnz)
    lidx2d/uidx2d: (D,R)  value indices of each update's L and U operand
    didx_local:    (D,R)  position of each update inside its destination
                          column segment (padded with >= C)
    col_positions: (D,C)  flat value indices of the destination segments
                          (padded with nnz)
    """
    lv = vals.at[norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[norm_idx].set(lv / dv, mode="drop")

    l = vals.at[lidx2d].get(mode="fill", fill_value=0.0)
    u = vals.at[uidx2d].get(mode="fill", fill_value=0.0)
    contribs = -(l * u)
    col_vals = vals.at[col_positions].get(mode="fill", fill_value=0.0)
    out = segmented_accumulate(col_vals, contribs, didx_local, interpret=interpret)
    return vals.at[col_positions].set(out, mode="drop")


level_update = functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0,))(
    level_update_body)


def level_update_batched_body(
    vals,
    norm_idx,
    norm_diag,
    lidx2d,
    uidx2d,
    didx_local,
    col_positions,
    *,
    interpret=None,
):
    """One GLU level for a whole batch of matrices sharing the plan.

    ``vals`` is (B, nnz); the layout arrays are the same as
    :func:`level_update` and shared across the batch.  The batch axis folds
    into the kernel's destination-column grid axis — contributions become
    (B*D, R) and segments (B*D, C) — so the whole batch is ONE kernel
    launch with grid (B*D, C//CB), not B launches.
    """
    B = vals.shape[0]
    D, R = lidx2d.shape
    C = col_positions.shape[1]
    lv = vals.at[:, norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[:, norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[:, norm_idx].set(lv / dv, mode="drop")

    l = vals.at[:, lidx2d].get(mode="fill", fill_value=0.0)       # (B, D, R)
    u = vals.at[:, uidx2d].get(mode="fill", fill_value=0.0)
    contribs = (-(l * u)).reshape(B * D, R)
    col_vals = vals.at[:, col_positions].get(mode="fill", fill_value=0.0)
    dl = jnp.broadcast_to(didx_local, (B, D, R)).reshape(B * D, R)
    out = segmented_accumulate(col_vals.reshape(B * D, C), contribs, dl,
                               interpret=interpret)
    return vals.at[:, col_positions].set(out.reshape(B, D, C), mode="drop")


level_update_batched = functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0,))(
    level_update_batched_body)


# -- planar complex twins ----------------------------------------------------
#
# ``vals`` carries split re/im planes in a trailing axis: (nnz, 2) single,
# (B, nnz, 2) batched.  Row gathers make the index machinery identical to
# the native path; the only new move is folding the PLANE axis into the
# Pallas kernel's destination-column grid axis — exactly like the batch
# fold above — so the dtype-generic real ``segmented_accumulate`` kernel
# runs complex levels unchanged: contributions become (2*D, R) [(B*2*D, R)
# batched] and segments (2*D, C).  Real and imaginary accumulations are
# independent (the complex cross terms live in ``pmul``, applied BEFORE the
# scatter), so per-plane segmented accumulation is exact.

def level_update_planar_body(
    vals,
    norm_idx,
    norm_diag,
    lidx2d,
    uidx2d,
    didx_local,
    col_positions,
    *,
    interpret=None,
):
    """Planar twin of :func:`level_update_body`: ``vals`` is (nnz, 2)."""
    D, R = lidx2d.shape
    C = col_positions.shape[1]
    lv = vals.at[norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[norm_idx].set(pdiv(lv, dv), mode="drop")

    l = vals.at[lidx2d].get(mode="fill", fill_value=0.0)      # (D, R, 2)
    u = vals.at[uidx2d].get(mode="fill", fill_value=0.0)
    contribs = jnp.moveaxis(-pmul(l, u), -1, 0).reshape(2 * D, R)
    col_vals = vals.at[col_positions].get(mode="fill", fill_value=0.0)
    cv = jnp.moveaxis(col_vals, -1, 0).reshape(2 * D, C)
    dl = jnp.broadcast_to(didx_local, (2, D, R)).reshape(2 * D, R)
    out = segmented_accumulate(cv, contribs, dl, interpret=interpret)
    out = jnp.moveaxis(out.reshape(2, D, C), 0, -1)           # (D, C, 2)
    return vals.at[col_positions].set(out, mode="drop")


level_update_planar = functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0,))(
    level_update_planar_body)


def level_update_planar_batched_body(
    vals,
    norm_idx,
    norm_diag,
    lidx2d,
    uidx2d,
    didx_local,
    col_positions,
    *,
    interpret=None,
):
    """Planar batched twin: ``vals`` is (B, nnz, 2); batch AND plane axes
    fold into the kernel grid — ONE launch with grid (B*2*D, C//CB)."""
    B = vals.shape[0]
    D, R = lidx2d.shape
    C = col_positions.shape[1]
    lv = vals.at[:, norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[:, norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[:, norm_idx].set(pdiv(lv, dv), mode="drop")

    l = vals.at[:, lidx2d].get(mode="fill", fill_value=0.0)   # (B, D, R, 2)
    u = vals.at[:, uidx2d].get(mode="fill", fill_value=0.0)
    contribs = jnp.moveaxis(-pmul(l, u), -1, 1).reshape(B * 2 * D, R)
    col_vals = vals.at[:, col_positions].get(mode="fill", fill_value=0.0)
    cv = jnp.moveaxis(col_vals, -1, 1).reshape(B * 2 * D, C)
    dl = jnp.broadcast_to(didx_local, (B, 2, D, R)).reshape(B * 2 * D, R)
    out = segmented_accumulate(cv, contribs, dl, interpret=interpret)
    out = jnp.moveaxis(out.reshape(B, 2, D, C), 1, -1)        # (B, D, C, 2)
    return vals.at[:, col_positions].set(out, mode="drop")


level_update_planar_batched = functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0,))(
    level_update_planar_batched_body)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def spmv(row_ids, colidx, a_vals, x, *, n_rows: int):
    """CSR-ish SpMV: y[row_ids] += a_vals * x[colidx] (segment-sum form)."""
    prods = a_vals * x[colidx]
    return jax.ops.segment_sum(prods, row_ids, num_segments=n_rows)


# --------------------------------------------------------------------------
# Numerical-robustness primitives (diagnostics + static pivoting)
# --------------------------------------------------------------------------

def _perturb_diags_body(vals, diag_idx, tau):
    """Static pivot perturbation (SuperLU_DIST-style): any diagonal with
    ``|d| < tau`` is replaced by ``tau * d/|d|`` — magnitude tau, phase
    preserved (for real values that is ``sign(d) * tau``; exact zeros bump
    to ``+tau``) — instead of poisoning the factors with inf/NaN.
    ``diag_idx`` is padded with ``nnz`` (one past the value array); padded
    slots are masked out explicitly so they contribute neither bumps nor
    counts whatever tau is."""
    valid = diag_idx < vals.shape[-1]
    d = vals.at[diag_idx].get(mode="fill", fill_value=1.0)
    mag = jnp.abs(d)
    tiny = (mag < tau) & valid
    phase = jnp.where(mag > 0, d / jnp.where(mag > 0, mag, 1.0), 1.0)
    bumped = jnp.where(tiny, (phase * tau).astype(vals.dtype), d)
    vals = vals.at[diag_idx].set(bumped, mode="drop")
    return vals, jnp.sum(tiny, dtype=jnp.int32)


perturb_diags = functools.partial(jax.jit, donate_argnums=(0,))(
    _perturb_diags_body)
perturb_diags_batched = functools.partial(jax.jit, donate_argnums=(0,))(
    jax.vmap(_perturb_diags_body, in_axes=(0, None, 0)))


def _perturb_diags_planar_body(vals, diag_idx, tau):
    """Planar twin of :func:`_perturb_diags_body`: ``vals`` is (nnz, 2),
    ``tau`` a REAL threshold.  Same bump rule on planes — magnitude via
    hypot, phase per plane (re/|d|, im/|d|; exact zeros bump to (+tau, 0)),
    so a planar factorization perturbs exactly where the native one does."""
    valid = diag_idx < vals.shape[-2]
    d = vals.at[diag_idx].get(mode="fill", fill_value=1.0)     # (P, 2)
    dr, di = d[..., 0], d[..., 1]
    mag = jnp.hypot(dr, di)
    tiny = (mag < tau) & valid
    safe = jnp.where(mag > 0, mag, 1.0)
    phr = jnp.where(mag > 0, dr / safe, 1.0)
    phi = jnp.where(mag > 0, di / safe, 0.0)
    bumped = jnp.stack([phr * tau, phi * tau], axis=-1).astype(vals.dtype)
    out = jnp.where(tiny[..., None], bumped, d)
    vals = vals.at[diag_idx].set(out, mode="drop")
    return vals, jnp.sum(tiny, dtype=jnp.int32)


perturb_diags_planar = functools.partial(jax.jit, donate_argnums=(0,))(
    _perturb_diags_planar_body)
perturb_diags_planar_batched = functools.partial(jax.jit, donate_argnums=(0,))(
    jax.vmap(_perturb_diags_planar_body, in_axes=(0, None, 0)))


def _factor_stats_body(vals, diag_idx, a_max):
    """One fused reduction pass over the factored values: element pivot
    growth ``max|LU| / max|A|`` and the smallest post-factorization
    diagonal magnitude (the two no-pivot health numbers)."""
    d = jnp.abs(vals[diag_idx])
    growth = jnp.max(jnp.abs(vals)) / jnp.maximum(a_max, jnp.finfo(vals.dtype).tiny)
    return growth, jnp.min(d)


# every factor_stats* variant runs as the program ``glu_factor_stats``
factor_stats = jax.jit(named("glu_factor_stats", _factor_stats_body))
factor_stats_batched = jax.jit(named(
    "glu_factor_stats", jax.vmap(_factor_stats_body, in_axes=(0, None, 0))))


def _factor_stats_planar_body(vals, diag_idx, a_max):
    """Planar twin of :func:`_factor_stats_body`: ``vals`` is (nnz, 2)."""
    mag = pabs(vals)
    d = mag[diag_idx]
    growth = jnp.max(mag) / jnp.maximum(a_max, jnp.finfo(mag.dtype).tiny)
    return growth, jnp.min(d)


factor_stats_planar = jax.jit(named("glu_factor_stats",
                                   _factor_stats_planar_body))
factor_stats_planar_batched = jax.jit(named(
    "glu_factor_stats",
    jax.vmap(_factor_stats_planar_body, in_axes=(0, None, 0))))


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(named, "glu_correct")
def masked_correction(x, d, berr, tol):
    """``x + d`` where the solve is still above tolerance, ``x`` unchanged
    where it has converged — the device-side convergence mask that lets
    iterative refinement issue several sweeps without a host sync per
    sweep.  ``berr`` is a scalar (single solve) or a (B,)/(K,) vector
    (batched / many-rhs), broadcast across the trailing axes of ``x``."""
    mask = berr > tol
    mask = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
    return x + jnp.where(mask, d, jnp.zeros((), dtype=x.dtype))
