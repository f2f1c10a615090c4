"""Pallas TPU kernel for the GLU submatrix (subcolumn) update.

This is the paper's central compute: for every update triple in a level,
``A(i,k) -= A(i,j) * A(j,k)``.  On the GPU this is an atomic MAC scatter; on
TPU we make it collision-free by segmenting updates per *destination column*
(the plan already stores them destination-major) and accumulating inside
VMEM with a one-hot matmul — the MXU performs the scatter-add.

Geometry (chosen at plan time per level — the TPU analogue of the paper's
three adaptive modes):
  D  — destination columns, SUB=8 of them per grid step (one sublane tile;
       the wrapper pads D up to a multiple of SUB)
  R  — padded updates per destination column (multiple of RC=256)
  C  — padded destination column length, split into CB=512 blocks
Every block is then (8, 128·k), which the TPU's (8, 128) tiling rule
accepts.  Type B levels compile with large D / small R,C; type C levels with
small D / large R,C (panel).  Type A levels bypass this kernel entirely
(flat XLA scatter-add is optimal there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

__all__ = ["segmented_accumulate", "RC", "CB", "SUB"]

RC = 256   # contribution chunk (MXU contraction dim)
CB = 512   # destination column block (MXU output dim)
SUB = 8    # destination columns per grid step (f32 sublane tile)

# contribution rows (1, RC) against the (CB, RC) one-hot: contract both
# last dims, the "NT" matmul form the MXU takes directly
_NT = (((1,), (1,)), ((), ()))


def _kernel(cv_ref, cb_ref, dl_ref, out_ref, *, n_rc: int, cb_size: int):
    """One (8 destination columns, column block) cell."""
    base = pl.program_id(1) * cb_size
    dtype = cv_ref.dtype
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (cb_size, RC), 0) + base
    for d in range(SUB):
        def chunk(rc, acc, d=d):
            off = pl.multiple_of(rc * RC, RC)
            dl = dl_ref[pl.ds(d, 1), pl.ds(off, RC)]             # (1, RC)
            contrib = cb_ref[pl.ds(d, 1), pl.ds(off, RC)]        # (1, RC)
            onehot = (dl == col_ids).astype(dtype)               # (CB, RC)
            return acc + jax.lax.dot_general(
                contrib, onehot, _NT, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=dtype)
        # int32 bounds: the loop index feeds Mosaic's i32 index arithmetic
        # even when JAX runs with 64-bit mode on
        acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_rc), chunk,
                                jnp.zeros((1, cb_size), dtype=dtype))
        out_ref[pl.ds(d, 1), :] = cv_ref[pl.ds(d, 1), :] + acc


def _row_block(d, b):
    # an int32 zero: a Python 0 would turn i64 under 64-bit mode, which the
    # Mosaic index map cannot return
    return d, jnp.int32(0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def segmented_accumulate(col_vals, contribs, didx_local, *, interpret=None):
    """col_vals (D,C) += scatter(contribs (D,R) at didx_local (D,R)).

    Padding: contribs 0-padded; didx_local padded with >= C (never matches).
    C must be a multiple of CB or < CB (then one block, a multiple of 128);
    R a multiple of RC.  ``interpret=None`` runs compiled on a TPU and
    interpreted elsewhere (see :mod:`repro.kernels.backend`).
    """
    interpret = resolve_interpret(interpret)
    D, C = col_vals.shape
    _, R = contribs.shape
    cb_size = min(C, CB)
    assert C % cb_size == 0 and R % RC == 0, (C, R)
    pad = -D % SUB
    if pad:
        col_vals = jnp.pad(col_vals, ((0, pad), (0, 0)))
        contribs = jnp.pad(contribs, ((0, pad), (0, 0)))
        didx_local = jnp.pad(didx_local, ((0, pad), (0, 0)),
                             constant_values=C)
    kernel = functools.partial(_kernel, n_rc=R // RC, cb_size=cb_size)
    out = pl.pallas_call(
        kernel,
        grid=((D + pad) // SUB, C // cb_size),
        in_specs=[
            pl.BlockSpec((SUB, cb_size), lambda d, b: (d, b)),
            pl.BlockSpec((SUB, R), _row_block),
            pl.BlockSpec((SUB, R), _row_block),
        ],
        out_specs=pl.BlockSpec((SUB, cb_size), lambda d, b: (d, b)),
        out_shape=jax.ShapeDtypeStruct((D + pad, C), col_vals.dtype),
        interpret=interpret,
    )(col_vals, contribs, didx_local)
    return out[:D] if pad else out
