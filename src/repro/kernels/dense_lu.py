"""Pallas TPU kernel: blocked unpivoted dense LU for the trailing submatrix.

Beyond-paper optimization (switch-to-dense): near the end of factorization
the trailing submatrix of circuit matrices becomes dense-ish (the paper's
type C levels).  Instead of long chains of tiny sparse levels, we gather the
trailing block into a dense tile and finish with a blocked right-looking LU
whose rank-B updates run on the MXU.

Layout: in-place LU, L strictly below the diagonal (unit diagonal implied),
U on/above.  No pivoting — the GLU flow guarantees numerically safe pivots
via MC64 + diagonal dominance, same assumption as the paper.

The tile lives in the output ref in VMEM and every step reads and writes it
through static, (8, 128)-aligned ref slices.  A pivot, a row or a column
selected by the loop index is extracted with a masked reduction, never by
dynamically indexing a loaded vector (which Mosaic cannot lower).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["dense_lu", "dense_lu_planar", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 128

_dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)


def _fori(n, body):
    """``fori_loop`` over int32 indices: the index feeds Mosaic's i32 index
    arithmetic even when JAX runs with 64-bit mode on."""
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, jnp.int32(0))


def _pick(mask, x, axis):
    """The one entry of ``x`` that ``mask`` selects along ``axis``."""
    return jnp.sum(jnp.where(mask, x, 0.0), axis=axis, keepdims=True)


def _vmem_params(n_tiles: int, N: int, dtype):
    """Scoped-VMEM budget: input + output tiles plus temporaries of the same
    order (the trailing product, panel and row views).  The default 16 MiB
    scope is too small from N=768 up (f32 needs about 7 tiles there); v5e
    has 128 MiB of VMEM."""
    tile = N * N * jnp.dtype(dtype).itemsize
    need = 8 * n_tiles * tile + (4 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need, 16 << 20), 100 << 20)))


def _panel_factor(o_ref, k0, B, N):
    """Factor the B-wide panel [k0:, k0:k0+B] in place, one column a step."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (N - k0, 1), 0) + k0
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def col_step(jj, carry):
        j = k0 + jj
        p = o_ref[k0:, k0:k0 + B]
        prow = _pick(rows == j, p, 0)                       # (1, B)
        piv = _pick(cols == jj, prow, 1)                    # (1, 1)
        col = _pick(cols == jj, p, 1)                       # (H, 1)
        below = rows > j
        lcol = jnp.where(below, col / piv, col)
        # rank-1 update restricted to the remaining panel columns
        lm = jnp.where(below, lcol, 0.0)
        um = jnp.where(cols > jj, prow, 0.0)
        o_ref[k0:, k0:k0 + B] = jnp.where(cols == jj, lcol, p) - lm * um
        return carry

    _fori(B, col_step)


def _trsm_rows(o_ref, k0, B, N):
    """Rows k0:k0+B of the trailing columns: U12 = L11^{-1} A12 (unit lower),
    column-oriented forward substitution down the B rows."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    l11 = o_ref[k0:k0 + B, k0:k0 + B]

    def row_step(ii, carry):
        x = o_ref[k0:k0 + B, k0 + B:]
        xrow = _pick(rows == ii, x, 0)                      # final row ii
        lcol = _pick(cols == ii, l11, 1)                    # L11[:, ii]
        o_ref[k0:k0 + B, k0 + B:] = x - jnp.where(rows > ii, lcol, 0.0) * xrow
        return carry

    _fori(B, row_step)


def _lu_kernel(a_ref, o_ref, *, N: int, B: int):
    o_ref[...] = a_ref[...]
    for k0 in range(0, N, B):
        _panel_factor(o_ref, k0, B, N)
        if k0 + B < N:
            _trsm_rows(o_ref, k0, B, N)
            # trailing update A22 -= L21 @ U12 on the MXU
            k1 = k0 + B
            o_ref[k1:, k1:] = o_ref[k1:, k1:] - _dot(o_ref[k1:, k0:k1],
                                                     o_ref[k0:k1, k1:])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dense_lu(a, *, block: int = DEFAULT_BLOCK, interpret=None):
    """In-place-layout unpivoted LU of a dense (N, N) tile."""
    interpret = resolve_interpret(interpret)
    N = a.shape[0]
    B = min(block, N)
    assert N % B == 0, (N, B)
    kernel = functools.partial(_lu_kernel, N=N, B=B)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((N, N), a.dtype),
        compiler_params=_vmem_params(1, N, a.dtype),
        interpret=interpret,
    )(a)


# --------------------------------------------------------------------------
# Planar complex twin: the SAME blocked algorithm on split re/im planes.
# The kernel sees only real operands — complex multiply is 4 real matmuls +
# sign on the MXU, the pivot reciprocal is conj(p) / (re^2 + im^2) — which
# is what lets complex dense tails stay on the Pallas path (TPU kernels take
# no complex operands).
# --------------------------------------------------------------------------

def _panel_factor_planar(o_ref, k0, B, N):
    """Planar twin of :func:`_panel_factor` on the (2, N, N) plane pair."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (N - k0, 1), 0) + k0
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def col_step(jj, carry):
        j = k0 + jj
        pr, pi = o_ref[0, k0:, k0:k0 + B], o_ref[1, k0:, k0:k0 + B]
        rr, ri = _pick(rows == j, pr, 0), _pick(rows == j, pi, 0)
        vr, vi = _pick(cols == jj, rr, 1), _pick(cols == jj, ri, 1)
        inv = 1.0 / (vr * vr + vi * vi)
        cr, ci = _pick(cols == jj, pr, 1), _pick(cols == jj, pi, 1)
        below = rows > j
        lr = jnp.where(below, (cr * vr + ci * vi) * inv, cr)
        li = jnp.where(below, (ci * vr - cr * vi) * inv, ci)
        # rank-1 update restricted to the remaining panel columns
        lmr, lmi = jnp.where(below, lr, 0.0), jnp.where(below, li, 0.0)
        umr, umi = jnp.where(cols > jj, rr, 0.0), jnp.where(cols > jj, ri, 0.0)
        here = cols == jj
        o_ref[0, k0:, k0:k0 + B] = (jnp.where(here, lr, pr)
                                    - (lmr * umr - lmi * umi))
        o_ref[1, k0:, k0:k0 + B] = (jnp.where(here, li, pi)
                                    - (lmr * umi + lmi * umr))
        return carry

    _fori(B, col_step)


def _trsm_rows_planar(o_ref, k0, B, N):
    """Planar twin of :func:`_trsm_rows`."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    l11r = o_ref[0, k0:k0 + B, k0:k0 + B]
    l11i = o_ref[1, k0:k0 + B, k0:k0 + B]

    def row_step(ii, carry):
        xr, xi = o_ref[0, k0:k0 + B, k0 + B:], o_ref[1, k0:k0 + B, k0 + B:]
        tr, ti = _pick(rows == ii, xr, 0), _pick(rows == ii, xi, 0)
        below = rows > ii
        lr = jnp.where(below, _pick(cols == ii, l11r, 1), 0.0)
        li = jnp.where(below, _pick(cols == ii, l11i, 1), 0.0)
        o_ref[0, k0:k0 + B, k0 + B:] = xr - (lr * tr - li * ti)
        o_ref[1, k0:k0 + B, k0 + B:] = xi - (lr * ti + li * tr)
        return carry

    _fori(B, row_step)


def _lu_kernel_planar(a_ref, o_ref, *, N: int, B: int):
    o_ref[...] = a_ref[...]
    for k0 in range(0, N, B):
        _panel_factor_planar(o_ref, k0, B, N)
        if k0 + B < N:
            _trsm_rows_planar(o_ref, k0, B, N)
            # trailing update A22 -= L21 @ U12: 4 real matmuls on the MXU
            k1 = k0 + B
            l21r, l21i = o_ref[0, k1:, k0:k1], o_ref[1, k1:, k0:k1]
            u12r, u12i = o_ref[0, k0:k1, k1:], o_ref[1, k0:k1, k1:]
            o_ref[0, k1:, k1:] = o_ref[0, k1:, k1:] - (
                _dot(l21r, u12r) - _dot(l21i, u12i))
            o_ref[1, k1:, k1:] = o_ref[1, k1:, k1:] - (
                _dot(l21r, u12i) + _dot(l21i, u12r))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dense_lu_planar(a, *, block: int = DEFAULT_BLOCK, interpret=None):
    """Unpivoted LU of a complex (N, N) tile stored as (2, N, N) planes."""
    interpret = resolve_interpret(interpret)
    N = a.shape[-1]
    B = min(block, N)
    assert a.shape == (2, N, N) and N % B == 0, (a.shape, B)
    kernel = functools.partial(_lu_kernel_planar, N=N, B=B)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2, N, N), a.dtype),
        compiler_params=_vmem_params(2, N, a.dtype),
        interpret=interpret,
    )(a)
