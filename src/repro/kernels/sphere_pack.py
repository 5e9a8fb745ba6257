"""Fused sphere-pack Pallas kernels for the plane-wave hot path.

The Hamiltonian hot chain ``pack(F(v_eff · F⁻¹(unpack(c))))`` pays two full
``(B, d, d, d)`` bounding-cube materializations per sweep: ``unpack``
writes packed CSR coefficients into a freshly-zeroed cube that the first
line-DFT stage immediately re-reads, and ``pack`` gathers npacked lanes back
out of a cube the last stage just wrote.  The CMU flexible-DFT framework
(1904.10119) argues the data-layout permutation should be fused *into* the
line-transform GEMM; these two kernels realize that in Pallas:

``unpack_dft``
    reads packed CSR lanes directly and applies the first rectangular
    (d→n, pad-fused) line-DFT stage per bounding-box line, writing the
    first-stage slab ``(B, ex, ey, n)`` without materializing the cube.
    The grid walks x-planes; a per-plane support flag lets planes whose
    lines are all outside the sphere cross-section skip the gather *and*
    the GEMM and write zeros straight from the accumulator.

``dft_pack``
    fuses the final truncating (n→d) line-DFT stage with the CSR gather
    back to ``(B, npacked)``.  Padded lanes of a ragged stacked batch are
    never written and come out exact zeros — the PR 4 validity contract
    (padded lanes come out +0.0 whatever the slab holds) holds bitwise.

Both kernels use the same split re/im four-GEMM formulation as
``dft_matmul._kernel`` (one ``dot_general`` per product, f32 accumulation at
the line-DFT precision, contraction over the full line) so on CPU
``interpret=True`` they are *bitwise* equal to the XLA matmul route — the
correctness oracle the stacked-vs-per-k harness gates.

Index tables are static numpy built at plan time (:func:`line_tables`),
CSR-by-xy per ``SphereDomain.pack_indices``: packed lanes of one (x, y) line
are contiguous with z ascending, so a line is ``(start, z_lo, cnt)`` and the
line's value at z is packed lane ``start + (z − z_lo)``.

Mosaic lowers a gather only within one vreg, so the kernels move lines with
aligned window copies instead: a line's lanes sit inside the
``_window(d)``-wide, 128-aligned window of the lane-padded packed row that
starts at ``floor128(off)`` (``off = start − z_lo + _PAD``), and one lane
rotation by ``off mod 128`` lines them up with z.  ``unpack_dft`` masks the
rotated window to ``[z_lo, z_lo + cnt)``; ``dft_pack`` rotates the
transformed line the other way and merges it into the resident output row
under the same mask.  The packed rows stay whole in VMEM, so the widths
these kernels take are bounded by :func:`vmem_bytes`, the model preflight
FFTB118 applies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.metrics import global_metrics

from .dft_matmul import dot_t, resolve_interpret

#: process-wide fused-kernel dispatch counts (python dispatch level: under
#: jit each traced call site counts once, like ``FftPlan.executions``) —
#: lets the bench gate assert the pallas route actually ran.
DISPATCHES = {"unpack_dft": 0, "dft_pack": 0}

global_metrics().register_probe("sphere_pack", lambda: dict(DISPATCHES))

_LANE = 128
#: zero lanes in front of every packed row: a line whose z_lo exceeds its
#: CSR start still has a non-negative window offset
_PAD = _LANE
#: scoped VMEM the kernels are compiled under (Mosaic's default on v5e)
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _reset_dispatches():          # test helper
    for k in DISPATCHES:
        DISPATCHES[k] = 0


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _window(d: int) -> int:
    """Lanes of the aligned window that holds any line of length ≤ d."""
    return _round_up(d, _LANE) + _LANE


def _padded_lanes(npacked: int, d: int) -> int:
    """Width of a packed row once padded for the window copies."""
    return _round_up(_PAD + npacked + _window(d), _LANE)


# --------------------------------------------------------------- tables
def line_tables(spheres, nbands: int):
    """Static per-row line tables for the fused kernels.

    For every sphere k and bounding-box line l = x·ey + y:
    ``start[k, l]`` — CSR lane of the line's first packed coefficient,
    ``zlo[k, l]`` — its z offset inside the box, ``cnt[k, l]`` — the line's
    packed length (0 outside the sphere's xy projection).  Tables are
    row-expanded to the stacked batch (row b belongs to sphere b // nbands)
    so the kernel needs no second indirection.  ``flag[x]`` is 1 iff *any*
    sphere has support in x-plane x — the kernels' zero-skip predicate must
    be conservative across the whole stacked batch.

    Returns ``(start, zlo, cnt, flag)``: three ``(len(spheres)·nbands, ex·ey)``
    int32 tables and an ``(ex,)`` int32 flag vector.
    """
    spheres = list(spheres)
    if not spheres:
        raise ValueError("line_tables needs at least one sphere")
    ex, ey, ez = spheres[0].extents
    nlines = ex * ey
    nk = len(spheres)
    start = np.zeros((nk, nlines), np.int32)
    zlo = np.zeros((nk, nlines), np.int32)
    cnt = np.zeros((nk, nlines), np.int32)
    flag = np.zeros((ex,), np.int32)
    for k, s in enumerate(spheres):
        if s.extents != (ex, ey, ez):
            raise ValueError(f"sphere batch must share one bounding box; "
                             f"got {s.extents} vs {(ex, ey, ez)}")
        # one CSR column per non-empty (x, y) line, z contiguous inside it
        # (the order ``pack_indices`` lays the lanes out in)
        off, (xl, yl, zl) = s.offsets, s.lower
        x = off["col_x"] - xl
        lines = x * ey + (off["col_y"] - yl)
        start[k, lines] = off["row_ptr"][:-1]
        zlo[k, lines] = off["z_lo"] - zl
        cnt[k, lines] = off["z_hi"] - off["z_lo"]
        flag[x] = 1
    rep = functools.partial(np.repeat, repeats=nbands, axis=0)
    return rep(start), rep(zlo), rep(cnt), flag


def _plane_tables(start, zlo, cnt, ex: int):
    """Per-plane SMEM tables ``(ex, B, ey)``: window offset, z_lo, z_hi."""
    B, nlines = start.shape
    ey = nlines // ex

    def planes(t):
        return jnp.transpose(t.reshape(B, ex, ey), (1, 0, 2))
    return planes(start - zlo + _PAD), planes(zlo), planes(zlo + cnt)


# ----------------------------------------------------------- VMEM model
def _f32_tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of an f32 (rows, cols) tile laid out in (8, 128) vregs."""
    return 4 * _round_up(rows, 8) * _round_up(cols, _LANE)


def vmem_bytes(B: int, npacked: int, ey: int, n: int, d: int) -> int:
    """Upper bound on the VMEM either kernel needs for one call.

    ``B`` rows of ``npacked`` lanes, ``ey`` lines per x-plane, lines of
    ``n`` (slab side) and ``d`` (sphere side) points.  Counts the resident
    re/im packed rows, the double-buffered DFT planes and ``(B, ey, n)``
    slab blocks, the line scratch, and ten ``(B·ey, max(n, d))`` f32
    arrays for the GEMM operands and products Mosaic stages — the count
    that bounds the scoped VMEM the v5e compiler asked for at
    d ∈ {16, 32, 64, 128} (``tests/test_tpu_compile.py`` holds the
    two to agree).  Preflight FFTB118 refuses a width past
    ``VMEM_LIMIT_BYTES``.
    """
    rows = B * ey
    wide = max(_round_up(n, _LANE), _round_up(d, _LANE))
    return (2 * _f32_tile_bytes(B, _padded_lanes(npacked, d))
            + 2 * 2 * max(_f32_tile_bytes(n, d), _f32_tile_bytes(d, n))
            + 2 * 2 * B * _f32_tile_bytes(ey, n)
            + 2 * _f32_tile_bytes(rows, d)
            + 10 * 4 * _round_up(rows, 8) * wide)


def _compiler_params(semantics: str):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _line_window(off_ref, lo_ref, hi_ref, r, ey: int):
    """Row ``b``, aligned window start, rotation and z bounds of line r."""
    b = r // ey
    y = r % ey
    off = off_ref[0, b, y]
    a = pl.multiple_of((off // _LANE) * _LANE, _LANE)
    return b, a, off - a, lo_ref[0, b, y], hi_ref[0, b, y]


# -------------------------------------------------------------- kernels
def _unpack_dft_kernel(flag_ref, off_ref, lo_ref, hi_ref, pr_ref, pi_ref,
                       wr_ref, wi_ref, yr_ref, yi_ref, xr_s, xi_s):
    """One x-plane: gather its ey packed lines, apply the d→n line DFT."""
    B, _, ey, n = yr_ref.shape
    d = wr_ref.shape[1]
    width = _window(d)
    live = flag_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(live))
    def _skip():
        # no sphere support anywhere in this plane: the oracle's GEMM over
        # all-zero lines yields exact +0.0 — write it without the FLOPs
        yr_ref[...] = jnp.zeros(yr_ref.shape, yr_ref.dtype)
        yi_ref[...] = jnp.zeros(yi_ref.shape, yi_ref.dtype)

    @pl.when(live)
    def _compute():
        z = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

        def gather(r, carry):
            b, a, rot, lo, hi = _line_window(off_ref, lo_ref, hi_ref, r, ey)
            keep = (z >= lo) & (z < hi)
            shift = (width - rot) % width        # rolled[z] = window[z + rot]
            for src, dst in ((pr_ref, xr_s), (pi_ref, xi_s)):
                win = pltpu.roll(src[pl.ds(b, 1), pl.ds(a, width)], shift, 1)
                dst[pl.ds(r, 1), :] = jnp.where(keep, win, 0.0)[:, :d]
            return carry

        jax.lax.fori_loop(0, B * ey, gather, 0)
        xr = xr_s[...]
        xi = xi_s[...]
        wr = wr_ref[...]
        wi = wi_ref[...]
        yr = dot_t(xr, wr) - dot_t(xi, wi)
        yi = dot_t(xr, wi) + dot_t(xi, wr)
        yr_ref[...] = yr.reshape(B, 1, ey, n).astype(yr_ref.dtype)
        yi_ref[...] = yi.reshape(B, 1, ey, n).astype(yi_ref.dtype)


def _dft_pack_kernel(flag_ref, off_ref, lo_ref, hi_ref, xr_ref, xi_ref,
                     wr_ref, wi_ref, pr_ref, pi_ref, yr_s, yi_s):
    """One x-plane: truncating n→d line DFT, then merge its lines into the
    resident packed rows."""
    B, _, ey, n = xr_ref.shape
    d = wr_ref.shape[0]
    dp = yr_s.shape[1]
    width = _window(d)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # lanes no line writes (ragged padding, the window margins) stay
        # exact +0.0, and so do the line scratch's lanes past d
        for ref in (pr_ref, pi_ref, yr_s, yi_s):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    @pl.when(flag_ref[pl.program_id(0)] != 0)
    def _compute():
        xr = xr_ref[...].reshape(B * ey, n)
        xi = xi_ref[...].reshape(B * ey, n)
        wr = wr_ref[...]
        wi = wi_ref[...]
        yr_s[:, :d] = dot_t(xr, wr) - dot_t(xi, wi)
        yi_s[:, :d] = dot_t(xr, wi) + dot_t(xi, wr)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        tail = jnp.zeros((1, width - dp), jnp.float32)

        def scatter(r, carry):
            b, a, rot, lo, hi = _line_window(off_ref, lo_ref, hi_ref, r, ey)
            keep = (j >= rot + lo) & (j < rot + hi)
            for src, dst in ((yr_s, pr_ref), (yi_s, pi_ref)):
                line = jnp.concatenate([src[pl.ds(r, 1), :], tail], axis=1)
                win = pltpu.roll(line, rot, 1)   # win[rot + z] = line[z]
                cur = dst[pl.ds(b, 1), pl.ds(a, width)]
                dst[pl.ds(b, 1), pl.ds(a, width)] = jnp.where(keep, win, cur)
            return carry

        jax.lax.fori_loop(0, B * ey, scatter, 0)


# ------------------------------------------------------------- wrappers
def _smem_plane_spec(B: int, ey: int):
    return pl.BlockSpec((1, B, ey), lambda i, flag: (i, 0, 0),
                        memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _unpack_dft(pr, pi, start, zlo, cnt, flag, wr, wi, *, interpret: bool):
    B, npk = pr.shape
    ex = flag.shape[0]
    ey = start.shape[1] // ex
    n, d = wr.shape
    lanes = _padded_lanes(npk, d)
    pad = ((0, 0), (_PAD, lanes - _PAD - npk))
    pr = jnp.pad(pr.astype(jnp.float32), pad)
    pi = jnp.pad(pi.astype(jnp.float32), pad)
    t_spec = _smem_plane_spec(B, ey)
    p_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((n, d), lambda i, flag: (0, 0))
    y_spec = pl.BlockSpec((B, 1, ey, n), lambda i, flag: (0, i, 0, 0))
    return pl.pallas_call(
        _unpack_dft_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(ex,),
            in_specs=[t_spec, t_spec, t_spec, p_spec, p_spec, w_spec,
                      w_spec],
            out_specs=[y_spec, y_spec],
            scratch_shapes=[pltpu.VMEM((B * ey, d), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((B, ex, ey, n), jnp.float32)] * 2,
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
    )(flag, *_plane_tables(start, zlo, cnt, ex), pr, pi, wr, wi)


def unpack_dft(pr, pi, start, zlo, cnt, flag, wr, wi, *,
               interpret: bool | None = None):
    """Fused CSR-unpack + first-stage line DFT.

    ``pr``/``pi``: (B, npacked) packed f32 planes; ``start``/``zlo``/``cnt``:
    (B, ex·ey) per-row line tables; ``flag``: (ex,) plane-support vector;
    ``wr``/``wi``: (n, d) rectangular DFT factor.  Returns the first-stage
    slab as (B, ex, ey, n) f32 re/im planes — the zero-padded cube is never
    materialized.  ``interpret=None`` decides from the platform at call
    time (:func:`~repro.kernels.dft_matmul.resolve_interpret`).
    """
    return _unpack_dft(pr, pi, start, zlo, cnt, flag, wr, wi,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("npacked", "interpret"))
def _dft_pack(xr, xi, start, zlo, cnt, flag, wr, wi, *, npacked: int,
              interpret: bool):
    B, ex, ey, n = xr.shape
    d = wr.shape[0]
    lanes = _padded_lanes(npacked, d)
    t_spec = _smem_plane_spec(B, ey)
    x_spec = pl.BlockSpec((B, 1, ey, n), lambda i, flag: (0, i, 0, 0))
    w_spec = pl.BlockSpec((d, n), lambda i, flag: (0, 0))
    p_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    # transformed lines are kept lane-padded to whole vregs, which the
    # window merge extends by one more
    line_s = pltpu.VMEM((B * ey, _round_up(d, _LANE)), jnp.float32)
    pr, pi = pl.pallas_call(
        _dft_pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(ex,),
            in_specs=[t_spec, t_spec, t_spec, x_spec, x_spec, w_spec,
                      w_spec],
            out_specs=[p_spec, p_spec],
            scratch_shapes=[line_s] * 2),
        out_shape=[jax.ShapeDtypeStruct((B, lanes), jnp.float32)] * 2,
        # every plane merges into the same resident output rows
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(flag, *_plane_tables(start, zlo, cnt, ex), xr, xi, wr, wi)
    return pr[:, _PAD:_PAD + npacked], pi[:, _PAD:_PAD + npacked]


def dft_pack(xr, xi, start, zlo, cnt, flag, wr, wi, *, npacked: int,
             interpret: bool | None = None):
    """Fused final truncating line DFT + CSR pack gather.

    ``xr``/``xi``: (B, ex, ey, n) last-stage slab planes;
    ``start``/``zlo``/``cnt``/``flag``: the :func:`line_tables` of the
    slab's lines (rows of a line with ``cnt == 0`` write nothing);
    ``wr``/``wi``: (d, n) truncating DFT factor.  Returns (B, npacked)
    packed f32 planes; lanes no line covers are exact zeros.
    """
    return _dft_pack(xr, xi, start, zlo, cnt, flag, wr, wi,
                     npacked=int(npacked),
                     interpret=resolve_interpret(interpret))
