"""Pallas TPU kernels for C3-SL's HRR codec (bind+superpose / unbind).

TPU adaptation: instead of the FFT route, the circular convolution is a
tiled Toeplitz-block contraction on the MXU.  The entry for input position
j and output position d is K[(d - j) mod D], so for output tile dt and
input tile jt the T x T block depends only on (dt - jt) mod (D/T): each
key has D/T distinct blocks, precomputed by :func:`toeplitz_blocks` and
selected by the key operand's block index map.

    bind:    S[g, d]      = sum_i sum_j Z[g, i, j] * K_i[(d - j) mod D]
    unbind:  Zhat[g, i, d] = sum_j S[g, j] * K_i[(j - d) mod D]

Unbind reads block (jt - dt) mod (D/T) and contracts with its transpose.
Grid: (G/GT, D/T, D/T) with accumulation over the last (j-tile) grid axis.
Each j-step does R (GT x T) @ (T x T) MXU contractions.  FLOPs match the
paper's Table 2 accounting (D^2 MACs per bound vector).

The row block GT follows from G alone (:func:`_row_block`): the largest
multiple of 8 dividing G, up to ROW_BLOCK = 256.  Each step fetches R key
blocks, so GT rows share that fetch: a serving prefill chunk of 256 rows
per slot group runs a (k, 32, 32) grid at D=4096.  A decode window's
G = slots/R (4 at 16 slots) keeps GT = G.

VMEM per step (T=128, R=4, GT=256, f32, every block double-buffered):
    Z (bind in) / Zhat (unbind out) block 2*256*8*128*4 = 2 MiB (R=4 pads
    to 8 sublanes), key blocks 2*4*128*128*4 = 512 KiB, S block 2*256*128*4
    = 256 KiB  -> ~2.8 MiB, inside the 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_tile(D: int, target: int = 128) -> int:
    """Largest divisor of D that is <= target (MXU-aligned when D % 128 == 0)."""
    t = min(D, target)
    while D % t:
        t -= 1
    return t


# The smallest tile the MXU/VPU lanes amortize: below this the Toeplitz
# grid degrades toward (G, D, D) single-element "contractions" — slower
# than the direct backend and liable to blow the grid-size limit for a
# prime D (tile 1 -> D^2 grid steps).
MIN_TILE = 8


def mxu_alignable(D: int, target: int = 128) -> bool:
    """Whether the Toeplitz tiling has a usable tile for this D: the
    largest divisor <= target must itself be lane-aligned (multiple of
    MIN_TILE).  False for prime/odd D like 4097 (largest divisor 17)."""
    return _pick_tile(D, target) % MIN_TILE == 0


def _check_tile(D: int, T: int):
    if T % MIN_TILE:
        raise ValueError(
            f"D={D} is not MXU-alignable: its largest tile <= 128 is {T}, "
            f"so the Toeplitz-tiled pallas backend would degrade to "
            f"{T}x{T} contractions over a (G, {D // T}, {D // T}) grid — "
            f"slower than backend='direct' and liable to blow the grid "
            f"limit.  Use backend='fft' (O(D log D), any D), or pad D to "
            f"a multiple of {MIN_TILE * MIN_TILE}.")


def toeplitz_blocks(K: jax.Array, T: int) -> jax.Array:
    """K (R, D) -> the (R, D/T, T, T) distinct Toeplitz blocks of the keys.

    ``blocks[i, m, a, b] = K_i[(m*T + b - a) mod D]``: the bind matrix
    entry for input row j and output column d depends only on
    ``(d - j) mod D``, so tile pair (d-tile dt, j-tile jt) reads block
    ``m = (dt - jt) mod (D/T)``, and unbind reads the transpose of block
    ``(jt - dt) mod (D/T)``.  R*D*T values: 8 MiB at D=4096, R=4, T=128."""
    R, D = K.shape
    L = 2 * D
    # circulant rows without a gather: T copies of [K || K] laid end to
    # end and re-cut at row length L - 1 shift by one per row, so
    # rows[i, a, x] = K_i[(x - a) mod D]; x = m*T + b splits the columns.
    tiled = jnp.tile(jnp.concatenate([K, K], axis=-1), (1, T))
    rows = tiled[:, :T * (L - 1)].reshape(R, T, L - 1)[:, :, :D]
    return rows.reshape(R, T, D // T, T).transpose(0, 2, 1, 3)


def _bind_kernel(z_ref, toep_ref, out_ref, *, R: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.zeros(out_ref.shape, jnp.float32)   # (GT, T_d)
    for i in range(R):
        z_i = z_ref[:, i, :].astype(jnp.float32)  # (GT, T_j)
        acc += jnp.dot(z_i, toep_ref[i, 0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    out_ref[...] += acc.astype(out_ref.dtype)


def _unbind_kernel(s_ref, toep_ref, out_ref, *, R: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.float32)            # (GT, T_j)
    for i in range(R):
        # contract s's j axis with the block's row axis: s @ block^T
        acc = jax.lax.dot_general(
            s, toep_ref[i, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (GT, T_d)
        out_ref[:, i, :] += acc.astype(out_ref.dtype)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def interpret_mode() -> bool:
    """True when pallas_call runs the kernels in INTERPRET mode (any
    non-TPU backend): the math is the kernel's, the speed is not."""
    return _interpret()


def execution_mode() -> str:
    """How a ``backend=pallas`` request actually executes here:
    ``"pallas-compiled"`` (real Mosaic kernels) or ``"pallas-interpret"``
    (CPU emulation — honest benchmarks must tag rows with this; see
    benchmarks/bench_roofline.py)."""
    return "pallas-interpret" if _interpret() else "pallas-compiled"


# Most rows a grid step streams through the R key blocks it fetches: the
# fetch and the step's fixed cost are shared by the rows of the step.
ROW_BLOCK = 256


def _row_block(G: int) -> int:
    """Rows per grid step: the largest multiple of 8 that divides G and is
    at most ROW_BLOCK; G itself when G is not a multiple of 8, since the
    (GT, T) row block needs GT % 8 == 0 or GT == G (TPU tiling)."""
    if G % 8:
        return G
    GT = min(G, ROW_BLOCK) // 8 * 8
    while G % GT:
        GT -= 8
    return GT


def _geometry(G: int, D: int, tile: int | None = None):
    """(T, GT, grid) of a call on G rows of width D: T the D tile, GT the
    row block, grid (G/GT, D/T, D/T) with the j-tile axis innermost."""
    T = tile or _pick_tile(D)
    _check_tile(D, T)
    GT = _row_block(G)
    return T, GT, (G // GT, D // T, D // T)


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("tile",))
def bind_superpose_kernel(Z: jax.Array, K: jax.Array, tile: int | None = None) -> jax.Array:
    """Z (G, R, D), keys K (R, D) -> S (G, D).  Requires divisible tiles."""
    G, R, D = Z.shape
    T, GT, grid = _geometry(G, D, tile)
    if K.shape != (R, D):
        raise ValueError(f"keys {K.shape} do not match Z's (R, D) = {(R, D)}")
    nT = D // T
    return pl.pallas_call(
        functools.partial(_bind_kernel, R=R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((GT, R, T), lambda g, dt, jt: (g, 0, jt)),
            pl.BlockSpec((R, 1, T, T),
                         lambda g, dt, jt: (0, (dt - jt + nT) % nT, 0, 0)),
        ],
        out_specs=pl.BlockSpec((GT, T), lambda g, dt, jt: (g, dt)),
        out_shape=jax.ShapeDtypeStruct((G, D), Z.dtype),
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_interpret(),
    )(Z, toeplitz_blocks(K, T))


@functools.partial(jax.jit, static_argnames=("tile",))
def unbind_kernel(S: jax.Array, K: jax.Array, tile: int | None = None) -> jax.Array:
    """S (G, D), keys K (R, D) -> Zhat (G, R, D).  Requires divisible tiles."""
    G, D = S.shape
    R = K.shape[0]
    T, GT, grid = _geometry(G, D, tile)
    if K.shape != (R, D):
        raise ValueError(f"keys {K.shape} do not match S's D = {D}")
    nT = D // T
    return pl.pallas_call(
        functools.partial(_unbind_kernel, R=R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((GT, T), lambda g, dt, jt: (g, jt)),
            pl.BlockSpec((R, 1, T, T),
                         lambda g, dt, jt: (0, (jt - dt + nT) % nT, 0, 0)),
        ],
        out_specs=pl.BlockSpec((GT, R, T), lambda g, dt, jt: (g, 0, dt)),
        out_shape=jax.ShapeDtypeStruct((G, R, D), S.dtype),
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_interpret(),
    )(S, toeplitz_blocks(K, T))
