"""Fused GLM loss+gradient Pallas kernel — one HBM pass over the design matrix.

This is the framework's #1 compute kernel (the reference's
ValueAndGradientAggregator.scala:34-280: one streaming pass accumulating
``sum w*l(z, y)`` and ``X^T (w * dl/dz)``). The stock XLA lowering runs it as
two matmuls — ``z = X @ w`` then ``g = X^T d`` — so the design matrix is read
from HBM twice per optimizer evaluation. On TPU the op is bandwidth-bound for
any realistically large ``N x D`` block, so this kernel tiles X over row blocks
and computes BOTH contractions per block while it is resident in VMEM:

    per block i:  z_i = X_i @ w + offsets_i          (MXU)
                  l_i, dz_i = pointwise loss          (VPU)
                  val  += sum(wgt_i * l_i)            (VPU, masked weights)
                  grad += X_i^T (wgt_i * dz_i)        (MXU)
                  wsum += sum(wgt_i * dz_i)

halving X's HBM traffic and collapsing the elementwise chain into the same
kernel. The TPU grid is sequential, so the VMEM accumulators carry across grid
steps (initialized at block 0) — the standard reduction pattern.

The kernel returns raw sums (loss sum, gradient vector sum, weighted-dz sum);
the caller applies the normalization shift/factor algebra and the L2 term
exactly as GLMObjective does, so the fused path is a drop-in replacement for
any normalization context.

Weight-0 rows are EXCLUDED (masked, not multiplied) to match
GLMObjective._weighted: padding rows and down-sampled rows must stay inert
even when their margins overflow the pointwise loss.

Gating: OFF by default. Enable with ``enable_pallas(True)`` or
``PHOTON_PALLAS=1``. The fused path only engages on the TPU backend for dense
float inputs with D <= MAX_FUSED_DIM[storage dtype] (the whole coefficient
vector and an [BN, D] block must fit VMEM); everything else falls back to the
XLA path.
CPU tests run the same kernel in interpret mode.
"""

from __future__ import annotations

import functools
import contextlib
import os

import jax
import jax.numpy as jnp

Array = jnp.ndarray

# Rows per grid step, and the widest D the value+gradient and HVP kernels are
# admitted at, per storage dtype. The [BLOCK_ROWS, D] X block is double-buffered
# inside the chip's 16 MiB default scoped VMEM next to the [D, 1] operands
# (lane-padded to [D, 128]: 2 MiB each at D=4096) and the in-kernel transposed
# copy. Measured on a v5e (PR 21), all three kernels at every D in {8, 64, 128,
# 256, 512, 1024, 2048, 4096}: bf16 storage compiles through D=4096; f32
# storage (f32-precision contractions, ``_dot``) compiles through D=1024, and
# Mosaic refuses the HVP kernel at D=2048 ("Ran out of memory in memory space
# vmem"). The gate admits only what compiled, so ``should_fuse`` never hands
# Mosaic a shape it rejects.
BLOCK_ROWS = 512
MAX_FUSED_DIM = {"float32": 1024, "bfloat16": 4096}

_enabled: bool | None = None


def enable_pallas(on: bool | None) -> None:
    """Process-wide switch for the fused kernels (overrides PHOTON_PALLAS;
    ``None`` reverts to the environment variable).

    The fuse decision is baked in at trace time, and the solver caches
    (optimization/solver_cache.py) hold traced programs — toggling must drop
    them or already-compiled solvers would keep their old lowering.
    """
    global _enabled
    new = None if on is None else bool(on)
    if new == _enabled:
        return
    _enabled = new
    from photon_ml_tpu.optimization import solver_cache

    solver_cache.clear()


def pallas_enabled() -> bool:
    if _enabled is not None:
        return _enabled
    return os.environ.get("PHOTON_PALLAS", "") not in ("", "0")


def enabled_override() -> bool | None:
    """The current process-wide override (None = deferring to PHOTON_PALLAS).

    Public accessor so callers (e.g. bench sweeps) can save/restore the switch
    without reaching into module internals; pair with :func:`pallas_override`.
    """
    return _enabled


@contextlib.contextmanager
def pallas_override(on: bool | None):
    """Scoped :func:`enable_pallas`: sets the switch, restores the previous
    override (and the solver caches' trace-time fuse decision) on exit."""
    prev = _enabled
    enable_pallas(on)
    try:
        yield
    finally:
        enable_pallas(prev)


def interpret_mode() -> bool:
    """CPU test hook: PHOTON_PALLAS_INTERPRET=1 runs the kernel interpreted,
    letting the integration path be exercised without a TPU. On a TPU backend
    it is an error: an interpreted kernel there would pass for a compiled
    one."""
    on = os.environ.get("PHOTON_PALLAS_INTERPRET", "") not in ("", "0")
    if on and jax.default_backend() == "tpu":
        raise RuntimeError(
            "PHOTON_PALLAS_INTERPRET is set on a TPU backend: the fused "
            "kernels would run interpreted while reporting as compiled; "
            "unset it"
        )
    return on


def should_fuse(n_cols: int, dtype, *, per_device: bool = False) -> bool:
    """True when the fused kernel should replace the two-matmul XLA path.

    Trace-time decision: ``dtype`` is the design matrix's STORAGE dtype (it
    sets the admitted width, MAX_FUSED_DIM); backend is the default backend
    of the process. The kernel is compiled for single-device execution — under a >1-device mesh
    GSPMD cannot partition an opaque pallas_call, so the GSPMD paths keep the
    XLA lowering UNLESS the caller runs inside shard_map (``per_device=True``:
    each device fuses over its own block and the objective psums the sums —
    see GLMObjective.psum_axis), where the kernel is always legal.
    """
    if not pallas_enabled():
        return False
    if n_cols > MAX_FUSED_DIM.get(jnp.dtype(dtype).name, 0):
        return False
    if interpret_mode():
        return True
    if jax.default_backend() != "tpu":
        return False
    return per_device or len(jax.devices()) == 1


def _block_prologue(i, x_ref, wgt_ref, n_valid):
    """Shared per-block prologue: row mask + garbage zeroing.

    Rows past n_valid (the ragged last grid block — X is NOT padded host-side,
    so out-of-bounds tile reads are garbage) and weight-0 rows are EXCLUDED,
    not multiplied: 0 * inf = NaN would poison both the sums and the matmuls
    (GLMObjective._weighted contract)."""
    from jax.experimental import pallas as pl  # noqa: F401

    x = x_ref[...]
    w = wgt_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + i * x.shape[0]
    live = (w != 0.0) & (rows < n_valid)
    x = jnp.where(live, x, jnp.zeros((), x.dtype))
    return x, w, live


def _mxu_dtype(x, v):
    """bf16 storage feeds the MXU bf16 x bf16 with f32 accumulation, matching
    data/matrix._mxu_dot's mixed-precision contract."""
    return v.astype(jnp.bfloat16) if x.dtype == jnp.bfloat16 else v


def _dot(a, b):
    """MXU contraction with f32 accumulation. f32 operands contract at full
    f32 precision: Mosaic's default rounds them to bf16 passes, which on a
    v5e put the f32 kernels 2e-3..3e-2 (relative) off the float64 reference
    while the stock XLA lowering of the same matrix-vector products sat at
    1e-7 (PR 21) — f32 storage must mean f32 math, bf16 storage is the
    opt-in. bf16 x bf16 products are exact in f32 either way."""
    precision = (
        jax.lax.Precision.HIGHEST
        if a.dtype == jnp.float32 and b.dtype == jnp.float32
        else None
    )
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=precision)


def _kernel(loss_and_dz, n_valid, x_ref, y_ref, off_ref, wgt_ref, coef_ref,
            val_ref, grad_ref, wsum_ref):
    """One grid step: fused contractions for rows [i*BN, (i+1)*BN)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    f32 = jnp.float32
    x, w, live = _block_prologue(i, x_ref, wgt_ref, n_valid)
    z = _dot(x, _mxu_dtype(x, coef_ref[...]))  # [BN, 1]
    z = z + off_ref[...]
    l, dz = loss_and_dz(z, y_ref[...])
    wl = jnp.where(live, w * l, 0.0)
    wdz = jnp.where(live, w * dz, 0.0)

    # (1, 1)-shaped reductions: Mosaic rejects SCALAR stores into VMEM refs
    # ("Cannot store scalars to VMEM" on real TPU; interpret mode permits
    # them, which is how the scalar-indexed form survived CPU testing).
    part_val = jnp.sum(wl, axis=(0, 1), keepdims=True)
    part_wsum = jnp.sum(wdz, axis=(0, 1), keepdims=True)
    part_grad = _dot(x.T, _mxu_dtype(x, wdz.astype(f32)))  # [D, 1]

    @pl.when(i == 0)
    def _init():
        val_ref[...] = part_val
        wsum_ref[...] = part_wsum
        grad_ref[...] = part_grad

    @pl.when(i != 0)
    def _acc():
        val_ref[...] += part_val
        wsum_ref[...] += part_wsum
        grad_ref[...] += part_grad



def _tiled_row_inputs(labels, offsets, margin_shift, weights, n, bn):
    """Pad the [N]-vectors (4 bytes/row — X itself is NOT padded; see the
    ragged-last-block mask) to the block multiple and lift them to [N_pad, 1]
    columns. margin_shift rides the offsets (it shifts z)."""
    f32 = jnp.float32
    n_pad = -(-n // bn) * bn

    def pad(v):
        return jnp.pad(v.astype(f32), (0, n_pad - n))[:, None]

    return pad(offsets + margin_shift), pad(labels), pad(weights), n_pad // bn


def _row_block_specs(pl, bn, d):
    """BlockSpecs for (X, y, off, w): X tiled over rows, vectors alongside."""
    return [
        pl.BlockSpec((bn, d), lambda i: (i, 0)),
        pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        pl.BlockSpec((bn, 1), lambda i: (i, 0)),
    ]


@functools.partial(
    jax.jit, static_argnames=("loss_and_dz", "interpret", "block_rows")
)
def fused_loss_grad_sums(
    X: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    eff_coef: Array,
    margin_shift: Array,
    *,
    loss_and_dz,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
) -> tuple[Array, Array, Array]:
    """(loss_sum, gradient_vector_sum [D], weighted_dz_sum) in one X pass.

    ``eff_coef``/``margin_shift`` are the normalization-effective coefficients
    and margin shift (NormalizationContext.effective_coefficients) — pass the
    raw coefficients and 0.0 when unnormalized. The caller applies
    ``normalization.apply_to_gradient`` and the L2 term to the returned sums.
    """
    from jax.experimental import pallas as pl

    n, d = X.shape
    bn = block_rows
    f32 = jnp.float32
    off, y, w, grid = _tiled_row_inputs(labels, offsets, margin_shift, weights, n, bn)
    coef = eff_coef.astype(f32)[:, None]  # [D, 1]

    kernel = functools.partial(_kernel, loss_and_dz, n)
    val, grad, wsum = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=_row_block_specs(pl, bn, d) + [
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), f32),
            jax.ShapeDtypeStruct((d, 1), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
        ],
        interpret=interpret,
    )(X, y, off, w, coef)
    return val[0, 0], grad[:, 0], wsum[0, 0]


def _hvp_kernel(dzz, n_valid, x_ref, y_ref, off_ref, wgt_ref,
                coef_ref, v_ref, sv_ref, vec_ref, usum_ref):
    """One grid step of the fused Gauss-Newton HVP: the X block is read from
    HBM once and used for all three contractions (z, dv, X^T u). The stock
    lowering reads X three times per HVP, and TRON evaluates one HVP per CG
    step (TRON.scala:278-338), making this the hottest op of a TRON solve."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    f32 = jnp.float32
    x, w, live = _block_prologue(i, x_ref, wgt_ref, n_valid)
    z = _dot(x, _mxu_dtype(x, coef_ref[...]))
    z = z + off_ref[...]  # [BN, 1]
    dv = _dot(x, _mxu_dtype(x, v_ref[...]))
    dv = dv + sv_ref[...]  # directional margin shift, (1, 1) broadcast
    u = jnp.where(live, w * dzz(z, y_ref[...]) * dv, 0.0)
    part_vec = _dot(x.T, _mxu_dtype(x, u.astype(f32)))  # [D, 1]
    # (1, 1) keepdims: scalar VMEM stores are illegal on real TPU (see _kernel)
    part_usum = jnp.sum(u, axis=(0, 1), keepdims=True)

    @pl.when(i == 0)
    def _init():
        vec_ref[...] = part_vec
        usum_ref[...] = part_usum

    @pl.when(i != 0)
    def _acc():
        vec_ref[...] += part_vec
        usum_ref[...] += part_usum


@functools.partial(jax.jit, static_argnames=("dzz", "interpret", "block_rows"))
def fused_hessian_vector_sums(
    X: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    eff_coef: Array,
    margin_shift: Array,
    eff_v: Array,
    shift_v: Array,
    *,
    dzz,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
) -> tuple[Array, Array]:
    """(vector_sum [D], u_sum) for the Gauss-Newton HVP in one X pass.

    Computes u = w * dzz(z, y) * (X @ eff_v + shift_v) with
    z = X @ eff_coef + margin_shift + offsets, returning (X^T u, sum u); the
    caller applies ``normalization.apply_to_gradient`` and the l2 term exactly
    as GLMObjective.hessian_vector does. ``shift_v`` is dv's own margin shift
    (it must NOT ride the offsets — those shift z, not dv).
    """
    from jax.experimental import pallas as pl

    n, d = X.shape
    bn = block_rows
    f32 = jnp.float32
    off, y, w, grid = _tiled_row_inputs(labels, offsets, margin_shift, weights, n, bn)
    coef = eff_coef.astype(f32)[:, None]
    v = eff_v.astype(f32)[:, None]

    kernel = functools.partial(_hvp_kernel, dzz, n)
    sv = jnp.reshape(jnp.asarray(shift_v, f32), (1, 1))
    vec, usum = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=_row_block_specs(pl, bn, d) + [
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, 1), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
        ],
        interpret=interpret,
    )(X, y, off, w, coef, v, sv)
    return vec[:, 0], usum[0, 0]


# The Hessian kernel holds an [BN, D] block, its normalized copy, and the
# [D, D] accumulator in VMEM at once: cap D and use a smaller row block.
HESS_BLOCK_ROWS = 256
MAX_HESS_DIM = 512


def _hess_kernel(dzz, n_valid, x_ref, y_ref, off_ref, wgt_ref, coef_ref,
                 shift_ref, factor_ref, h_ref):
    """One grid step of the fused Hessian build: H += A_i^T diag(d_i) A_i with
    A_i = (X_i - shift) * factor computed in VMEM — the stock lowering
    materializes the full normalized design in HBM and reads it twice
    (HessianMatrixAggregator semantics, objective.hessian_matrix). This is the
    per-iteration hot op of the NEWTON solver."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    f32 = jnp.float32
    x, w, live = _block_prologue(i, x_ref, wgt_ref, n_valid)
    z = _dot(x, _mxu_dtype(x, coef_ref[...]))
    z = z + off_ref[...]  # [BN, 1]
    d = jnp.where(live, w * dzz(z, y_ref[...]), 0.0)  # [BN, 1]
    # variance/Hessian math runs at f32 even for bf16 storage (the stock
    # path's "reduction dtype" contract): upcast the block, THEN normalize.
    a = x.astype(f32)
    a = (a - shift_ref[...]) * factor_ref[...]  # [BN, D], shift/factor [1, D]
    a = jnp.where(live, a, 0.0)  # masked rows contribute nothing even if inf
    part = _dot(a.T, a * d)  # [D, D]

    @pl.when(i == 0)
    def _init():
        h_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        h_ref[...] += part


@functools.partial(jax.jit, static_argnames=("dzz", "interpret", "block_rows"))
def fused_hessian_matrix(
    X: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    eff_coef: Array,
    margin_shift: Array,
    shifts: Array,
    factors: Array,
    *,
    dzz,
    interpret: bool = False,
    block_rows: int = HESS_BLOCK_ROWS,
) -> Array:
    """Full [D, D] Gauss-Newton Hessian (no l2 term) in one X pass.

    ``eff_coef``/``margin_shift`` produce the margins exactly as
    GLMObjective._margins; ``shifts``/``factors`` are the normalization
    vectors applied to the design rows (pass zeros/ones when unnormalized).
    The caller adds the l2 diagonal.
    """
    from jax.experimental import pallas as pl

    n, d = X.shape
    bn = block_rows
    f32 = jnp.float32
    off, y, w, grid = _tiled_row_inputs(labels, offsets, margin_shift, weights, n, bn)
    coef = eff_coef.astype(f32)[:, None]
    sh = shifts.astype(f32)[None, :]
    fc = factors.astype(f32)[None, :]

    kernel = functools.partial(_hess_kernel, dzz, n)
    H = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=_row_block_specs(pl, bn, d) + [
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, d), f32),
        interpret=interpret,
    )(X, y, off, w, coef, sh, fc)
    return H
