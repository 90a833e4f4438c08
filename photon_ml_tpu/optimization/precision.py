"""Precision policy: storage vs accumulation dtypes, and the host dtype boundary.

The hot coordinate-descent loop does ~0.5 flop/byte (counted from shapes) —
memory-bandwidth-bound, so bytes ARE the budget. ``PrecisionPolicy`` names the
one lever that halves them: store the big arrays (per-entity coefficient
tables, bucket feature blocks, per-sample scoring views, serving coefficient
tables) in bfloat16/float16 while every reduction, solve and score ACCUMULATES
in float32. The reduced-precision bytes live in HBM; the f32 upcasts happen in
registers as XLA fuses the convert into the consuming gather/matvec, so the
traffic saving is real and the arithmetic is not degraded beyond the storage
rounding itself.

Contract:

- ``FLOAT32`` (the default) is the REFERENCE policy: every cast it implies is
  an identity, so code threading a policy through an existing f32 path remains
  BITWISE identical to the un-threaded code — the existing bitwise parity
  gates (update-program vs per-bucket, serving vs eager) keep guarding it.
- Reduced policies (``BFLOAT16``/``FLOAT16``) are opt-in and tolerance-gated:
  ``bench.py --host-loop`` measures their held-out log-loss drift against the
  f32 reference and fails when it exceeds an explicit bound
  (benchmarks/host_loop_bench.BF16_HELDOUT_LOGLOSS_TOL). Never compare a
  reduced-precision run bitwise against f32 — that is a category error the
  policy object exists to make impossible to express by accident.

This module is also the single owner of the HOST-side dtype boundary rules
that used to live as per-call-site branches and comments:

- ``offsets_fuse_on_device`` — the serving engine's f64-offset host-link
  branch (``GameServingEngine.score``/``predict``): offsets whose dtype would
  not survive device conversion (float64 on a non-x64 runtime, any integer
  dtype) must be added — and linked — host-side at full precision to preserve
  the eager output dtype contract.
- ``HOST_LINK_EXP_ULPS`` / ``host_link`` — the documented 1-ulp numpy-exp
  budget: numpy's SIMD exp can differ from itself by one ulp depending on
  array alignment, so host-side link application (the f64-offset branch above)
  agrees with any other exp evaluation only to HOST_LINK_EXP_ULPS ulps; tests
  comparing across that boundary budget exactly this constant instead of
  re-deriving it in comments.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# numpy's SIMD exp may differ from itself by one ulp depending on input array
# alignment; every host-link comparison (engine predict host branch vs eager,
# mixed-dtype engine scoring) budgets exactly this many ulps.
HOST_LINK_EXP_ULPS = 1

_STORAGE_DTYPES = ("float32", "bfloat16", "float16")

# CLI / config spellings -> canonical storage dtype name
_ALIASES = {
    "f32": "float32",
    "float32": "float32",
    "fp32": "float32",
    "bf16": "bfloat16",
    "bfloat16": "bfloat16",
    "f16": "float16",
    "fp16": "float16",
    "float16": "float16",
}


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Storage dtype for the big device-resident arrays + accumulation dtype
    for everything that reduces over them. Hashable (frozen, string fields) so
    it participates in ``solver_cache``'s lru_cache keys directly."""

    storage: str = "float32"
    accum: str = "float32"

    def __post_init__(self):
        canon = _ALIASES.get(str(self.storage).lower())
        if canon is None:
            raise ValueError(
                f"unknown storage precision {self.storage!r}; expected one of "
                f"{sorted(set(_ALIASES))}"
            )
        object.__setattr__(self, "storage", canon)
        if self.accum != "float32":
            # f32 accumulation is the whole point of the policy: bf16/f16
            # accumulation silently loses mass in long reductions (the MP001
            # lint hazard). Nothing in the codebase wants anything else.
            raise ValueError(
                f"accumulation dtype must be float32, got {self.accum!r}"
            )

    @property
    def name(self) -> str:
        """Short bench/CLI name: 'f32', 'bf16', 'f16'."""
        return {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}[self.storage]

    @property
    def storage_dtype(self):
        return jnp.dtype(self.storage)

    @property
    def accum_dtype(self):
        return jnp.dtype(self.accum)

    @property
    def is_reference(self) -> bool:
        """True for the f32 policy whose casts are all identities — the
        bitwise-gated path."""
        return self.storage == "float32"

    def to_storage(self, x):
        """Cast an array to the storage dtype. The REFERENCE policy is a
        strict no-op for every input — including f64 tables on x64 runtimes —
        because 'f32' there means 'leave the existing dtype contract alone',
        not 'force f32'."""
        if self.is_reference or x is None or x.dtype == self.storage_dtype:
            return x
        return x.astype(self.storage_dtype)

    def to_accum(self, x):
        """Cast an array up to the accumulation dtype (strict no-op under the
        reference policy, same rationale as ``to_storage``)."""
        if self.is_reference or x is None or x.dtype == self.accum_dtype:
            return x
        return x.astype(self.accum_dtype)


FLOAT32 = PrecisionPolicy()
BFLOAT16 = PrecisionPolicy(storage="bfloat16")
FLOAT16 = PrecisionPolicy(storage="float16")


def resolve_precision(spec) -> PrecisionPolicy:
    """None / 'f32' / 'bf16' / 'f16' / dtype-like / PrecisionPolicy -> policy."""
    if spec is None:
        return FLOAT32
    if isinstance(spec, PrecisionPolicy):
        return spec
    return PrecisionPolicy(storage=str(np.dtype(spec)) if not isinstance(spec, str) else spec)


# --------------------------------------------------------------------------
# host dtype boundary (the engine's f64-offset host-link branch, centralized)
# --------------------------------------------------------------------------


def offsets_fuse_on_device(offsets: np.ndarray) -> bool:
    """True when a request's offsets can be added (and linked) ON DEVICE
    without changing the eager output dtype contract.

    Floating offsets whose dtype survives device conversion promote the same
    way under jnp and numpy, so fusing is transparent. Two cases must stay
    host-side: float64 offsets on a non-x64 runtime (device conversion would
    silently truncate — the eager path adds them in numpy at full f64), and
    integer offsets (jnp f32+i64 -> f32 but numpy -> f64, a dtype divergence).
    One empty-slice probe answers both without transferring data."""
    offsets = np.asarray(offsets)
    return (
        bool(np.issubdtype(offsets.dtype, np.floating))
        and jnp.asarray(offsets[:0]).dtype == offsets.dtype
    )


def host_link(task, margins: np.ndarray) -> np.ndarray:
    """Host-side link-inverse for the offsets-stay-on-host branch: numpy
    sigmoid / exp / identity at the margins' own (full) precision. Agrees
    with any other exp evaluation only to HOST_LINK_EXP_ULPS ulps (numpy SIMD
    exp alignment effect) — budget that constant, don't expect bitwise."""
    from photon_ml_tpu.types import TaskType

    task = TaskType(task)
    if task == TaskType.LOGISTIC_REGRESSION:
        return 1.0 / (1.0 + np.exp(-margins))
    if task == TaskType.POISSON_REGRESSION:
        return np.exp(margins)
    return margins
