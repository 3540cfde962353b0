"""Precision policies for the solve pipeline (DESIGN.md Sec. 7).

The paper trades flops for parallelism — substitution becomes
multiplication by pre-inverted diagonal blocks — "while maintaining
numerical stability" (Sec. V).  On TPU that trade is only fully cashed
in at low precision: the MXU's peak throughput needs bf16 inputs.  A
:class:`PrecisionPolicy` separates the four dtype roles so the sweep
can run at MXU-native precision while the answer is recovered at high
precision by iterative refinement (``repro.core.refine``):

* ``storage``    — dtype of the resident cyclic factor fed to the sweep
                   (cast ONCE, at distribution time).
* ``compute``    — dtype the sweep's GEMM operands are held in (the
                   MXU input precision; presets keep it == storage).
* ``accumulate`` — dtype of GEMM partial sums (``preferred_element_type``
                   threaded down to the Pallas kernels and the shard_map
                   sweep; bf16 inputs accumulate in fp32 on the MXU at
                   no extra cost).
* ``residual``   — dtype of the refinement residual r = B - op(A)·X and
                   of the refined solution; a SECOND copy of the factor
                   is kept resident at this precision when
                   ``refine_steps > 0`` (classic mixed-precision
                   iterative refinement corrects toward the
                   high-precision operator, not the rounded one).

Presets (the ``precision=`` argument everywhere accepts these names):

    name         storage  compute  accumulate residual steps  io dtype
    ----         -------  -------  ---------- -------- -----  --------
    fp32         f32      f32      f32        f32      0      f32
    bf16         bf16     bf16     f32        f32      0      bf16
    bf16_refine  bf16     bf16     f32        f32      2      f32
    fp64_refine  f32      f32      f32        f64      2      f64

``fp64_refine`` needs ``jax_enable_x64``; it serves fp64 accuracy from
an fp32 sweep (the factor is never touched in fp64 by the sweep).

A policy is hashable and lands verbatim in the
``CompiledSolverCache`` key, so every distinct precision configuration
compiles (and retraces) exactly once per solve shape.

Every GEMM in the solve stack passes ``precision=gemm_precision(a, b)``:
on a TPU the default precision for f32 operands is ONE bf16 pass, which
would serve bf16-grade answers from the ``fp32`` preset and cap the
``bf16_refine`` residual at bf16 accuracy.  f32/f64 operands therefore
run at ``HIGHEST``; bf16 operands keep the default (native MXU input).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def gemm_precision(*operands):
    """The matmul ``precision=`` for GEMM operands (arrays or dtypes):
    ``HIGHEST`` when the promoted operand dtype is f32 or wider, None
    (the default) below that."""
    dt = jnp.result_type(*operands)
    if jnp.issubdtype(dt, jnp.floating) and jnp.finfo(dt).bits >= 32:
        return jax.lax.Precision.HIGHEST
    return None


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype assignment for one solve pipeline; see module docstring.

    Dtypes are stored as canonical dtype-name strings so the policy is
    hashable (it is part of the compiled-program cache key) and prints
    compactly.  ``name`` is cosmetic and excluded from equality/hash:
    two policies with the same dtype roles and trip count are the SAME
    cache key (the preset ``"fp32"`` and the legacy uniform float32
    policy share one compiled program).  Use :func:`resolve` to build
    one from a preset name, a dtype, or another policy.
    """
    name: str = dataclasses.field(compare=False)
    storage: str
    compute: str
    accumulate: str
    residual: str
    refine_steps: int = 0

    def __post_init__(self):
        for field in ("storage", "compute", "accumulate", "residual"):
            canon = jnp.dtype(getattr(self, field)).name
            object.__setattr__(self, field, canon)
        if self.refine_steps < 0:
            raise ValueError(f"refine_steps must be >= 0, got "
                             f"{self.refine_steps}")

    # dtype-object views of the string fields
    @property
    def storage_dtype(self):
        return jnp.dtype(self.storage)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute)

    @property
    def accumulate_dtype(self):
        return jnp.dtype(self.accumulate)

    @property
    def residual_dtype(self):
        return jnp.dtype(self.residual)

    @property
    def io_dtype(self):
        """Dtype of the program boundary (B in, X out): the residual
        dtype when refining (that is the accuracy being served),
        otherwise the sweep's compute dtype."""
        return self.residual_dtype if self.refine_steps else \
            self.compute_dtype

    @property
    def refines(self) -> bool:
        return self.refine_steps > 0

    def describe(self) -> str:
        return (f"{self.name}: storage={self.storage} compute={self.compute} "
                f"accumulate={self.accumulate} residual={self.residual} "
                f"refine_steps={self.refine_steps}")


def _preset(name, storage, compute, accumulate, residual, steps):
    return PrecisionPolicy(name=name, storage=storage, compute=compute,
                           accumulate=accumulate, residual=residual,
                           refine_steps=steps)


PRESETS: dict[str, PrecisionPolicy] = {
    "fp32": _preset("fp32", "float32", "float32", "float32", "float32", 0),
    "bf16": _preset("bf16", "bfloat16", "bfloat16", "float32", "float32", 0),
    "bf16_refine": _preset("bf16_refine", "bfloat16", "bfloat16",
                           "float32", "float32", 2),
    "fp64_refine": _preset("fp64_refine", "float32", "float32",
                           "float32", "float64", 2),
}


def from_dtype(dtype) -> PrecisionPolicy:
    """The uniform (legacy) policy: every role at ``dtype``, no
    refinement — exactly the pre-policy pipeline behavior, so code that
    passes only ``dtype=`` keys and compiles identically to before."""
    d = jnp.dtype(dtype).name
    return PrecisionPolicy(name=d, storage=d, compute=d, accumulate=d,
                           residual=d, refine_steps=0)


def resolve(precision=None, dtype=None) -> PrecisionPolicy:
    """Normalize the ``precision=`` argument into a PrecisionPolicy.

    * ``PrecisionPolicy`` — returned as-is.
    * preset name (``"fp32" | "bf16" | "bf16_refine" | "fp64_refine"``)
      — looked up in :data:`PRESETS`.
    * ``None`` — the uniform policy at ``dtype`` (which must then be
      given): the legacy single-dtype pipeline.
    """
    if isinstance(precision, PrecisionPolicy):
        return precision
    if precision is not None:
        try:
            return PRESETS[precision]
        except KeyError:
            raise ValueError(
                f"unknown precision preset {precision!r}; expected one of "
                f"{sorted(PRESETS)} or a PrecisionPolicy") from None
    if dtype is None:
        raise ValueError("need precision= or dtype= to resolve a policy")
    return from_dtype(dtype)
