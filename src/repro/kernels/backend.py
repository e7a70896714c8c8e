"""Kernel-lowering selection shared by every Pallas entry point.

Historically each kernel wrapper defaulted to ``interpret=True`` and
``kernels/ops.py`` hand-rolled an ``_on_tpu()`` check per call site — so a
direct kernel call on a real accelerator silently ran the Python interpreter
path unless the caller remembered to flip the flag.  This module centralizes
the policy (DESIGN.md §9):

* **interpret only when explicitly requested or when no real backend
  exists.**  ``resolve_interpret(None)`` is False exactly when
  ``jax.default_backend()`` is a platform the kernel has a lowering for.
* **descent dispatch** — ``descent_plan()`` picks the lowering of the fused
  wavelet-descent family: ``tpu`` (``make_async_copy`` tile gathers), ``gpu``
  (Pallas-on-Triton ``plgpu.load`` gathers), or ``ref`` (the vectorized pure-jnp
  fallback — strictly faster than sequential interpret-mode grids inside a
  search ``while_loop``, so it is the no-accelerator default).
* **forcing** — tests and the CI gpu-lowering job select a code path that the
  host cannot compile by forcing e.g. ``gpu:interpret`` (the Triton kernel
  body, run by the Pallas interpreter).  Either ``force_plan(...)`` (context
  manager) or the ``REPRO_KERNEL_BACKEND`` environment variable.

Resolution precedence: explicit argument > ``force_plan`` > environment >
auto-detection.  Resolution happens OUTSIDE jit traces (the plan strings are
static jit arguments), so a forced plan never leaks into a cached executable
compiled under a different plan.
"""
from __future__ import annotations

import contextlib
import os
from typing import NamedTuple

import jax

ENV_VAR = "REPRO_KERNEL_BACKEND"

# platforms with a real (compiled) lowering of the descent-family kernels
ACCELERATORS = ("tpu", "gpu")

_FORCED: list[str | None] = [None]


def canonical_backend() -> str:
    """``jax.default_backend()`` with vendor names collapsed: 'cuda'/'rocm'
    -> 'gpu'."""
    return {"cuda": "gpu", "rocm": "gpu"}.get(jax.default_backend(),
                                              jax.default_backend())


def accelerator() -> str | None:
    """'tpu' / 'gpu' when that is the default backend, else None."""
    b = canonical_backend()
    return b if b in ACCELERATORS else None


def resolve_interpret(interpret: bool | None,
                      supported: tuple[str, ...] = ACCELERATORS) -> bool:
    """The interpret flag a kernel entry point should run with.

    ``interpret`` not None is an explicit request and wins.  Otherwise
    interpret exactly when the default backend is not one the kernel has a
    compiled lowering for — the regression contract of ISSUE 8: a kernel
    called on a real backend must compile, not silently interpret."""
    if interpret is not None:
        return bool(interpret)
    return canonical_backend() not in supported


class KernelPlan(NamedTuple):
    """A resolved lowering choice for the descent-family kernels."""
    kind: str        # "tpu" | "gpu" | "ref"
    interpret: bool  # run the Pallas body under the interpreter

    @property
    def tag(self) -> str:
        """Canonical string form — the executor-cache key component."""
        return f"{self.kind}:interpret" if self.interpret else self.kind


VALID_REQUESTS = ("auto", "tpu", "gpu", "ref", "interpret",
                  "tpu:interpret", "gpu:interpret")


def _requested(request: str | None) -> str:
    req = request or _FORCED[0] or os.environ.get(ENV_VAR) or "auto"
    if req not in VALID_REQUESTS:
        raise ValueError(f"unknown kernel backend {req!r}; expected one of "
                         f"{VALID_REQUESTS}")
    return req


def descent_plan(request: str | None = None) -> KernelPlan:
    """Lowering for ``ops.wavelet_count_batch`` (and the fused beam-step).

    auto: tpu -> compiled TPU kernel, gpu -> compiled Triton kernel,
    else -> the vectorized jnp fallback (``ref``).  A forced accelerator kind
    the host cannot compile degrades to its interpret mode (that *is* the
    explicit request the interpret policy requires) — how CI exercises the
    Triton code path on CPU-only runners."""
    req = _requested(request)
    if req == "auto":
        acc = accelerator()
        plan = KernelPlan(acc, False) if acc else KernelPlan("ref", False)
    elif req == "ref":
        plan = KernelPlan("ref", False)
    elif req == "interpret":
        plan = KernelPlan("gpu", True)      # portable body under interpret
    else:
        kind, _, mode = req.partition(":")
        plan = KernelPlan(kind, mode == "interpret" or accelerator() != kind)
    _record_plan(plan)
    return plan


def _record_plan(plan: KernelPlan) -> None:
    """Count lowering resolutions per tag in the live obs registry — a
    production sanity gauge: a tag you didn't deploy showing up here means a
    stray force/env leaked into serving.  Free while the registry is
    disabled (the counter's write is one checked no-op)."""
    import repro.obs as obs
    reg = obs.default_registry()
    if not reg.enabled:              # skip even the get-or-create lookup
        return
    reg.counter("repro_kernel_plan_total", {"tag": plan.tag},
                "descent-kernel lowering resolutions by plan tag").inc()


def kernel_plan(lowering: str | None = None,
                interpret: bool | None = None) -> KernelPlan:
    """Like :func:`descent_plan` but for a direct kernel call, which cannot
    fall back to jnp: 'ref' (and the no-accelerator auto case) resolve to the
    portable gpu body under interpret."""
    plan = descent_plan(lowering)
    if plan.kind == "ref":
        plan = KernelPlan("gpu", True)
    if interpret is not None:
        plan = KernelPlan(plan.kind, bool(interpret))
    return plan


@contextlib.contextmanager
def force_plan(request: str):
    """Force a lowering for the dynamic extent of the context (tests/CI).
    Nested forces restore the previous value on exit."""
    _requested(request)                     # validate eagerly
    prev, _FORCED[0] = _FORCED[0], request
    try:
        yield
    finally:
        _FORCED[0] = prev
