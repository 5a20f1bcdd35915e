"""Kernel-dispatch registry: one seam for every merge-gain / pair-cost call.

Backends (``KERNEL_BACKENDS``):

  * ``"ref"``              — the jitted pure-jnp oracle (:mod:`repro.kernels.ref`);
    the XLA path a CPU host runs, and the differential-test baseline.
  * ``"pallas-interpret"`` — the Pallas kernel body executed in Python
    (``interpret=True``); the container-level validation mode exercised by
    the CI lane (slow: a host callback per grid step).
  * ``"pallas"``           — the compiled Pallas kernel; the deployment path
    on real TPUs (VMEM sizing notes in :mod:`repro.kernels.merge_gain`).

Selection (:func:`resolve_kernel_backend`): an explicit name — from
``SummaryConfig.kernel_backend`` — beats the ``SSUMM_KERNEL`` environment
variable, which beats the default ``"ref"``. Unknown names raise with the
valid set. The resolved name is a jit-static argument, so each backend
compiles its own executable and the choice never leaks into traced code.

Compat shim: :func:`backend_from_flags` maps the retired ``use_pallas`` /
``interpret`` bool pair onto a registry name for any caller still speaking
the old vocabulary; nothing inside the repo threads those bools anymore.
"""

from __future__ import annotations

import functools
import os

import jax

from repro.kernels import ref
from repro.kernels.entropy_bits import pair_cost_pallas
from repro.kernels.merge_gain import merge_gain_pallas

ENV_VAR = "SSUMM_KERNEL"

# name → (merge_gain impl, pair_cost impl); the single dispatch table.
_REGISTRY = {
    "ref": (
        ref.merge_gain_ref,
        ref.pair_cost_ref,
    ),
    "pallas-interpret": (
        functools.partial(merge_gain_pallas, interpret=True),
        functools.partial(pair_cost_pallas, interpret=True),
    ),
    "pallas": (
        functools.partial(merge_gain_pallas, interpret=False),
        functools.partial(pair_cost_pallas, interpret=False),
    ),
}

KERNEL_BACKENDS = tuple(sorted(_REGISTRY))


def resolve_kernel_backend(name: str | None = None) -> str:
    """Resolve a backend name: explicit config > ``$SSUMM_KERNEL`` > "ref".

    Raises ``ValueError`` naming the valid set for unknown backends (both
    from the argument and from the environment).
    """
    source = "config"
    if name is None:
        name = os.environ.get(ENV_VAR) or "ref"
        source = f"${ENV_VAR}"
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown kernel backend {name!r} (from {source}); "
            f"valid backends: {list(KERNEL_BACKENDS)}"
        )
    return name


def backend_from_flags(use_pallas: bool, interpret: bool = False) -> str:
    """Compat shim: the retired ``use_pallas``/``interpret`` bool pair →
    registry name. New code should pass backend names directly."""
    if not use_pallas:
        return "ref"
    return "pallas-interpret" if interpret else "pallas"


@functools.partial(jax.jit, static_argnames=("backend",))
@jax.named_scope("merge_gain")
def merge_gain(m, n, s, t, n_u, cidx, w, cbar, log2v, *, backend=None):
    """(rel, red) gain matrices [G, C, C] — Eq. (20)/(17) per candidate pair."""
    impl, _ = _REGISTRY[resolve_kernel_backend(backend)]
    return impl(m, n, s, t, n_u, cidx, w, cbar, log2v)


@functools.partial(jax.jit, static_argnames=("backend",))
def pair_cost(cnt, pi, cbar, log2v, *, backend=None):
    """Optimal per-pair description cost min(C̄+Cost₍₁₎, Cost₍₂₎)."""
    _, impl = _REGISTRY[resolve_kernel_backend(backend)]
    return impl(cnt, pi, cbar, log2v)
