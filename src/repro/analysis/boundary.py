"""Registered kernel boundaries for the jaxpr auditor.

The integer-datapath contract says a whole-pool dequant to float may only
happen *inside a kernel*: on TPU the Pallas kernels dequantize int4 tiles
in VMEM, and on the ref/CPU backend the bit-exact oracles (and serve_int's
gathered-view fallback) do the equivalent in plain jnp.  The auditor can't
see Pallas kernel bodies in the jaxpr (they are opaque calls), but the jnp
equivalents are inline — so they must be *named scopes* the auditor can
recognize and exempt from the pool-scale-cast rule (while still auditing
everything around them).

``kernel_boundary`` wraps a function in a non-inlined ``jax.jit`` so it
shows up as a ``jit`` eqn carrying the function's name, and records that
name here.  ``repro.analysis.jaxpr_audit`` treats eqn scopes whose name is
registered as kernel interiors.

This module must stay import-light (no jax import at module scope beyond
the lazy wrap) so kernel modules can import it without cycles; the rest of
``repro.analysis`` imports *from* kernels, never the other way around.
"""
from __future__ import annotations

from typing import Callable

# scope name -> short human description of why the interior is exempt
REGISTRY: dict[str, str] = {}


def register(name: str, why: str) -> None:
    REGISTRY[name] = why


def kernel_boundary(*, why: str, static_argnums=()) -> Callable:
    """Decorator: mark ``fn`` as a kernel-equivalent scope.

    Wraps ``fn`` in ``jax.jit(..., inline=False)`` so that when traced
    inside an outer jit it appears as a named ``jit`` eqn, and registers
    the name for the auditor.  Numerics are unchanged; under an outer jit
    the XLA inliner still fuses the body after lowering.
    """
    def deco(fn):
        import jax
        register(fn.__name__, why)
        return jax.jit(fn, static_argnums=static_argnums, inline=False)
    return deco
