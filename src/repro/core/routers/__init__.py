"""Pluggable token->expert routers.

``MoEConfig.routing`` is a key into this registry.  Built-in strategies:

* ``topk``          — GShard/Switch sequential top-k (paper 3.2/3.3, the
  looping argmax of Table 2);
* ``prototype``     — M6-T k top-1 expert prototyping (Eq. 3 / Fig. 8);
* ``expert_choice`` — expert-choice routing (Zhou et al., 2022): experts
  pick their top-C tokens, perfect load balance by construction;
* ``hash``          — stateless hash routing (Roller et al., 2021):
  deterministic position-hash assignment, no learned router;
* ``sigmoid_bias``  — DeepSeek-V3 sigmoid scores, top-k on scores plus a
  correction bias, gates from the unbiased scores, renormalised and
  scaled.  A router with a ``bias_spec`` gets its own bias leaf
  (``router_bias``) and receives it as ``plan(..., bias=b)``.

Adding a strategy is ~50 lines::

    from repro.core.routers import register_router
    from repro.core.routers.base import Router, RoutingPlan

    @register_router
    class MyRouter:
        name = "mine"
        def param_spec(self, m, d_model, init): ...
        def plan(self, x32, w, m, capacity, combine_dtype=...): ...

Registration must happen before a ``MoEConfig(routing="mine")`` is
constructed (config validation consults this registry).
"""
from __future__ import annotations

from typing import Dict, Tuple, Type

from repro.core.routers.base import Router, RoutingPlan  # noqa: F401

_REGISTRY: Dict[str, Router] = {}


def register_router(cls: Type) -> Type:
    """Class decorator: instantiate and register a Router under cls.name."""
    name = getattr(cls, "name", None)
    if not name or not isinstance(name, str):
        raise ValueError(f"router class {cls!r} needs a string `name` attribute")
    _REGISTRY[name] = cls()
    return cls


def get_router(name: str) -> Router:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown routing mode {name!r}; registered routers: "
            f"{', '.join(available_routers())}"
        ) from None


def available_routers() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# Built-ins self-register on import.
from repro.core.routers import (  # noqa: E402,F401
    expert_choice, hashed, prototype, sigmoid, topk)

__all__ = [
    "Router", "RoutingPlan", "register_router", "get_router",
    "available_routers",
]
