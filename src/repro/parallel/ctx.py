"""Activation-sharding context: model code stays mesh-agnostic.

``constrain(x, *logical_axes)`` is a no-op unless a mesh+rules context is
active (cells.Cell.lower / launch.train install one). Under a context it
applies jax.lax.with_sharding_constraint with the spec derived from the same
logical->mesh rules used for parameters — the GSPMD hygiene that keeps big
intermediates (SSD chunk tensors, MoE dispatch, logits) sharded instead of
replicated (see EXPERIMENTS.md §Perf iteration 0).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding

from repro.parallel import sharding as sh

_state = threading.local()


def _current():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules=None):
    prev = _current()
    _state.ctx = (mesh, rules or sh.DEFAULT_RULES)
    try:
        yield
    finally:
        _state.ctx = prev


def sharding_active() -> bool:
    """Whether an :func:`activation_sharding` context is installed."""
    return _current() is not None


def constrain(x, *axes: Optional[str]):
    """Constrain ``x``'s sharding by logical axis names (no-op w/o context)."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(axes) != x.ndim:
        return x
    spec = sh.spec_for(x.shape, axes, mesh, rules)
    if all(e is None for e in spec):
        return x          # fully replicated constraint would only pessimize
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
