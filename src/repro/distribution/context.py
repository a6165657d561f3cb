"""Mesh context for model-internal SPMD decisions.

Model code (e.g. the MoE dispatch shard_map) needs the mesh at trace time;
`jax.sharding.get_abstract_mesh()` is only populated in explicit-axes mode,
so launchers wrap lowering/execution in `with_mesh_context(mesh)` and model
code asks `current_mesh()` (which also falls back to the abstract mesh when
present)."""

from __future__ import annotations

import contextlib
import threading

import jax

_state = threading.local()


@contextlib.contextmanager
def with_mesh_context(mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh = prev


def current_mesh():
    m = getattr(_state, "mesh", None)
    if m is not None:
        return m
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am
