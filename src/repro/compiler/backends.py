"""Backend registry: capability-aware execution strategies over a
CompiledProgram.

One lowered program, many ways to replay it. Each backend is registered by
name and provides two factories — `single` (one sample) and `batched`
(leading batch axis) — that take a `CompiledProgram` and a `BackendOptions`
and return a runner with the uniform serving contract:

    runner({input_name: np.ndarray, ...}) -> {output_name: np.ndarray, ...}

numpy in, numpy out, graph outputs only, blocking until the result is
ready. `Deployment.run` / `Server` / the executor benchmark all go
through this table, so a third-party backend (a new kernel library,
a remote accelerator client) plugs in with one `register_backend` call and
is immediately selectable as `repro.compile(..., backend="mine")`.

Every backend carries a `BackendCapabilities` descriptor so callers can
validate a (backend, options) pair *before* building a runner —
`Deployment.with_backend` checks at swap time, `repro.compile` at compile
time — instead of failing on the first `run`. Execution knobs travel as a
typed, frozen `BackendOptions` (accepted as
``repro.compile(..., backend_options=...)``, carried through `Deployment`
save/load and `Server`), replacing the old ad-hoc ``interpret=None``
auto-detection scattered through `repro.core.compiled`.

Built-in backends (see repro/core/compiled.py for their numerics):

  * ``numpy``  — vectorized fused-tile replay; bit-exact oracle twin.
  * ``jax``    — the whole program as one jitted (and, batched, vmapped)
    XLA function; the serving fast path.
  * ``pallas`` — the fused per-core megakernel over the Pallas kernels
    (`repro.core.megakernel`): scratchpad-sized segments, one
    `pallas_call` each and `num_cores` of them when the scratchpad allows,
    requant fused in epilogues. Real Mosaic lowering on TPU, interpret
    mode elsewhere.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from .. import tracing
from ..core import compiled as _C
from ..core import megakernel as _MK


class BackendError(KeyError):
    """Unknown backend, conflicting registration, or an option the target
    backend does not support."""


Runner = Callable[[dict], dict]


@dataclasses.dataclass(frozen=True)
class BackendOptions:
    """Typed execution knobs, validated against a backend's capabilities.

    All fields default to None ("backend decides"), so a default instance
    is valid for every backend. Fields:

      interpret          — Pallas interpret mode. None: auto (real Mosaic
                           lowering on TPU, interpret elsewhere); False
                           requires the backend's `requires_device`.
      scratchpad_budget  — bytes; packs the megakernel segments against
                           less than the machine's scratchpad capacity
                           (a larger value is clamped to the capacity).
      max_kernels        — target count of emitted pallas_calls per
                           program, met only within the scratchpad
                           capacity (None: the program's core count).
    """

    interpret: bool | None = None
    scratchpad_budget: int | None = None
    max_kernels: int | None = None

    def set_fields(self) -> tuple[str, ...]:
        """Names of explicitly-set (non-None) fields — what capability
        validation checks against `supported_options`."""
        return tuple(f.name for f in dataclasses.fields(self)
                     if getattr(self, f.name) is not None)

    def cache_key(self) -> tuple:
        """Hashable identity for runner/deployment caches."""
        return tuple((f.name, getattr(self, f.name))
                     for f in dataclasses.fields(self))

    def to_manifest(self) -> dict:
        """JSON-safe dict of the set fields (deployment artifacts)."""
        return {name: getattr(self, name) for name in self.set_fields()}

    @classmethod
    def from_manifest(cls, d: dict | None) -> "BackendOptions":
        """Lenient inverse of `to_manifest`: unknown keys (newer artifacts)
        are ignored, absent ones default."""
        d = d or {}
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend can do — checked before runners are built.

    supports_batched_native — the batched factory is a real batched
        lowering, not the per-sample fallback loop.
    supports_decode — usable for LM decode step functions (serving loops).
    requires_device — jax platform needed for native execution (e.g.
        "tpu"); `interpret=False` off that device fails validation.
    supported_options — `BackendOptions` field names this backend honors;
        explicitly-set fields outside this set fail validation.
    mesh — executes across a jax device mesh: requires (and is required
        by) a machine whose `HardwareModel.mesh_shape` is set —
        `repro.compile` enforces the pairing both ways.
    """

    supports_batched_native: bool = False
    supports_decode: bool = False
    requires_device: str | None = None
    supported_options: frozenset = frozenset()
    mesh: bool = False


@dataclasses.dataclass(frozen=True)
class Backend:
    """A named pair of options-aware runner factories + capabilities."""

    name: str
    single: Callable[[_C.CompiledProgram, BackendOptions], Runner]
    batched: Callable[[_C.CompiledProgram, BackendOptions], Runner]
    capabilities: BackendCapabilities = BackendCapabilities()

    def validate_options(self, options: BackendOptions) -> None:
        """Raise `BackendError` if `options` sets a knob this backend does
        not support, or demands native execution off the required device.
        A default (all-None) options object always validates."""
        unsupported = [f for f in options.set_fields()
                       if f not in self.capabilities.supported_options]
        if unsupported:
            raise BackendError(
                f"backend {self.name!r} does not support option(s) "
                f"{unsupported}; supported: "
                f"{sorted(self.capabilities.supported_options)}")
        dev = self.capabilities.requires_device
        if options.interpret is False and dev is not None:
            import jax
            if jax.default_backend() != dev:
                raise BackendError(
                    f"backend {self.name!r} with interpret=False requires "
                    f"a {dev!r} device (running on "
                    f"{jax.default_backend()!r}); use interpret=None/True")

    def validate_machine(self, machine) -> None:
        """Raise `BackendError` when the backend/machine mesh pairing is
        inconsistent: a mesh backend needs a machine carrying a mesh shape
        (`HardwareModel.with_mesh`), and a single-device backend refuses a
        mesh machine. Enforced at compile time, per-call backend override,
        and `with_backend` swap — an invalid pairing never reaches a
        runner."""
        mesh_shape = getattr(machine, "mesh_shape", None)
        if self.capabilities.mesh and mesh_shape is None:
            raise BackendError(
                f"backend {self.name!r} executes across a device mesh but "
                f"machine {machine.name!r} has no mesh shape; target it "
                f"with machine.with_mesh(data, model)")
        if mesh_shape is not None and not self.capabilities.mesh:
            raise BackendError(
                f"machine {machine.name!r} targets mesh shape {mesh_shape} "
                f"but backend {self.name!r} is single-device; use "
                f'backend="mesh" (or a machine without a mesh shape)')


_REGISTRY: dict[str, Backend] = {}


def register_backend(name: str, *,
                     single: Callable,
                     batched: Callable | None = None,
                     capabilities: BackendCapabilities | None = None,
                     overwrite: bool = False) -> Backend:
    """Register (or replace, with overwrite=True) an execution backend.

    `batched` defaults to a per-sample loop over `single` — correct for any
    backend, so plugins only need the single-sample runner. Factories take
    ``(prog, options)``."""
    if name in _REGISTRY and not overwrite:
        raise BackendError(
            f"backend {name!r} already registered; pass overwrite=True")
    has_native_batched = batched is not None
    if batched is None:
        batched = _loop_batched(single)
    caps = capabilities or BackendCapabilities(
        supports_batched_native=has_native_batched)
    be = Backend(name=name, single=single, batched=batched,
                 capabilities=caps)
    _REGISTRY[name] = be
    return be


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise BackendError(
            f"unknown backend {name!r}; registered: {list_backends()}"
        ) from None


def list_backends() -> list[str]:
    return sorted(_REGISTRY)


def _loop_batched(single_factory):
    """Default batched factory: run `single` per sample and stack."""
    def factory(prog: _C.CompiledProgram, options: BackendOptions) -> Runner:
        single = single_factory(prog, options)

        def run(batch: dict) -> dict:
            B = next(iter(batch.values())).shape[0]
            outs = [single({k: v[b] for k, v in batch.items()})
                    for b in range(B)]
            return {t: np.stack([o[t] for o in outs])
                    for t in prog.graph.outputs}
        return run
    return factory


# -- built-in backends --------------------------------------------------------

def _numpy_single(prog: _C.CompiledProgram,
                  options: BackendOptions) -> Runner:
    def run(inputs: dict) -> dict:
        vals = _C.run_numpy(prog, inputs)      # exposes every buffer
        return {t: vals[t] for t in prog.graph.outputs}
    return run


def _jax_single(prog: _C.CompiledProgram,
                options: BackendOptions) -> Runner:
    import functools
    _C.jit_single(prog)                        # trace once at build time
    return functools.partial(_C.run_jax, prog, batched=False)


def _jax_batched(prog: _C.CompiledProgram,
                 options: BackendOptions) -> Runner:
    import functools
    _C.jit_batched(prog)
    return functools.partial(_C.run_jax, prog, batched=True)


def _numpy_io(fn, ship: Callable[[dict], dict]) -> Runner:
    """numpy in, numpy out around a jitted program, in three spans: the
    copy of the inputs to the device (`repro.runner.h2d`), the call, which
    returns once the program is enqueued (`repro.runner.launch`), and the
    copy of the outputs back, which waits for the device to finish
    (`repro.runner.fetch`). A network of several outputs (YOLOv5s's three
    Detect maps) has every output's copy started before any is waited on
    (`jax.device_get`).

    `ship` puts the host inputs in the layout the program takes
    (`megakernel.ship_inputs`): an input that the network's stem reads
    crosses to the device as (N, H / s, s·W·C) row groups, a free reshape
    of the C-contiguous (N, H, W, C) batch `Server` stacks, lane-dense on
    the device. In its (N, H, W, C) shape the copy would pad 3 channels
    out to 128 lanes (4.76 against 1.57 ms for 4 frames of 640×640×3 on a
    TPU v5e).

    Two more entry points let a caller overlap the input copy of one call
    with the program of another. `run.stage(inputs)` issues the copy alone
    and returns the device arrays without waiting on them; `run` takes
    them in place of numpy arrays and skips the copy. `run.launch(inputs)`
    enqueues the program and returns the fetch, a no-argument callable,
    so the caller can work between the two."""
    import jax
    import jax.numpy as jnp

    def stage(inputs: dict) -> dict:
        with tracing.span("repro.runner.h2d"):
            return {k: jnp.asarray(v) for k, v in ship(inputs).items()}

    def launch(inputs: dict) -> Callable[[], dict]:
        if not all(isinstance(v, jax.Array) for v in inputs.values()):
            inputs = stage(inputs)
        with tracing.span("repro.runner.launch"):
            out = fn(inputs)

        def fetch() -> dict:
            with tracing.span("repro.runner.fetch"):
                return jax.device_get(out)
        return fetch

    def run(inputs: dict) -> dict:
        return launch(inputs)()

    run.stage, run.launch = stage, launch
    return run


def _pallas_fn(prog: _C.CompiledProgram, options: BackendOptions,
               batched: bool):
    """The traced megakernel program for (options, batched)."""
    interpret = _C.resolve_interpret(options.interpret)
    make = _MK.megakernel_batched if batched else _MK.jit_megakernel_single
    return make(prog, interpret=interpret,
                budget=options.scratchpad_budget,
                max_kernels=options.max_kernels)


def _pallas_single(prog: _C.CompiledProgram,
                   options: BackendOptions) -> Runner:
    import functools
    return _numpy_io(_pallas_fn(prog, options, batched=False),
                     functools.partial(_MK.ship_inputs, prog))


def _pallas_batched(prog: _C.CompiledProgram,
                    options: BackendOptions) -> Runner:
    import functools
    return _numpy_io(_pallas_fn(prog, options, batched=True),
                     functools.partial(_MK.ship_inputs, prog))


def _mesh_single(prog: _C.CompiledProgram,
                 options: BackendOptions) -> Runner:
    from ..cluster.mesh import mesh_single_runner
    return mesh_single_runner(prog)


def _mesh_batched(prog: _C.CompiledProgram,
                  options: BackendOptions) -> Runner:
    from ..cluster.mesh import mesh_batched_runner
    return mesh_batched_runner(prog)


register_backend("numpy", single=_numpy_single,
                 capabilities=BackendCapabilities())
register_backend("jax", single=_jax_single, batched=_jax_batched,
                 capabilities=BackendCapabilities(
                     supports_batched_native=True, supports_decode=True))
register_backend("pallas", single=_pallas_single, batched=_pallas_batched,
                 capabilities=BackendCapabilities(
                     supports_batched_native=True,
                     requires_device="tpu",
                     supported_options=frozenset(
                         {"interpret", "scratchpad_budget",
                          "max_kernels"})))
register_backend("mesh", single=_mesh_single, batched=_mesh_batched,
                 capabilities=BackendCapabilities(
                     supports_batched_native=True, supports_decode=True,
                     mesh=True))
