"""call / capture / map — the ArBB execution trio on JAX.

Paper §2: "Closures can be used to capture computations for later optimisation.
At compile time an intermediate representation of the code is generated which
is optimised for the target architecture detected at runtime by a JIT
compiler."

    call(f)      -> CallClosure: trace-once-per-signature, JIT-compile, cache.
                    The executable is retargeted per ExecLevel (O2/O3/O4) —
                    the ArBB runtime-retargeting story.
    capture(f)   -> Closure: the *inspectable* IR (jaxpr).  Exposes op_counts()
                    and collective introspection; the roofline tooling builds
                    on the same idea at the HLO level.
    emap(f, in_axes) -> ArBB map(): apply a scalar function across all
                    elements of one or more containers (jax.vmap underneath).
                    in_axes: 0 = mapped elementwise, None = whole container
                    captured uniformly (the paper's mod2as passes matvals/
                    invec/indx uniformly and rowpi/rowpj elementwise).
"""
from __future__ import annotations

import collections
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import execlevel, sharding as shrules
from repro.core.containers import Dense, unwrap

__all__ = ["call", "capture", "emap", "Closure", "CallClosure"]


class Closure:
    """A captured computation: the ArBB 'intermediate representation'."""

    def __init__(self, fn: Callable, jaxpr: jax.extend.core.ClosedJaxpr, out_tree):
        self.fn = fn
        self.jaxpr = jaxpr
        self._out_tree = out_tree

    def op_counts(self) -> dict[str, int]:
        """Primitive-name -> count over the captured IR (recursing into
        control-flow sub-jaxprs).  Used by tests and the DSL-level roofline."""
        counts: collections.Counter[str] = collections.Counter()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                counts[eqn.primitive.name] += 1
                for v in eqn.params.values():
                    vals = v if isinstance(v, (list, tuple)) else (v,)
                    for item in vals:
                        if hasattr(item, "jaxpr"):
                            inner = item.jaxpr
                            walk(inner.jaxpr if hasattr(inner, "jaxpr") else inner)

        walk(self.jaxpr.jaxpr)
        return dict(counts)

    def gather_free(self) -> bool:
        """True if the captured IR contains no gather/scatter — the structural
        property the split-stream FFT (paper §3.3) is designed to have."""
        counts = self.op_counts()
        return not any(k.startswith(("gather", "scatter")) for k in counts)


def capture(fn: Callable, *example_args: Any) -> Closure:
    """Capture ``fn`` into an inspectable Closure (ArBB closure capture)."""
    flat_fn = _dense_transparent(fn)
    jaxpr, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(*example_args)
    return Closure(fn, jaxpr, out_shape)


def _dense_transparent(fn: Callable) -> Callable:
    """Dense containers are pytrees, so jit/vmap handle them natively; this
    wrapper exists only to normalise plain-array returns to the caller's
    container convention (no-op for Dense-in/Dense-out programs)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        return fn(*args, **kwargs)

    return wrapped


class CallClosure:
    """The object returned by ``call(f)``.

    Invocation JIT-compiles ``f`` for the *current execution level* and caches
    the compiled executable per (level, mesh, kernel plane) — consulting
    :mod:`repro.core.registry` for the resolved backend plane, mirroring how
    ArBB re-optimises the captured IR "for the target architecture detected
    at runtime".
    At O3/O4 the arguments are placed with rank-heuristic shardings
    (:mod:`repro.core.sharding`) before dispatch, so XLA partitions the
    computation across the mesh without any change to the program text.
    """

    def __init__(self, fn: Callable, static_argnums: Sequence[int] = ()):
        self.fn = fn
        self.static_argnums = tuple(static_argnums)
        self._jitted: dict[Any, Callable] = {}

    def _get_executable(self, key) -> Callable:
        if key not in self._jitted:
            self._jitted[key] = jax.jit(
                _dense_transparent(self.fn), static_argnums=self.static_argnums
            )
        return self._jitted[key]

    def _retarget_key(self, ctx, mesh) -> tuple:
        """One executable per (level, mesh, kernel plane): retracing when the
        registry would resolve kernel ops differently keeps a compiled
        closure from baking in a stale variant choice."""
        from repro.core import registry
        return (ctx.level, id(mesh) if mesh is not None else None,
                registry.resolve_backend())

    def __call__(self, *args: Any):
        ctx = execlevel.current()
        if not ctx.is_distributed:
            return self._get_executable(self._retarget_key(ctx, None))(*args)
        mesh = ctx.mesh
        placed = []
        for i, a in enumerate(args):
            if i in self.static_argnums or not isinstance(a, (Dense, jax.Array)):
                placed.append(a)
                continue
            arr = unwrap(a)
            sh = shrules.auto_sharding(arr.shape, mesh)
            arr = jax.device_put(arr, sh)
            placed.append(Dense(arr) if isinstance(a, Dense) else arr)
        with jax.sharding.set_mesh(mesh):
            return self._get_executable(self._retarget_key(ctx, mesh))(*placed)

    def lower(self, *args: Any):
        """AOT-lower without executing (feeds the dry-run/roofline path)."""
        return jax.jit(_dense_transparent(self.fn),
                       static_argnums=self.static_argnums).lower(*args)

    def closure(self, *example_args: Any) -> Closure:
        return capture(self.fn, *example_args)


def call(fn: Callable, *, static_argnums: Sequence[int] = ()) -> CallClosure:
    """ArBB ``call()``: wrap a kernel function for JIT capture + execution."""
    return CallClosure(fn, static_argnums=static_argnums)


def emap(fn: Callable, in_axes: Sequence[Optional[int]]):
    """ArBB ``map()``: invoke a scalar function across container elements.

    ``in_axes[i] == 0``   -> argument i is consumed elementwise (scalar view).
    ``in_axes[i] is None`` -> argument i is captured whole (uniform).

    Returns a function of the same arity producing a Dense of results.  The
    paper's mod2as usage becomes::

        reduce = lambda matvals, invec, indx, ri, rj: ...scalar...
        outvec = emap(reduce, in_axes=(None, None, None, 0, 0))(
            matvals, invec, indx, rowpi, rowpj)
    """
    axes = tuple(in_axes)

    def mapped(*args):
        if len(args) != len(axes):
            raise TypeError(f"emap expected {len(axes)} args, got {len(args)}")
        vm = jax.vmap(_dense_transparent(fn), in_axes=axes)
        out = vm(*args)
        return out if isinstance(out, Dense) else Dense(jnp.asarray(unwrap(out)))

    return mapped
