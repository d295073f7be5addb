"""Shared utilities for the Pallas kernels.

One copy of the interpret-mode default (:func:`resolve_interpret`): an
explicit ``interpret=`` argument at a call site wins; otherwise a kernel
interprets exactly when the default backend is CPU, which has no Pallas
lowering.  On an accelerator nothing but an explicit ``interpret=True``
puts a kernel in the interpreter, so a chip run always executes the
compiled (Mosaic) kernel it asked for.

The CPU arm is a perf footgun: the interpreter is orders of magnitude
slower than a compiled lowering.  The first silent fallback per process
emits one ``RuntimeWarning`` plus a
``repro_kernel_interpret_fallbacks_total`` counter tick (every fallback
counts; only the warning is once-per-process).  Explicit requests never
warn, and test runs (``PYTEST_CURRENT_TEST`` set) stay quiet: differential
tests pin interpret mode on purpose.
"""

from __future__ import annotations

import os
import warnings

import jax

__all__ = ["note_trace", "resolve_interpret"]

_fallback_warned = False


def _note_interpret_fallback() -> None:
    global _fallback_warned
    from repro.obs import global_obs

    global_obs().metrics.counter(
        "repro_kernel_interpret_fallbacks_total").inc()
    if _fallback_warned or "PYTEST_CURRENT_TEST" in os.environ:
        return
    _fallback_warned = True
    warnings.warn(
        "no compiled Pallas lowering for this host (default backend is "
        "cpu): kernels will run in INTERPRET mode, which is orders of "
        "magnitude slower.  Use the compiled 'xla' fused backend "
        "(fused_backend='xla' / --fused-backend xla, the CPU auto-dispatch "
        "default), or pass interpret=True explicitly.",
        RuntimeWarning, stacklevel=3,
    )


def resolve_interpret(interpret: bool | None = None, *,
                      quiet: bool = False) -> bool:
    """Resolve a kernel's interpret-mode flag (see module docstring).

    ``quiet=True`` suppresses the silent-fallback warning/counter — for
    *probes* (e.g. the executor's fused auto-dispatch asking "would Pallas
    interpret here?") that make a decision rather than run a kernel.
    """
    if interpret is not None:
        return bool(interpret)
    fallback = jax.default_backend() == "cpu"
    if fallback and not quiet:
        _note_interpret_fallback()
    return fallback


def note_trace(kernel: str) -> None:
    """Count one *trace* of a kernel wrapper in the process-global metrics.

    Kernel wrappers run at jax trace time, inside ``jit`` — once per new
    shape, not once per device launch — so the counter is named
    ``repro_kernel_traces_total``: it measures how often XLA had to rebuild
    a kernel, which is exactly the jit-cache-health signal (launch counts
    live in ``repro_mining_launches_total``, emitted host-side by the
    executor).  The import is lazy and the global default is a no-op
    bundle, so the disabled-mode cost is one function call per trace.
    """
    from repro.obs import global_obs

    global_obs().metrics.counter("repro_kernel_traces_total",
                                 kernel=kernel).inc()
