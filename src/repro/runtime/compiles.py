"""Which programs JAX lowers, and how long compiling takes, from JAX's own
monitoring events.

One listener, installed once per process, keeps every lowering's function
name (``jit_<fn>``, the same name the program carries in a profiler trace)
in order, and sums the seconds of lowering and of the backend compile.
Tracing nests inside other work, so it is left out. Callers take a
``mark()`` before the work they watch and read ``since(mark)`` after it:
``LDAEngine.fit`` reports the names lowered during the call as
``history["lowered"]``.

A lowering happens on every miss of a function's in-memory cache, with or
without a hit in the persistent compilation cache, so an empty
``since(mark)`` means nothing was compiled or loaded in between.
"""

from __future__ import annotations

import re
import threading

import jax

__all__ = ["LOWER_EVENT", "COMPILE_EVENT", "mark", "since", "seconds",
           "LateLowerings"]

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = False
_lowered: list[str] = []
_seconds = [0.0]


def _program_name(fun_name: str) -> str:
    """``jit(word_stats)`` -> ``jit_word_stats``: the event's function
    name as the lowered module (and the profiler trace) spells it."""
    return re.sub(r"^(\w+)\((.*)\)$", r"\1_\2", fun_name)


def _on_event(event: str, duration: float, **kw) -> None:
    if event == LOWER_EVENT:
        with _lock:
            _lowered.append(_program_name(str(kw.get("fun_name", "?"))))
            _seconds[0] += duration
    elif event == COMPILE_EVENT:
        with _lock:
            _seconds[0] += duration


def _install() -> None:
    """Register the listener (once; later calls do nothing)."""
    global _installed
    with _lock:
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _installed = True


def mark() -> int:
    """Position in the process's list of lowerings (installs the listener)."""
    _install()
    with _lock:
        return len(_lowered)


def since(position: int) -> list[str]:
    """Function names lowered after ``position``, in order."""
    with _lock:
        return _lowered[position:]


def seconds() -> float:
    """Seconds spent lowering and compiling since the listener went in."""
    _install()
    with _lock:
        return _seconds[0]


class LateLowerings:
    """Names, through ``log_fn``, each program lowered after a training
    call's first iteration (and its evaluation): in a steady loop nothing
    lowers there, so a name in the log is the step that recompiled.

    ``settle(it)`` is called at the end of every iteration or chunk; the
    first call only takes the mark."""

    def __init__(self, log_fn):
        self.log_fn = log_fn
        self._mark: int | None = None

    def settle(self, it: int) -> None:
        if self._mark is not None and self.log_fn is not None:
            names = since(self._mark)
            if names:
                self.log_fn(f"iter={it:4d} lowered after the first "
                            f"iteration: {', '.join(names)}")
        self._mark = mark()
