"""Count the programs JAX builds while active, split by where they came from.

Every program JAX builds fires one backend-compile event.  A build that the
persistent cache answers fires a cache-hit event first, in the same thread:
that program was compiled before (in set-up) and only fell out of JAX's
in-memory caches, so it is reloaded, not compiled.  ``count`` holds the
builds the compiler had to make; ``reloads`` the ones read back from disk.
"""

from __future__ import annotations

import threading
import traceback

from jax import monitoring

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


def _caller() -> str:
    """The innermost frame of the program (``repro``) that asked for the
    build, so a build in the window names its call site."""
    for frame in reversed(traceback.extract_stack()):
        if "/repro/" in frame.filename:
            return f"{frame.filename.split('/repro/')[-1]}:{frame.lineno}"
    return "?"


class CompileCounter:
    _installed = None
    _hit = threading.local()

    def __init__(self):
        self.active = False
        self.count = 0
        self.names: dict = {}
        self.reloads = 0
        self.reload_names: dict = {}
        self._lock = threading.Lock()
        if CompileCounter._installed is None:
            monitoring.register_event_listener(CompileCounter._on_event)
            monitoring.register_event_duration_secs_listener(CompileCounter._hook)
        CompileCounter._installed = self

    def reset(self) -> None:
        self.count, self.names, self.reloads, self.reload_names = 0, {}, 0, {}

    @staticmethod
    def _on_event(event, **kw):
        if event == _HIT:
            CompileCounter._hit.flag = True

    @staticmethod
    def _hook(event, duration, **kw):
        if event != _COMPILE:
            return
        hit = getattr(CompileCounter._hit, "flag", False)
        CompileCounter._hit.flag = False
        self = CompileCounter._installed
        if self is None or not self.active:
            return
        name = f"{kw.get('fun_name', '?')} at {_caller()}"
        with self._lock:
            if hit:
                self.reloads += 1
                self.reload_names[name] = self.reload_names.get(name, 0) + 1
            else:
                self.count += 1
                self.names[name] = self.names.get(name, 0) + 1
