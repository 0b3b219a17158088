"""Every XLA compile of the process, tagged with the phase that caused it
(copied from ``chip_smoke.py``'s ``CompileLog``: JAX reports each backend
compile through ``jax.monitoring``)."""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self, jax):
        self.phase = "setup"
        self.events = []  # (phase, program, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **kw):
        if event == COMPILE_EVENT:
            self.events.append((self.phase, kw.get("fun_name", "?"), seconds))

    def in_phase(self, phase: str):
        return [(n, s) for p, n, s in self.events if p == phase]
