"""Backend-compile seconds as JAX reports them (persistent-cache hits
compile nothing and add nothing)."""
from __future__ import annotations


class CompileClock:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]
