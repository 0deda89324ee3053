"""Compile requests as JAX itself reports them (``jax.monitoring``)."""


class CompileEvents:
    """Every request to compile a program ends as a hit or a miss of the
    persistent cache; both are a compile request inside the process."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    @property
    def compile_requests(self):
        return self.hits + self.misses

    def listen(self):
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
