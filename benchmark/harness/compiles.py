"""XLA backend compiles of this process, counted through ``jax.monitoring``
(as ``chip_smoke._count_backend_compiles``): the event fires for a cache
load as well, so a count of 0 over the window means no program was built or
loaded inside it."""

import time

EVENT = "/jax/core/compile/backend_compile_duration"


def count_backend_compiles():
    """A list that grows by one host-clock stamp per backend compile from
    now on."""
    import jax.monitoring

    seen = []

    def _on(event, _secs, **_kw):
        if event == EVENT:
            seen.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(_on)
    return seen
