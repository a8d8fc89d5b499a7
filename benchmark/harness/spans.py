"""The benchmark's own spans around its calls into the program.

Each span is kept on the host clock (name, start, end; written out when the
run ends) and, in a traced run, is also a ``jax.profiler.TraceAnnotation``,
so that it sits on the profiler's clock beside the device's operations and
an idle gap can be laid to what the host was doing."""

import contextlib
import threading
import time


class Spans:
    def __init__(self):
        self.records = []          # (name, t0, t1, thread name)
        self.annotate = False      # True while the profiler is tracing

    @contextlib.contextmanager
    def span(self, name):
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(name)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
            self.records.append((name, t0, t1,
                                 threading.current_thread().name))

    def durations_ms(self, name, since=None, until=None):
        return [(t1 - t0) * 1e3 for n, t0, t1, _ in self.records
                if n == name and (since is None or t0 >= since)
                and (until is None or t1 <= until)]
