"""The measured window of one run: its clock, the set-up time before it,
the profiler in a traced run, the host-span tracer (in a traced run, and
in any run whose job asks for spans), compilations counted inside it,
and the device memory peak after it."""
from __future__ import annotations

import os
import shutil
import time
from typing import Optional

import jax

#: host annotation that brackets the window in the profiler's trace
MARK = "bench.window"


class Window:
    def __init__(self, t_start: float, trace_dir: Optional[str] = None,
                 spans: bool = False):
        self.t_start = t_start          # process start on ``clock``
        self.trace_dir = trace_dir
        self.tracer = None
        if trace_dir is not None or spans:
            from repro.obs.spans import Tracer
            self.tracer = Tracer(clock=self.clock)
        self.setup_s: Optional[float] = None
        self.t0 = self.t1 = None
        self.compiles = 0
        self._counting = False
        self._installed = None
        self._ann = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_stop_s: Optional[float] = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @staticmethod
    def clock() -> float:
        return time.perf_counter()

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self._counting and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def setup_done(self) -> None:
        self.setup_s = self.clock() - self.t_start

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        if self.setup_s is None:
            self.setup_done()
        if self.tracer is not None:
            from repro.obs import spans
            self._installed = spans.install(self.tracer)
            self._installed.__enter__()
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            # no Python tracer: it records every Python call of the host
            # loop, slows it, and makes the trace take minutes to write
            # and read
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(MARK)
        self._counting = True
        self.t0 = self.clock()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.t1 = self.clock()
        self._counting = False
        if self.trace_dir is not None:
            t = self.clock()
            jax.profiler.stop_trace()
            self.trace_stop_s = self.clock() - t
        if self._installed is not None:
            self._installed.__exit__(None, None, None)
        return False

    def read_memory(self) -> None:
        """Peak bytes in use on the fullest chip, where the backend says."""
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None
