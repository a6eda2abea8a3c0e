"""What every driver needs around the program: the host spans, the clock of
set-up, the traced sub-window, compile counting, the device's description."""
import contextlib
import os
import shutil
import time

from benchmark.harness import trace as trace_mod


class Spans:
    """Host spans on ``time.perf_counter``: (name, start_s, end_s), kept in
    memory. One list append per span, so they are on in every run."""

    def __init__(self):
        self.rows = []

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def named(self, name, lo=None, hi=None):
        return [(s, e) for n, s, e in self.rows if n == name
                and (lo is None or s >= lo) and (hi is None or e <= hi)]


class CompileCounter:
    """Counts the programs JAX lowers (every new jit specialisation lowers
    once, whether its executable then comes from the cache or the compiler)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


class Run:
    """One run's shared state; the driver fills it, ``run.py`` reads it."""

    def __init__(self, *, t_process, args, cell, workload, config, peaks,
                 devices, scratch):
        self.t_process = t_process
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.cell = cell
        self.workload = workload
        self.config = config
        self.peaks = peaks
        self.devices = devices
        self.scratch = scratch
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.reference_s = 0.0
        self.t_window = None
        self.trace = None
        self._trace_dir = os.path.join(scratch, "trace")

    @contextlib.contextmanager
    def reference(self):
        """Time spent in the plain reference: not the program's set-up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.reference_s += time.perf_counter() - t0

    def window_opens(self):
        """Call at the first instant of the measured window."""
        self.t_window = time.perf_counter()
        self.compiles_before = self.compiles.count
        return self.t_window

    @property
    def setup_s(self):
        return self.t_window - self.t_process - self.reference_s

    def window_closes(self):
        """Call at the last instant of the measured window; returns its
        length in seconds."""
        self.window_s = time.perf_counter() - self.t_window
        self._lowered = self.compiles.count - self.compiles_before
        return self.window_s

    def compiles_in_window(self):
        return self._lowered

    # -- the traced sub-window ------------------------------------------
    def trace_start(self):
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_ANNOTATION)
        self._ann.__enter__()
        self._trace_t0 = time.perf_counter()

    def trace_stop(self):
        import jax

        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.spans.rows.append(
            (trace_mod.WINDOW_ANNOTATION, self._trace_t0, t1))
        jax.profiler.stop_trace()

    def reduce_trace(self, **kw):
        """After the window: the trace as numbers (raises TraceError)."""
        path = trace_mod.newest_xplane(self._trace_dir)
        self.trace = trace_mod.reduce_file(
            path, host_spans=self.spans.rows, n_devices=len(self.devices),
            **kw)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        return self.trace


def device_description(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the backend counts them."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if any(p is None for p in peaks):
        raise RuntimeError("a device reports no peak_bytes_in_use")
    return int(max(peaks))
