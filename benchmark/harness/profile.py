"""The profiler around a slice of the window, and host spans on its clock."""

import contextlib
import glob
import os
import shutil


def span(name, **kw):
    """A host span on the device trace's clock (a no-op outside a
    trace)."""
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name, **kw)


class Tracer:
    """Traces the last ``length_s`` seconds of a window of ``seconds``
    into ``benchmark_out/trace`` (emptied first).  The last seconds,
    because stopping the profiler stalls the host for many seconds (27 s
    measured), and that must not fall inside the window."""

    def __init__(self, root, length_s, seconds):
        self.out_dir = os.path.join(root, "benchmark_out", "trace")
        self.length_s = min(length_s, seconds)
        self.start_s = seconds - self.length_s
        self.on = False
        self.done = False

    def poll(self, now_in_window):
        """Call between steps, with the time since the window's start.
        Starts the trace on time; the driver stops it at the window's
        end."""
        import jax

        if not self.on and not self.done and now_in_window >= self.start_s:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            os.makedirs(self.out_dir, exist_ok=True)
            kw = {}
            try:                      # the Python tracer slows the host
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                kw["profiler_options"] = opts
            except AttributeError:
                pass
            jax.profiler.start_trace(self.out_dir, **kw)
            self.on = True

    def stop(self):
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True

    def load(self):
        from . import trace_reduce

        paths = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        return trace_reduce.load(sorted(paths)[-1])


@contextlib.contextmanager
def quiet_gc():
    """Quiet this process's collector for the window."""
    import gc

    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()
