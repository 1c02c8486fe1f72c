"""What both runners need from the process that owns the chip: the
program's model class built from a configuration file, a count of
compilations, the devices' memory peak and the traced window."""

from __future__ import annotations

import importlib
import threading
from typing import Any, Dict, Optional


def model_config(config: Dict[str, Any]):
    """The program's model class built from the published keys."""
    mod, _, cls = config["model_class"].rpartition(".")
    klass = getattr(importlib.import_module(mod), cls)
    c = config
    kw = dict(vocab_size=c["vocab_size"], dim=c["hidden_size"],
              n_layers=c["num_hidden_layers"],
              n_heads=c["num_attention_heads"],
              n_kv_heads=c["num_key_value_heads"],
              mlp_dim=c["intermediate_size"],
              rope_theta=float(c["rope_theta"]),
              norm_eps=float(c["rms_norm_eps"]),
              tie_embeddings=bool(c["tie_word_embeddings"]))
    kw.update(config.get("model_options", {}))
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            import jax.numpy as jnp

            kw[key] = getattr(jnp, kw[key])
    return klass(**kw)


class CompileCounter:
    """Programs this process compiled, or read from the persistent cache
    in the compiler's place: each one is a program that was not ready.
    The count inside the measured window has to be 0."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def memory_peak_bytes(devices) -> Optional[int]:
    """peak_bytes_in_use of the fullest device (None off an accelerator)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max([p for p in peaks if p is not None], default=None)


class TraceWindow:
    """The profiler over one stretch of a run, in the process that owns
    the chip.  The Python tracer is off: it slows the host's threads;
    host spans (TraceAnnotation) are still recorded.  A thread of this
    class holds the span ``bench.trace_window`` open from just after
    the profiler started to just before it stops: that span, on the
    trace's own clock, is the window trace_reduce measures idle time
    over."""

    def __init__(self, trace_dir: str, *, allow_empty: bool = False):
        self.trace_dir, self.allow_empty = trace_dir, allow_empty
        self._open = threading.Event()
        self._close = threading.Event()
        self._thread = threading.Thread(target=self._hold, daemon=True)

    def _hold(self) -> None:
        import jax

        from benchmarks.harness import trace_reduce

        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            self._open.set()
            self._close.wait()

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._thread.start()
        self._open.wait()

    def stop(self) -> None:
        import jax

        self._close.set()
        self._thread.join()
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[Dict[str, Any]]:
        """What the stopped profiler wrote, reduced: seconds of work,
        so after the measured window."""
        from benchmarks.harness import trace_reduce

        return trace_reduce.load_and_reduce(
            self.trace_dir, allow_empty=self.allow_empty)
