"""Starting the profiler the way every driver does: device trace and the
benchmark's own ``TraceAnnotation`` spans, without the Python tracer (its
per-call events would swamp the trace and slow the host)."""

from __future__ import annotations


def start_trace(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
