"""Names of the serving loop's profiler spans.

``ServingEngine.step`` and ``Scheduler.admit`` open these with
``jax.profiler.TraceAnnotation`` (``serve.step`` with
``StepTraceAnnotation``), so a profiler trace holds them on the host
plane beside the device's programs, on the same clock. With the profiler
off a span costs about a microsecond and records nothing. Nesting::

    serve.step (step_num)
      serve.retire            lookahead and early retires
        serve.record_wait     blocking read of one step record
      serve.admit             Scheduler.admit
        serve.prefill         one admission group (bucket, size, rids)
      serve.dispatch          the fused serve step's dispatch
"""
STEP = "serve.step"
RETIRE = "serve.retire"
RECORD_WAIT = "serve.record_wait"
ADMIT = "serve.admit"
PREFILL = "serve.prefill"
DISPATCH = "serve.dispatch"

ALL = (STEP, RETIRE, RECORD_WAIT, ADMIT, PREFILL, DISPATCH)
