"""repro.obs — unified metrics / span / sentinel telemetry for the stack.

One dependency-free layer that every subsystem (core FZ, kernels, kvpool,
bucketed reduce, trainer, engine, launchers) reports into, replacing the
per-module ad-hoc counters. Four pieces:

  * :mod:`registry`  — counters / gauges / log-bucketed histograms, labeled,
    process-wide, snapshot-able to a plain dict (``obs.snapshot()``);
  * :mod:`spans`     — ``with obs.span("kvpool.park", pages=n):`` nested
    timed scopes feeding the histograms, a bounded event ring, and
    ``jax.named_scope`` + ``jax.profiler.TraceAnnotation`` so the same names
    appear in real XLA profiles;
  * :mod:`trace`     — exporter: Chrome ``trace_event`` JSON;
  * :mod:`sentinels` — always-on health monitors (error-bound violations,
    ratio drift, scheduler starvation) behind ``obs.assert_healthy()``.

How to read a StepReport
------------------------
``obs.step_report()`` returns one row per span name: call count, p50/p99/max
milliseconds, and total time; pass ``bytes_by_tag=`` (from
``hlo_cost.analyze(...)["cross_pod_by_tag"]`` or
``bucketed_reduce.expected_cross_pod_bytes``) and rows whose span name
carries a matching tag (e.g. ``dist.bucket0_reduce``) gain a bytes column
and the implied GB/s. That turns "did the per-bucket all-gather hide under
backward?" into a table scan: a hidden transfer's span time is small while
its bytes are large (high effective GB/s because the wall-clock was paid by
overlapped compute); a serialized one shows GB/s near the raw link rate.
``report.render()`` prints it; ``--metrics-out`` JSONs it.

How to open the trace in Perfetto
---------------------------------
Run any launcher (or ``examples/serve_compressed_kv.py``) with
``--trace-out trace.json``, then load the file at https://ui.perfetto.dev
(or ``chrome://tracing``). Eager spans are complete events nested by
timestamp on one track per thread; category ``jit-trace`` marks
once-per-compilation spans recorded while jax was tracing a region (they
sit inside the eager span that triggered compilation — that is where the
``engine -> kvpool -> fz -> kernel-stage`` nesting comes from, since the
kernel stages only execute inside ``jit``). On real hardware add
``--profile-dir`` to capture a full ``jax.profiler`` trace with the same
span names as XLA annotations.

What each sentinel means
------------------------
  * ``sentinel_eb_violations{tier=...}`` — a sampled container decompressed
    to more than the configured error bound (plus the documented f32
    rounding allowance). Always a bug: the compressor's contract is broken.
    ``assert_healthy()`` raises on it; the scheduler and trainer call that
    hook every step.
  * ``sentinel_ratio_drift{tier=...}`` — the achieved compression ratio
    moved more than ``ratio_drift_factor``x from its EWMA for a tier
    (``wire`` gradient hops / ``kv_cold`` parked pages /
    ``kv_cold_entropy`` entropy-coded parked blobs / ``ckpt``
    checkpoints). A flag, not a failure: it usually means the data
    distribution changed (warmup gradients, new workload), but a sudden
    drift is the first symptom of a mis-resolved bound.

Cold-tier entropy counters
--------------------------
``fz.to_bytes`` / ``fz.from_bytes`` bump
``entropy_stage{op=encode|decode, selected=true|false, tier=...}`` — one
increment per serialized container, labeled with whether the probe selected
the entropy stage and which tier asked (``kv_cold_entropy``, ``ckpt``, or
``adhoc`` for untiered calls). The serializers deliberately do *not* feed
the ratio EWMAs; callers sample ``note_ratio`` at their own cadence (the
pool inside its sentinel check, the checkpointer once per save) so
legitimate per-container variance cannot trip the drift sentinel.
  * ``sched_waiting / sched_running / sched_parked / sched_max_wait_steps``
    — serving queue depths and the starvation high-water (longest any
    request waited for admission), sampled every scheduler step.

jit discipline: spans entered while jax is tracing record no runtime state
(see :mod:`spans`); instrumented hot paths stay retrace-free and the
compiled programs are bit-identical with obs on or off. ``obs.disabled()``
suspends all recording — the bench tier uses it to pin the instrumentation
overhead under 5%.
"""
from .registry import (DEFAULT, Registry, counter, disabled, enabled,  # noqa: F401
                       gauge, histogram, reset, set_enabled, snapshot)
from .report import StepReport, step_report  # noqa: F401
from .sentinels import (CONFIG, HealthError, SentinelConfig,  # noqa: F401
                        assert_healthy, check_error_bound, configure,
                        note_ratio, note_scheduler, should_check_eb,
                        violations)
from .spans import (clear_events, current_stack, events,  # noqa: F401
                    ring_capacity, set_ring_capacity, span)
from .trace import chrome_trace, write_chrome_trace  # noqa: F401
