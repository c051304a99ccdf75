"""The device's idle time, checked against the program's own account: the
share (%) of the traced stretch's idle nanoseconds on the first device (the
stretch less the union of its ``XLA Ops``) that lie inside a dry interval of
the program's step records (`telemetry.tracing.step_records`, field ``dry``:
``[t0, t1, cause]`` on ``time.perf_counter()``), whatever the cause.

The two clocks meet in the one anchor the harness holds: the traced stretch
on ``perf_counter`` (``obs["trace_clock"]``) is the ``cb.window`` span in the
trace's nanoseconds (``Trace.lo`` / ``.hi``); a stamp is mapped linearly
between the two ends. Nothing to read without a trace, without the anchor,
or from a program whose records keep no intervals."""
from chipbench.lib.trace import clip, subtract, total, union
from chipbench.readers.program_steps import records


def to_trace_ns(clock, trace):
    """``perf_counter`` seconds -> the trace's nanoseconds."""
    (t_lo, t_hi), lo, hi = clock, trace.lo, trace.hi
    scale = (hi - lo) / (t_hi - t_lo)
    return lambda t: lo + (t - t_lo) * scale


def dry_intervals(obs):
    """The window's dry intervals in the trace's nanoseconds, merged and cut
    to the traced stretch; None where the program keeps none."""
    recs = records(obs, "step_records")
    if not recs or "dry" not in recs[0]:
        return None
    trace = obs["trace"]
    ns = to_trace_ns(obs["trace_clock"], trace)
    return clip(union([[ns(t0), ns(t1)] for r in recs
                       for t0, t1, _ in r["dry"]]), trace.lo, trace.hi)


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs.get("trace_clock"):
        return None
    dry = dry_intervals(obs)
    if dry is None:
        return None
    dev = sorted(trace.devices)[0]
    idle = subtract([[trace.lo, trace.hi]], trace.busy_intervals(dev))
    if not total(idle):
        return None
    return 100.0 * (1.0 - total(subtract(idle, dry)) / total(idle))
