"""The program's own step records (`telemetry.tracing.step_records`: one per
iteration of `Scheduler.step` that made progress, stamped inside the program
on ``time.perf_counter()``, the window's clock) over the window: a percentile
(ms) of one field less others, or the named fields' share (%) of the summed
``wall``. A program without the records reads nothing."""
from chipbench.lib.trace import percentile


def records(obs, name):
    """The program's `name` records whose key stamp lies in the window, or
    None where the program keeps none."""
    try:
        from incubator_mxnet_tpu.telemetry import tracing
    except ImportError:
        return None
    read = getattr(tracing, name, None)
    w = obs["window"]
    return read(w["t_open"], w["t_close"]) if read else None


def read(obs, field, q=None, minus=(), per=None):
    recs = records(obs, "step_records")
    fields = [field] if isinstance(field, str) else list(field)
    if not recs:
        return None
    if per is not None:
        whole = sum(r[per] for r in recs)
        return 100.0 * sum(r[f] for r in recs for f in fields) / whole \
            if whole else None
    # a step that never ran the phase (no decode launch) has no reading
    v = [1e3 * (r[fields[0]] - sum(r[m] for m in minus))
         for r in recs if r[fields[0]] > 0.0]
    return percentile(v, q) if v else None
