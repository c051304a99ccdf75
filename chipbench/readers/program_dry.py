"""The program's own account of when the device had nothing queued
(`telemetry.tracing.step_records`, fields ``dry_<cause>``: seconds of the dry
intervals that an iteration's launch ended, stamped inside the program on
``time.perf_counter()`` with no profiler attached): the named causes' summed
seconds over the records of the window, as a share (%) of the window. One
cause or a list. A program whose records carry no such field reads nothing."""
from chipbench.readers.program_steps import records


def read(obs, cause):
    recs = records(obs, "step_records")
    fields = ["dry_" + c for c in
              ([cause] if isinstance(cause, str) else cause)]
    w = obs["window"]
    span = w["t_close"] - w["t_open"]
    if not recs or span <= 0 or any(f not in recs[0] for f in fields):
        return None
    return 100.0 * sum(r[f] for r in recs for f in fields) / span
