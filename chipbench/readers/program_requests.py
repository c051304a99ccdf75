"""The program's own request records (`telemetry.tracing.request_records`:
one per request that left the engine, stamped inside the program on the
window's clock): a percentile (ms) of ``end - start`` over the requests whose
``t_submit_call`` lies in the window and that reached both stamps."""
from chipbench.lib.trace import percentile
from chipbench.readers.program_steps import records


def read(obs, start, end, q):
    recs = records(obs, "request_records")
    v = [1e3 * (r[end] - r[start]) for r in recs or ()
         if r.get(start) is not None and r.get(end) is not None]
    return percentile(v, q) if v else None
