"""Unified host+device timeline: merge span traces with the XLA device
trace into ONE Chrome-trace JSON (open at https://ui.perfetto.dev →
"Open trace file", or chrome://tracing).

Both sources are in epoch µs: `telemetry.tracing` derives a span's start
from its `perf_counter` reading, and `profiler._ingest_device_trace`
rebases the XPlane device events by an epoch anchor (0.1-0.2 ms off on a
v5e, TELEMETRY.md) — so a serve request's prefill span sits above the
device slices it caused, to within that.
Lanes: pid 0 host op dispatch (when the profiler recorded it), pid 2
host spans (one lane per request via the ``lane`` attr, one per thread
otherwise), pid 1000+ the XLA device/runtime lanes.

Modes
-----
``--demo`` (default when no input is given)
    Run a small traced serving workload (tiny GPT through
    `mx.serve.ServeEngine` under `profiler.start()`/`stop()`) and write
    the merged timeline — this is how the committed example
    ``benchmark/trace_timeline_example.json`` is produced::

        python tools/trace_timeline.py -o benchmark/trace_timeline_example.json

``--flightrec FILE``
    Convert a crash flight-recorder dump (``benchmark/flightrec_*.json``)
    into a viewable timeline (no device lanes — the recorder snapshots
    spans only).

``--live``
    Export whatever the CURRENT process recorded (for use from a REPL /
    notebook after a traced run; from a fresh CLI process this is empty
    — prefer the API: ``tracing.dump_chrome(path)``).

``--fleet DIR``
    Stitch a directory of per-rank span dumps
    (``fleet_spans_rank*.json``, written by
    ``telemetry.fleet.dump_rank_trace()`` on every rank) into ONE
    timeline with a process lane per rank, timestamps rebased by each
    rank's estimated clock offset. Collective spans carry a
    ``coll_seq`` arg — barrier #N lines up vertically across lanes::

        python tools/trace_timeline.py --fleet /shared/fleet_traces -o fleet.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chrome_from_flightrec(payload):
    """Span dicts (flight-recorder schema) -> chrome trace events."""
    lanes: dict = {}

    def lane_tid(s):
        key = s.get("lane") or f"thread {s.get('thread')}"
        if key not in lanes:
            lanes[key] = len(lanes) + 1
        return lanes[key]

    events = []
    for s in payload.get("spans", []) + payload.get("open_spans", []):
        tid = lane_tid(s)
        args = {"trace_id": s.get("trace_id"), "span_id": s.get("span_id")}
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        args.update({k: str(v)[:120]
                     for k, v in (s.get("attrs") or {}).items()})
        events.append({"name": s["name"], "ph": "X", "pid": 2, "tid": tid,
                       "ts": s["ts_us"], "dur": s.get("dur_us") or 0,
                       "args": args})
        for ev in s.get("events", []):
            events.append({"name": ev["name"], "ph": "i", "s": "t",
                           "pid": 2, "tid": tid, "ts": ev["ts_us"],
                           "args": {k: str(v)[:120]
                                    for k, v in
                                    (ev.get("attrs") or {}).items()}})
    meta = [{"name": "process_name", "ph": "M", "pid": 2,
             "args": {"name": "host: spans (flight recorder)"}}]
    for key, tid in lanes.items():
        meta.append({"name": "thread_name", "ph": "M", "pid": 2,
                     "tid": tid, "args": {"name": str(key)}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def _demo_payload(requests=6, max_slots=2):
    """Traced tiny-GPT serving workload with a live device trace: the
    committed-example generator. Programs compile OUTSIDE the device
    trace window so the timeline shows steady-state serving."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    try:
        import numpy as onp

        from incubator_mxnet_tpu import profiler, serve
        from incubator_mxnet_tpu.models.gpt import gpt_tiny
        from incubator_mxnet_tpu.telemetry import tracing
    finally:
        sys.path.pop(0)

    tracing.enable()
    net = gpt_tiny(vocab_size=97, max_length=64, dropout=0.0)
    net.initialize()
    eng = serve.ServeEngine(net, max_slots=max_slots, max_len=64,
                            max_queue=64)
    rng = onp.random.RandomState(0)
    # warm the prefill buckets + decode program (compile stays out of the
    # recorded window)
    eng.generate(rng.randint(0, 97, (5,)).astype(onp.int32), 2)
    eng.generate(rng.randint(0, 97, (20,)).astype(onp.int32), 2)
    tracing.reset()                     # the example starts clean

    profiler.set_config(profile_imperative=False)
    profiler.start()
    handles = [eng.submit(rng.randint(0, 97,
                                      (int(rng.randint(3, 24)),))
                          .astype(onp.int32),
                          int(rng.randint(2, 10)))
               for _ in range(requests)]
    eng._drive_until(handles)           # noqa: SLF001 — demo driver
    profiler.stop()
    eng.shutdown(drain=True)
    failed = [h for h in handles if h.error is not None]
    if failed:
        raise RuntimeError(f"{len(failed)} demo requests failed: "
                           f"{failed[0].error}")
    payload = tracing.chrome_trace(include_device=True)
    tracing.disable()
    n_dev = sum(1 for e in payload["traceEvents"]
                if e.get("pid", 0) >= 1000 and e.get("ph") == "X")
    n_spans = sum(1 for e in payload["traceEvents"]
                  if e.get("pid") == 2 and e.get("ph") == "X")
    print(f"demo: {len(handles)} requests, {n_spans} host spans, "
          f"{n_dev} device events", file=sys.stderr)
    return payload


def clip_to_spans(payload, margin_us=1000.0, drop_python_lane=True):
    """Trim a demo/committed artifact: drop device events outside the
    span window (±margin) — the raw XPlane trace records the whole
    start()/stop() interval including runtime bookkeeping — and (by
    default) the jax profiler's per-frame *python* lane, which
    duplicates the span story at tens of thousands of events. Metadata
    rows and every span survive; the trim is recorded in the trace
    itself as a ``clip_note`` metadata event (a trimmed artifact must
    say so)."""
    ev = payload["traceEvents"]
    span_ts = [e["ts"] for e in ev if e.get("pid") == 2
               and e.get("ph") == "X"]
    if not span_ts:
        return payload
    lo = min(span_ts) - margin_us
    hi = max(e["ts"] + e.get("dur", 0) for e in ev
             if e.get("pid") == 2 and e.get("ph") == "X") + margin_us
    python_tids = set()
    if drop_python_lane:
        python_tids = {(e.get("pid"), e.get("tid")) for e in ev
                       if e.get("ph") == "M"
                       and e.get("name") == "thread_name"
                       and e.get("pid", 0) >= 1000
                       and "python" in str(
                           e.get("args", {}).get("name", "")).lower()}
    kept, dropped = [], 0
    for e in ev:
        if e.get("pid", 0) >= 1000 and e.get("ph") != "M":
            ts = e.get("ts")
            if (ts is not None and not lo <= ts <= hi) \
                    or (e.get("pid"), e.get("tid")) in python_tids:
                dropped += 1
                continue
        kept.append(e)
    kept.append({"name": "clip_note", "ph": "M", "pid": 2,
                 "args": {"note": f"{dropped} device-lane events were "
                                  "trimmed (outside the span window, or "
                                  "the python frame lane) — "
                                  "tools/trace_timeline.py clip_to_spans"}})
    return {"traceEvents": kept,
            "displayTimeUnit": payload.get("displayTimeUnit", "ms")}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merged host-span + XLA-device Chrome trace "
                    "(see module docstring)")
    ap.add_argument("-o", "--out", default="trace_timeline.json",
                    help="output Chrome-trace JSON path")
    ap.add_argument("--flightrec", default=None,
                    help="convert a flightrec_*.json dump instead of "
                         "running the demo workload")
    ap.add_argument("--live", action="store_true",
                    help="export this process's recorded spans as-is")
    ap.add_argument("--fleet", default=None, metavar="DIR",
                    help="stitch per-rank fleet_spans_rank*.json dumps "
                         "from DIR into one multi-lane timeline")
    ap.add_argument("--demo", action="store_true",
                    help="run the traced tiny-GPT serving demo (default)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--no-clip", action="store_true",
                    help="keep device events outside the span window "
                         "(demo mode clips them by default)")
    args = ap.parse_args(argv)

    if args.fleet:
        sys.path.insert(0, REPO)
        try:
            from incubator_mxnet_tpu.telemetry import fleet
        finally:
            sys.path.pop(0)
        payload = fleet.stitch_traces(args.fleet)
        meta = payload.get("fleet", {})
        print(f"stitched {meta.get('n_ranks')} rank(s), "
              f"{meta.get('n_spans')} spans, clock-offset bound "
              f"{meta.get('offset_bound_s')}s")
    elif args.flightrec:
        with open(args.flightrec) as f:
            payload = _chrome_from_flightrec(json.load(f))
    elif args.live:
        sys.path.insert(0, REPO)
        try:
            from incubator_mxnet_tpu.telemetry import tracing
        finally:
            sys.path.pop(0)
        payload = tracing.chrome_trace(include_device=True)
    else:
        payload = _demo_payload(requests=args.requests)
        if not args.no_clip:
            payload = clip_to_spans(payload)

    with open(args.out, "w") as f:
        json.dump(payload, f)
    print(f"wrote {args.out} ({len(payload['traceEvents'])} events) — "
          "open at https://ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
