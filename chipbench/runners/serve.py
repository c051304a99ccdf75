"""A served model under a traffic file: `ServeEngine.submit` -> the `start()`
driver thread -> `iter_tokens`, as a user of `mx.serve` drives it.

Set-up (`start`) builds the block from the configuration file, fills it from
the seed, starts the engine and warms the cell's own prefill buckets and the
decode program. `window` lets the traffic ramp until the slots are in mixed
phases, then measures `seconds` of that same traffic. What the window served
is then compared with the family's plain reference (see `check`).
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as onp

from chipbench.lib import harness, loadgen, seeded

COUNTERS = ("mx_serve_prefix_tokens_total", "mx_serve_prefill_chunks_total",
            "mx_serve_tokens_total", "mx_decode_bucket_pad_tokens_total")


def build_engine(spec, seed):
    """The program's side: a `GPTModel` of the configuration's sizes behind a
    `ServeEngine` with the configuration's engine settings."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import gpt

    cfg = spec.config
    ref = harness.module_of("reference", cfg["family"], spec.root)
    n_layer, c, n_head, f, v, n_pos = ref.sizes(cfg)
    net = gpt.GPTModel(v, c, f, n_layer, n_head, n_pos, dropout=0.0)
    net.setattr("grad_req", "null")      # a served model keeps no gradients
    seeded.fill(net, ref.leaves(cfg), seed)
    eng = mx.serve.ServeEngine(net, **cfg["engine"])
    # The engine stacks every layer's weights into arrays of its own and
    # never reads the block's per-layer buffers again; at 1.56 B float32
    # parameters the two copies and the KV pool do not fit one chip. Free the
    # block's copies (PERF.md, Open questions: the program should).
    for name, p in net.collect_params().items():
        if name.startswith("blocks."):
            p.data()._data.delete()  # noqa: SLF001
    return net, eng


def free(net, slots):
    """Give back every device buffer of the program: the reference runs
    after the peak was read, on an emptied chip."""
    import jax

    for leaf in jax.tree.leaves(slots._dec._params):  # noqa: SLF001
        if not leaf.is_deleted():
            leaf.delete()
    for p in net.collect_params().values():
        if not p.data()._data.is_deleted():  # noqa: SLF001
            p.data()._data.delete()  # noqa: SLF001
    gc.collect()


def counters():
    from incubator_mxnet_tpu.telemetry import registry

    return {name: registry.counter(name).value for name in COUNTERS}


def warm(eng, seed, vocab):
    """One request per prefill bucket (its last chunk lands in that bucket)
    and a few decode steps: every program the window can touch."""
    slots = eng._sched.slots  # noqa: SLF001
    rng = onp.random.default_rng([int(seed), 0xA])
    client = loadgen.Client(eng)
    reqs = []
    for i, b in enumerate(slots.chunk_buckets):
        n = slots.prefill_chunk + max(2, b - 3)
        reqs.append(client.submit(loadgen.Req(
            -1 - i, rng.integers(0, vocab, n).astype(onp.int32), 4, False)))
    for r in reqs:
        if not r.done.wait(1100.0) or r.error is not None:
            raise RuntimeError(f"warm-up request failed: {r.error!r}")
    client.join(30.0)


def start(env):
    """Set-up as far as a warm, running engine. In a traced run the
    benchmark's spans go around the engine's calls, from outside."""
    net, eng = build_engine(env.spec, env.seed)
    env.mark("block filled from the seed, engine built")
    slots = eng._sched.slots  # noqa: SLF001
    calls = {"decode": [], "prefill": []}
    eng.start()
    try:
        warm(eng, env.seed, env.spec.config["vocab_size"])
    except BaseException:
        eng.shutdown(drain=False)
        raise
    env.mark("prefill buckets and decode warmed")
    if env.trace:
        spans = env.spans
        spans.wrap(eng, "step", "cb.serve.step")
        spans.wrap(slots, "decode_step", "cb.serve.decode_step",
                   lambda last, pos, active, *a: calls["decode"].append(
                       (time.perf_counter(),
                        [int(p) + 1 for p, on in zip(pos, active) if on])))
        spans.wrap(slots, "prefill_chunk_step", "cb.serve.prefill_chunk",
                   lambda slot, chunk, t_start, *a, **k:
                   calls["prefill"].append(
                       (time.perf_counter(), int(t_start), len(chunk))))
    return SimpleNamespace(net=net, eng=eng, slots=slots, calls=calls)


def window(env, live, traffic, seed):
    """The ramp, then `env.seconds` of the traffic against the running
    engine. Every request due in the window is owed a first token: it is
    waited for, up to 60 s past the close (a late answer is late, and its
    latency says so). Returns the window's readings and what it finished."""
    eng = live.eng
    client = loadgen.Client(eng)
    gen = loadgen.Generator(
        client, traffic,
        loadgen.requests(traffic, seed, env.spec.config["vocab_size"]))
    gen.start()
    time.sleep(traffic["ramp_s"])
    in_window = env.watch.snapshot()
    c0 = counters()
    before = {id(r): r.handle.prefill_pos for r in list(client.sent)
              if r.handle is not None}
    env.open_window()
    t_open = time.perf_counter()
    gen.open(t_open)
    time.sleep(max(0.0, t_open + env.seconds - time.perf_counter()))
    t_close = time.perf_counter()
    sent = list(client.sent)
    after = {id(r): r.handle.prefill_pos for r in sent if r.handle is not None}
    c1 = counters()
    compiled = env.watch.since(in_window)
    gen.stop()

    due = [r for r in sent if t_open <= r.due < t_close]
    t_end = time.monotonic() + 60.0
    for r in due:
        while not r.token_times and not r.done.is_set() \
                and time.monotonic() < t_end:
            time.sleep(0.01)
    # what the window finished: done by now, its last token not before the
    # window opened
    finished = [r for r in sent if r.done.is_set() and r.error is None
                and r.token_times and r.token_times[-1] >= t_open]

    in_win = lambda t: t_open <= t < t_close  # noqa: E731
    # prompt tokens that entered the KV cache in the window, by the handles'
    # prefill positions at its open and close
    prefilled = sum(hi - before.get(rid, 0) for rid, hi in after.items())
    out_tokens, itl = 0, []
    for r in sent:
        ts = r.token_times
        out_tokens += sum(1 for t in ts if in_win(t))
        itl += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if in_win(b)]
    readings = {
        "wall_s": t_close - t_open, "t_open": t_open, "t_close": t_close,
        "out_tokens": out_tokens, "prefill_tokens": prefilled,
        "tokens": out_tokens + prefilled,
        "itl_ms": itl,
        "ttft_ms": [(r.token_times[0] - r.due) * 1e3 for r in due
                    if r.token_times],
        "late_ms": [s * 1e3 for s in gen.late_s],
        "submit_wait_ms": [(r.submitted - r.started) * 1e3 for r in due
                           if r.submitted is not None],
        "prompt_tokens_due": sum(r.prompt.size for r in due),
        "requests_finished": len(finished),
        "itl_count": len(itl),
        "itl_share_over_ms": {str(ms): sum(1 for g in itl if g >= ms)
                              / max(1, len(itl)) for ms in (200, 250, 300)},
    }
    return SimpleNamespace(
        readings=readings, client=client, due=due, finished=finished,
        failed=[r for r in due if not r.token_times],
        counters={k: c1[k] - c0[k] for k in c0}, compiled=compiled)


def run(env):
    spec = env.spec
    live = start(env)
    try:
        got = window(env, live, spec.traffic, env.seed)
        peak = harness.memory_peak(env.devices)
    finally:
        live.eng.shutdown(drain=False)
    got.client.join(30.0)
    free(live.net, live.slots)           # the program's state goes first
    calls = live.calls
    del live, got.client                 # ... and whatever still names it
    gc.collect()
    checks, picked = check(spec, env.seed, got.finished, spec.cell["limits"])
    return {"sample": picked, "window": got.readings,
            "attempted": len(got.due), "failed": len(got.failed),
            "memory_peak_bytes": peak, "checks": checks,
            "counters": got.counters, "calls": calls,
            "compiled_in_window": got.compiled}


def sample(finished, n, seed):
    """`n` finished requests drawn from the seed, the longest among them and,
    where one was admitted on a prefix-cache hit, one of those."""
    rng = onp.random.default_rng([int(seed), 0xC])
    pool = sorted(finished, key=lambda r: r.index)
    longest = max(pool, key=lambda r: r.prompt.size + len(r.tokens))
    picked = [longest]
    hits = [r for r in pool if r.handle.shared_tokens and r is not longest]
    if hits:
        picked.append(hits[rng.integers(len(hits))])
    rest = [r for r in pool if all(r is not p for p in picked)]
    rng.shuffle(rest)
    picked += rest[:max(0, n - len(picked))]
    while len(picked) < n:              # fewer finished than asked: repeat
        picked.append(picked[len(picked) % len(pool)])
    return picked[:n]


def served_rows(picked, pad_to):
    """The teacher-forced batch of a sample: ``tokens`` (B, pad_to), and for
    each served token the ``(b, position)`` whose logits chose it."""
    tokens = onp.zeros((len(picked), pad_to), onp.int32)
    rows, served = [], []
    for b, r in enumerate(picked):
        seq = onp.concatenate([r.prompt, onp.asarray(r.tokens, onp.int32)])
        if seq.size - 1 > pad_to:
            raise ValueError(f"request of {seq.size} tokens exceeds the "
                             f"check's padded length {pad_to}")
        tokens[b, :seq.size - 1] = seq[:-1]
        for j, tok in enumerate(r.tokens):
            rows.append((b, r.prompt.size - 1 + j))
            served.append(tok)
    return tokens, rows, onp.asarray(served, onp.int64)


def gaps(logits, tokens):
    """By how much each token's reference logit lies below the best."""
    return logits.max(-1) - logits[onp.arange(len(tokens)), tokens]


def reference_logits(spec, seed, picked, dtype="float32", everywhere=False):
    """The reference's logits over a sample, teacher-forced: at the positions
    that chose the served tokens, with those tokens; or `everywhere`, at
    every position of the same prompts and tokens (what the control reads)."""
    traffic = spec.traffic
    ref = harness.module_of("reference", spec.config["family"], spec.root)
    tokens, rows, served = served_rows(picked, traffic["check_pad"])
    if everywhere:
        rows = [(b, t) for b, r in enumerate(picked)
                for t in range(r.prompt.size + len(r.tokens) - 1)]
        return ref.logits_at(spec.config, seed, tokens, rows, dtype), None
    n_rows = traffic["check_requests"] * traffic["output"]["hi"]
    padded = rows + [(0, 0)] * (n_rows - len(rows))   # one compiled shape
    logits = ref.logits_at(spec.config, seed, tokens, padded, dtype)
    return logits[:len(rows)], served


def gap_checks(g, limits):
    """The two compared numbers of a set of gaps, each beside its limit: the
    program's served tokens and a control's go through the same lines."""
    return [{"name": "logit_gap_max", "value": float(g.max()),
             "limit": limits["logit_gap_max"]},
            {"name": "logit_gap_mean", "value": float(g.mean()),
             "limit": limits["logit_gap_mean"]}]


def check(spec, seed, finished, limits):
    """Run the reference once over a sample of what the window served and
    read the widest and the mean gap by which a served token's logit lies
    below the reference's best. Greedy traffic only."""
    vocab = spec.config["vocab_size"]
    bad = [r for r in finished if len(r.tokens) != r.max_new
           or not all(0 <= t < vocab for t in r.tokens)]
    checks = [{"name": "bad_streams", "value": len(bad), "limit": 0},
              {"name": "finished_requests_missing",
               "value": int(not finished), "limit": 0}]
    picked = []
    if finished and not bad:
        picked = sample(finished, spec.traffic["check_requests"], seed)
        logits, served = reference_logits(spec, seed, picked)
        g = gaps(logits, served)
        checks += gap_checks(g, limits) + [
            {"name": "served_tokens_compared", "value": len(served),
             "limit": limits["min_tokens_compared"], "at_least": True}]
    return checks, picked
