"""The EvaByte family under a traffic file, through the same `ServeEngine`
entry points as `runners/serve.py`, whose `window`, `check` and load
generator it uses as they are. Its own: the decoder built from the
configuration's seeded leaves (`build_engine`), the warm-up — which crosses a
window boundary once in prefill and once in decode, so that the roll program
is compiled before the window opens —, the counters of the two page kinds,
and what the window says of them (`decode_positions`, the share of decode
steps that also ran a prefill chunk, the phases' share of the steps' wall).
"""
from __future__ import annotations

import functools
import gc
import threading
import time
from types import SimpleNamespace

import numpy as onp

from chipbench.lib import harness, loadgen
from chipbench.readers.program_steps import records
from chipbench.runners import serve as base
from chipbench.runners.serve import (check, gap_checks, gaps,  # noqa: F401
                                     reference_logits)

ROWS = "mx_serve_decode_rows_total"
ACCOUNTED = ("admit", "prefill_launch", "prefill_readback", "decode_launch",
             "decode_readback", "emit")
# reference leaf -> (the program's leaf, transposed to (in, out))
TOP = {"embed_tokens.weight": ("embed", False), "norm.offset": ("norm", False),
       "lm_head.weight": ("head", True)}
LAYER = {"input_layernorm.offset": ("g1", False),
         "self_attn.q_proj.weight": ("wq", True),
         "self_attn.k_proj.weight": ("wk", True),
         "self_attn.v_proj.weight": ("wv", True),
         "self_attn.o_proj.weight": ("wo", True),
         "self_attn.adaptive_phi": ("phi", False),
         "self_attn.adaptive_mu_k": ("mu", False),
         "post_attention_layernorm.offset": ("g2", False),
         "mlp.gate_proj.weight": ("w_gate", True),
         "mlp.up_proj.weight": ("w_up", True),
         "mlp.down_proj.weight": ("w_down", True)}


def build_decoder(cfg, seed, ref, dtype="bfloat16"):
    """The program's `EvaByteDecoder` with every leaf the reference's seeded
    value, made on the device one leaf at a time in the dtype it is kept in
    (the float32 model is never resident)."""
    import jax
    import jax.numpy as jnp

    from chipbench.lib import seeded
    from incubator_mxnet_tpu.models import evabyte

    s = ref.sizes(cfg)

    @functools.partial(jax.jit, static_argnames=("tag", "shape", "kind",
                                                 "turn", "to"))
    def make(key, layer, tag, shape, kind, turn, to):
        a = ref.leaf(key, tag, layer, shape, kind, s.init_std, s.d)
        return (a.T if turn else a).astype(to)

    key = seeded.key_of(seed)
    params = {"layers": [{} for _ in range(s.layers)]}
    for _, tag, layer, shape, kind in ref.leaves(cfg):
        name, turn = (TOP.get(tag) or LAYER[tag])
        to = dtype if kind == "matrix" else "float32"
        value = make(key, jnp.int32(layer), tag, tuple(shape), kind, turn, to)
        (params if tag in TOP else params["layers"][layer])[name] = value
    return evabyte.EvaByteDecoder(evabyte.EvaByteConfig.from_dict(cfg),
                                  params, dtype=dtype)


def build_engine(spec, seed):
    import incubator_mxnet_tpu as mx

    cfg = spec.config
    ref = harness.module_of("reference", cfg["family"], spec.root)
    dec = build_decoder(cfg, seed, ref, cfg.get("served_dtype", "bfloat16"))
    return dec, mx.serve.ServeEngine(dec, **cfg["engine"])


def free(dec, slots):
    """Give back every device buffer of the program (the pools went with
    the engine's shutdown)."""
    import jax

    slots.release()
    for leaf in jax.tree.leaves(dec._params):  # noqa: SLF001
        if not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def counters():
    """The base runner's counters, and the rows decode attended by the kind
    of page they lie in (both kinds together under the bare name)."""
    from incubator_mxnet_tpu.telemetry import registry

    out = base.counters()
    kinds = {k: registry.counter(ROWS, labels={"kind": k}).value
             for k in ("window", "summary")}
    out.update({f"{ROWS}.{k}": v for k, v in kinds.items()})
    out[ROWS] = sum(kinds.values())
    out["mx_serve_eva_rolls_total"] = registry.counter(
        "mx_serve_eva_rolls_total").value
    return out


def warm(eng, seed, vocab):
    """Every program the window can touch: each prefill bucket, a prompt
    whose prefill crosses a window boundary (the roll between two chunks),
    decode, and a request whose decode crosses one (the roll before a
    step)."""
    slots = eng._sched.slots  # noqa: SLF001
    rng = onp.random.default_rng([int(seed), 0xA])
    client = loadgen.Client(eng)
    w, pc = slots.window, slots.prefill_chunk
    sizes = [(pc + max(2, b - 3), 4) for b in slots.chunk_buckets]
    sizes += [(w + max(2, slots.chunk_buckets[0] - 3), 4), (w - 4, 8)]
    reqs = [client.submit(loadgen.Req(
        -1 - i, rng.integers(0, vocab, n).astype(onp.int32), new, False))
        for i, (n, new) in enumerate(sizes)]
    for r in reqs:
        if not r.done.wait(1100.0) or r.error is not None:
            raise RuntimeError(f"warm-up request failed: {r.error!r}")
    client.join(30.0)


def start(env):
    """Set-up as far as a warm, running engine; in a traced run the
    benchmark's spans go around the engine's calls, as the base runner's."""
    dec, eng = build_engine(env.spec, env.seed)
    env.mark("decoder filled from the seed, engine built")
    slots = eng._sched.slots  # noqa: SLF001
    calls = {"decode": [], "prefill": []}
    eng.start()
    try:
        warm(eng, env.seed, env.spec.config["vocab_size"])
    except BaseException:
        eng.shutdown(drain=False)
        raise
    env.mark("prefill buckets, decode and the roll warmed")
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
    return SimpleNamespace(net=dec, eng=eng, slots=slots, calls=calls)


def window(env, live, traffic, seed):
    """`runners/serve.py`'s window as it is, with this family's counters
    read at its open and (by a timer, to a step's accuracy) at its close,
    and two readings made from what it returns."""
    mine = {}
    inner = env.open_window

    def open_window():
        mine["open"] = counters()
        t = threading.Timer(env.seconds,
                            lambda: mine.setdefault("close", counters()))
        t.daemon = True
        t.start()
        inner()

    env.open_window = open_window
    try:
        got = base.window(env, live, traffic, seed)
    finally:
        env.open_window = inner
    close = mine.get("close") or counters()
    got.counters.update({k: close[k] - mine["open"][k] for k in close
                         if k not in got.counters})
    w = got.readings
    in_win = lambda t: w["t_open"] <= t < w["t_close"]  # noqa: E731
    # the positions the window's decode steps stood at (what attention over
    # the whole context would have covered): token j >= 1 of a request left
    # a step at position prompt + j - 1, that attends prompt + j positions
    w["decode_positions"] = sum(
        r.prompt.size + j for r in got.client.sent
        for j, t in enumerate(r.token_times) if j and in_win(t))
    steps = records({"window": w}, "step_records")
    if steps:
        decode = [r for r in steps if r["decoding"]]
        w["decode_steps"] = len(decode)
        w["decode_steps_with_chunk_share"] = \
            sum(1 for r in decode if r["chunks"]) / max(1, len(decode))
        # what `step_accounted_share.itl` reads (its phases, without the
        # roll): the roll is charged inside `wall` and must leave it whole
        w["step_accounted_share"] = sum(
            r[ph] for r in steps for ph in ACCOUNTED) \
            / max(1e-9, sum(r["wall"] for r in steps))
    return got


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
    t0 = time.perf_counter()
    checks, picked = check(spec, env.seed, got.finished, spec.cell["limits"])
    got.readings["check_s"] = time.perf_counter() - t0
    return {"sample": picked, "window": got.readings,
            "attempted": len(got.due), "failed": len(got.failed),
            "memory_peak_bytes": peak, "checks": checks,
            "counters": got.counters, "calls": calls,
            "compiled_in_window": got.compiled}
