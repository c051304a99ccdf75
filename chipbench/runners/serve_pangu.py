"""The openPangu-Ultra-MoE family under a traffic file, through the same
`ServeEngine` entry points as `runners/serve.py`, whose `window` and load
generator it uses as they are, and whose `check` it repeats with one more
compared number (`gap_checks`: the mean gap over the tokens whose choice of
experts is decisive). Its own beside: the decoder built from the
configuration's seeded leaves, each made on the device in the form and dtype
the program keeps it in (`build_decoder`; the float32 model is never
resident), the counters of the expert layers and the latent rows, and, in a
traced run, what each decode step's routing hit: the context a decode launch
leaves in ``calls["decode"]`` is the list of its slots' contexts, as every
runner's, and carries ``experts_hit`` (held experts hit, one entry an expert
layer) once the step's tokens were fetched — `flops/pangu.py` counts the
experts' bytes from it.
"""
from __future__ import annotations

import functools
import gc
import threading
import time
from types import SimpleNamespace

from chipbench.lib import harness
from chipbench.readers.program_steps import records
from chipbench.runners import serve as base
from chipbench.runners.serve import (gaps, reference_logits,  # noqa: F401
                                     warm)
from chipbench.runners.serve_eva import free

ROWS = "mx_serve_decode_rows_total"
PAIRS = "mx_serve_moe_pairs_total"
HIT = "mx_serve_moe_experts_hit_total"
# reference leaf -> the program's leaf (`models.pangu.stored` gives its form)
TOP = {"embed_tokens.weight": ("embed",), "norm.weight": ("norm",),
       "lm_head.weight": ("head",)}
LAYER = {"input_layernorm.weight": ("n_in",),
         "self_attn.q_a_proj.weight": ("w_qa",),
         "self_attn.q_a_layernorm.weight": ("n_q",),
         "self_attn.q_b_proj.weight": ("w_qb",),
         "self_attn.kv_a_proj_with_mqa.weight": ("w_kva",),
         "self_attn.kv_a_layernorm.weight": ("n_kv",),
         "self_attn.kv_b_proj.weight": ("w_uk", "w_uv"),
         "self_attn.o_proj.weight": ("w_o",),
         "post_attention_layernorm.weight": ("n_post_attn",),
         "pre_mlp_layernorm.weight": ("n_pre_mlp",),
         "post_mlp_layernorm.weight": ("n_post_mlp",),
         "mlp.gate_proj.weight": ("w_gate",),
         "mlp.up_proj.weight": ("w_up",),
         "mlp.down_proj.weight": ("w_down",),
         "mlp.gate.weight": ("w_router",),
         "mlp.shared_experts.gate_proj.weight": ("ws_gate",),
         "mlp.shared_experts.up_proj.weight": ("ws_up",),
         "mlp.shared_experts.down_proj.weight": ("ws_down",)}
EXPERT = {"mlp.experts.gate_proj.weight": "we_gate",
          "mlp.experts.up_proj.weight": "we_up",
          "mlp.experts.down_proj.weight": "we_down"}


class Context(list):
    """A decode launch's context: its slots' contexts, and (once its tokens
    were fetched) the held experts its routing hit, an entry an expert
    layer."""

    experts_hit = None


def build_decoder(cfg, seed, ref, dtype="bfloat16"):
    """The program's `PanguDecoder` with every leaf the reference's seeded
    value, made on the device one leaf (one expert) at a time in the form
    and dtype it is kept in."""
    import jax
    import jax.numpy as jnp

    from chipbench.lib import seeded
    from incubator_mxnet_tpu.models import pangu

    pcfg = pangu.PanguConfig.from_dict(cfg)
    s = ref.sizes(cfg)

    @functools.partial(jax.jit, static_argnames=("tag", "shape", "kind",
                                                 "name", "to"))
    def make(key, code, tag, shape, kind, name, to):
        return pangu.stored(pcfg, name, ref.leaf(
            key, tag, code, shape, kind, s.init_std)).astype(to)

    def kept(name):
        return "float32" if name in pangu.FLOAT32 else dtype

    key = seeded.key_of(seed)
    params = {"layers": [{} for _ in range(s.layers)]}
    experts = {}
    for full, tag, code, shape, kind in ref.leaves(cfg):
        if tag in EXPERT:
            li = int(full.split(".")[1])
            experts.setdefault((li, EXPERT[tag]), []).append(
                make(key, jnp.int32(code), tag, tuple(shape), kind,
                     EXPERT[tag], dtype))
            continue
        into = params if tag in TOP else params["layers"][code]
        for name in TOP.get(tag) or LAYER[tag]:
            into[name] = make(key, jnp.int32(code), tag, tuple(shape), kind,
                              name, kept(name))
    for (li, name), parts in experts.items():
        params["layers"][li][name] = jnp.stack(parts)
        del parts[:]
    return pangu.PanguDecoder(pcfg, params, dtype=dtype)


def build_engine(spec, seed):
    import incubator_mxnet_tpu as mx

    cfg = spec.config
    ref = harness.module_of("reference", cfg["family"], spec.root)
    dec = build_decoder(cfg, seed, ref, cfg.get("served_dtype", "bfloat16"))
    return dec, mx.serve.ServeEngine(dec, **cfg["engine"])


def counters():
    """The base runner's counters, the latent rows decode attended and the
    expert layers' pairs and experts hit."""
    from incubator_mxnet_tpu.telemetry import registry

    out = base.counters()
    out[ROWS] = registry.counter(ROWS, labels={"kind": "latent"}).value
    for kind in ("held", "routed"):
        out[f"{PAIRS}.{kind}"] = registry.counter(
            PAIRS, labels={"kind": kind}).value
    out[HIT] = registry.counter(HIT).value
    return out


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
    env.mark("prefill buckets and decode warmed")
    if env.trace:
        spans = env.spans
        launched = []           # contexts whose tokens are not fetched yet

        def on_decode(last, pos, active, *a):
            ctx = Context(int(p) + 1 for p, on in zip(pos, active) if on)
            launched.append(ctx)
            calls["decode"].append((time.perf_counter(), ctx))

        inner = slots.fetch_tokens

        def fetch_tokens(out):
            tokens = inner(out)
            if launched:        # fetched in the order launched
                launched.pop(0).experts_hit = [
                    int(h) for h in slots.last_expert_stats[:, 1]]
            return tokens

        slots.fetch_tokens = fetch_tokens
        spans.wrap(eng, "step", "cb.serve.step")
        spans.wrap(slots, "decode_step", "cb.serve.decode_step", on_decode)
        spans.wrap(slots, "prefill_chunk_step", "cb.serve.prefill_chunk",
                   lambda slot, chunk, t_start, *a, **k:
                   calls["prefill"].append(
                       (time.perf_counter(), int(t_start), len(chunk))))
    return SimpleNamespace(net=dec, eng=eng, slots=slots, calls=calls)


def window(env, live, traffic, seed):
    """`runners/serve.py`'s window as it is, with this family's counters
    read at its open and (by a timer, to a step's accuracy) at its close,
    and the expert layers' readings made from the program's step records."""
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
    w["moe_pairs_routed"] = got.counters[f"{PAIRS}.routed"]
    steps = records({"window": w}, "step_records")
    if steps:
        layers = live.slots._expert_layers  # noqa: SLF001
        decode = [r for r in steps if r.get("decoding")
                  and "moe_experts_hit" in r and not r.get("chunks")]
        w["decode_steps"] = len(decode)
        # held experts hit an expert layer, decode-only steps
        w["moe_experts_hit"] = [r["moe_experts_hit"] / layers for r in decode]
    return got


def gap_checks(g, limits, margin=None):
    """`runners/serve.py`'s two numbers over every compared token and, where
    the reference's `route_margin` of each is given, the mean over the
    DECISIVE ones beside its own, much closer limit: a token whose margin
    is under ``limits["decisive_margin"]`` may choose another held expert
    in bfloat16 than the float32 reference does, and then lies a whole
    expert's part off (what `logit_gap_mean` has to leave room for); the
    others may not, and a layer that drops a held expert's pairs shows on
    them as on any."""
    checks = base.gap_checks(g, limits)
    if margin is not None:
        at = margin > limits["decisive_margin"]
        checks += [
            {"name": "logit_gap_mean_decisive", "value":
             float(g[at].mean()) if at.any() else float("inf"),
             "limit": limits["logit_gap_mean_decisive"]},
            {"name": "decisive_tokens_compared", "value": int(at.sum()),
             "limit": limits["min_decisive_tokens"], "at_least": True}]
    return checks


def check(spec, seed, finished, limits):
    """`runners/serve.py`'s check with this family's `gap_checks`: the
    checks, the sample, and the sample's ``(gaps, margins)`` a compared
    token (for a control)."""
    vocab = spec.config["vocab_size"]
    bad = [r for r in finished if len(r.tokens) != r.max_new
           or not all(0 <= t < vocab for t in r.tokens)]
    checks = [{"name": "bad_streams", "value": len(bad), "limit": 0},
              {"name": "finished_requests_missing",
               "value": int(not finished), "limit": 0}]
    picked, rows = [], None
    if finished and not bad:
        picked = base.sample(finished, spec.traffic["check_requests"], seed)
        ref = harness.module_of("reference", spec.config["family"], spec.root)
        tokens, rows, served = base.served_rows(
            picked, spec.traffic["check_pad"])
        n_rows = spec.traffic["check_requests"] * spec.traffic["output"]["hi"]
        logits, margin = ref.logits_at(    # one compiled shape
            spec.config, seed, tokens, rows + [(0, 0)] * (n_rows - len(rows)),
            with_margin=True)
        g, margin = gaps(logits[:len(rows)], served), margin[:len(rows)]
        checks += gap_checks(g, limits, margin) + [
            {"name": "served_tokens_compared", "value": len(served),
             "limit": limits["min_tokens_compared"], "at_least": True}]
        rows = (g, margin)
    return checks, picked, rows


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
    checks, picked, rows = check(spec, env.seed, got.finished,
                                 spec.cell["limits"])
    got.readings["check_s"] = time.perf_counter() - t0
    return {"sample": picked, "rows": rows, "window": got.readings,
            "attempted": len(got.due), "failed": len(got.failed),
            "memory_peak_bytes": peak, "checks": checks,
            "counters": got.counters, "calls": calls,
            "compiled_in_window": got.compiled}
