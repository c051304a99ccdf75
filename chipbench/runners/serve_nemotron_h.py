"""The Nemotron-H family under a traffic file, through the same `ServeEngine`
entry points as `runners/serve.py`. From `runners/serve_pangu.py`, as they
are: the decode launch's `Context` (it carries ``experts_hit`` once the step's
tokens were fetched — `flops/nemotron_h.py` counts the experts' bytes from
it), the expert layers' counters and window readings (`window`), and the
check with `logit_gap_mean_decisive` (`check`, `gap_checks`). Its own: the
decoder built from the configuration's seeded leaves, each made on the device
in the form and dtype the program keeps it in (`build_decoder`; the float32
model is never resident), what the window's step records say of the
recurrent state — ``state_resets`` (a value an engine iteration of the window:
the slots whose state was reset in it) and ``chunk_step_share`` (the share of
iterations that carried a prefill chunk) — and **the state itself, compared**:
when the window has closed, with the engine's lock held and the step in flight
landed, two decoding slots' recurrent state (the shortest and the longest
context) is read from the pools with the tokens it has consumed, and the check
holds it against the reference's state after the same tokens
(``state_gap_first``, ``state_gap_max``: the largest relative distance in the
first Mamba block, and in any).
A served token says little of the state (with seeded weights ``D x`` outweighs
``S C``, and a prompt of hundreds of tokens forgets what a slot held before
it): a state not reset, or carried in a lower precision, moves the tokens'
gaps by less than the seeds do and the state's own by an order (PERF.md §2).
"""
from __future__ import annotations

import functools
import gc
import time
from types import SimpleNamespace

import numpy as onp

from chipbench.lib import harness
from chipbench.readers.program_steps import records
from chipbench.runners import serve_pangu as pangu
from chipbench.runners.serve import (gaps, reference_logits,  # noqa: F401
                                     served_rows, warm)
from chipbench.runners.serve_eva import free
from chipbench.runners.serve_pangu import (Context, check,  # noqa: F401
                                           gap_checks)

# reference leaf -> the program's leaf; every matrix is turned to (in, out)
TOP = {"embeddings.weight": "embed", "norm_f.weight": "norm",
       "lm_head.weight": "head"}
LAYER = {"norm.weight": "n",
         "mixer.in_proj.weight": "w_in", "mixer.conv1d.weight": "conv_w",
         "mixer.conv1d.bias": "conv_b", "mixer.dt_bias": "dt_bias",
         "mixer.A_log": "a_log", "mixer.D": "d",
         "mixer.norm.weight": "g_norm", "mixer.out_proj.weight": "w_out",
         "mixer.q_proj.weight": "w_q", "mixer.k_proj.weight": "w_k",
         "mixer.v_proj.weight": "w_v", "mixer.o_proj.weight": "w_o",
         "mixer.gate.weight": "w_router",
         "mixer.gate.e_score_correction_bias": "b_router",
         "mixer.fc1_latent_proj.weight": "w_dn",
         "mixer.fc2_latent_proj.weight": "w_up",
         "mixer.shared_experts.up_proj.weight": "ws_1",
         "mixer.shared_experts.down_proj.weight": "ws_2"}
EXPERT = {"mixer.experts.up_proj.weight": "we_1",
          "mixer.experts.down_proj.weight": "we_2"}


def build_decoder(cfg, seed, ref, dtype="bfloat16"):
    """The program's `NemotronHDecoder` with every leaf the reference's
    seeded value, made on the device one leaf (one expert) at a time in the
    form and dtype it is kept in."""
    import jax
    import jax.numpy as jnp

    from chipbench.lib import seeded
    from incubator_mxnet_tpu.models import nemotron_h

    ncfg = nemotron_h.NemotronHConfig.from_dict(cfg)
    s = ref.sizes(cfg)

    @functools.partial(jax.jit, static_argnames=("tag", "shape", "kind",
                                                 "to"))
    def make(key, code, tag, shape, kind, to):
        a = ref.leaf(key, tag, code, shape, kind, s)
        turn = a.ndim == 2 and tag != "embeddings.weight"
        return (a.T if turn else a).astype(to)

    def kept(name):
        return "float32" if name in nemotron_h.FLOAT32 else dtype

    key = seeded.key_of(seed)
    params = {"layers": [{} for _ in range(s.layers)]}
    experts = {}
    for full, tag, code, shape, kind in ref.leaves(cfg):
        if tag in EXPERT:
            li = int(full.split(".")[1])
            experts.setdefault((li, EXPERT[tag]), []).append(
                make(key, jnp.int32(code), tag, tuple(shape), kind, dtype))
            continue
        name = TOP.get(tag) or LAYER[tag]
        into = params if tag in TOP else params["layers"][code]
        into[name] = make(key, jnp.int32(code), tag, tuple(shape), kind,
                          kept(name))
    for (li, name), parts in experts.items():
        params["layers"][li][name] = jnp.stack(parts)
        del parts[:]
    for lp in params["layers"]:
        if "w_q" in lp:                 # one product for q, k and v
            lp["w_qkv"] = jnp.concatenate(
                [lp.pop("w_q"), lp.pop("w_k"), lp.pop("w_v")], axis=1)
    return nemotron_h.NemotronHDecoder(ncfg, params, dtype=dtype)


def build_engine(spec, seed):
    import incubator_mxnet_tpu as mx

    cfg = spec.config
    ref = harness.module_of("reference", cfg["family"], spec.root)
    dec = build_decoder(cfg, seed, ref, cfg.get("served_dtype", "bfloat16"))
    return dec, mx.serve.ServeEngine(dec, **cfg["engine"])


def start(env):
    """Set-up as far as a warm, running engine; in a traced run the
    benchmark's spans go around the engine's calls, as
    `runners/serve_pangu.py`'s."""
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
    """`runners/serve_pangu.py`'s window as it is, and the state's readings
    made from the program's step records."""
    got = pangu.window(env, live, traffic, seed)
    w = got.readings
    steps = records({"window": w}, "step_records")
    if steps:
        w["state_resets"] = [r.get("state_resets", 0) for r in steps]
        w["chunk_step_share"] = \
            sum(1 for r in steps if r.get("chunks")) / len(steps)
    return got


def slot_states(live, n=2):
    """``[(tokens consumed, {"ssm": (blocks, H, P, N), ...}), ...]`` of up
    to `n` decoding slots, the shortest and the longest context first: read
    with the engine's lock held and the decode step in flight landed, so
    that a slot's state has consumed exactly the tokens named."""
    eng = live.eng
    sched = eng._sched  # noqa: SLF001
    with eng._lock:  # noqa: SLF001
        sched.settle()
        rows = sorted(
            ((int(sched._pos[s]), s) for s, req  # noqa: SLF001
             in enumerate(sched._in_slot)  # noqa: SLF001
             if req is not None and sched._active[s]),  # noqa: SLF001
            key=lambda r: r[0])
        out = []
        for pos, s in (rows[:1] + rows[-1:] if len(rows) > 1 else rows)[:n]:
            req = sched._in_slot[s]  # noqa: SLF001
            seq = onp.concatenate(
                [req.prompt, onp.asarray(req.tokens, onp.int32)])
            if pos != seq.size - 1:
                raise RuntimeError(
                    f"slot {s} stands at {pos} with {seq.size} tokens known")
            out.append((seq[:pos], live.slots.slot_state(s)))
    return out


def reference_states(spec, seed, sequences, dtype="float32", initial=None):
    """The reference's recurrent state after each of `sequences`: ``[(Mamba
    blocks, H, P, N), ...]``, and its `forward` `finals` (for a control
    that hands them on)."""
    ref = harness.module_of("reference", spec.config["family"], spec.root)
    pad = spec.traffic["check_pad"]
    tokens = onp.zeros((len(sequences), pad), onp.int32)
    for b, seq in enumerate(sequences):
        tokens[b, :seq.size] = seq
    finals = {}
    ref.forward(spec.config, seed, tokens, [s.size for s in sequences],
                dtype, initial=initial, finals=finals)
    blocks = sorted({li for li, _ in finals})
    return [onp.stack([onp.asarray(finals[li, b][0]) for li in blocks])
            for b in range(len(sequences))], finals


def state_gaps(states, wanted):
    """``(slots, Mamba blocks)``: the relative distance of each state from
    the one wanted, ``|S - S_ref| / |S_ref|`` (Frobenius)."""
    return onp.asarray([
        [onp.linalg.norm(s[k] - w[k]) / max(onp.linalg.norm(w[k]), 1e-30)
         for k in range(len(w))] for s, w in zip(states, wanted)])


def state_checks(gaps, limits):
    """The compared numbers of some slots' states, `gaps` ``(slots, Mamba
    blocks)`` (the program's and a control's go through the same lines):
    the FIRST block's largest — its input is the embedding, so the program
    reads its own arithmetic there and the limit can stand close — and the
    largest of all, which grows with depth as the blocks' inputs drift from
    the reference's, under a limit for gross faults."""
    n = 0 if gaps is None else len(gaps)
    checks = [{"name": "state_slots_compared", "value": n,
               "limit": limits.get("min_state_slots", 0),
               "at_least": True}]
    if n:
        checks += [
            {"name": "state_gap_first", "value": float(gaps[:, 0].max()),
             "limit": limits["state_gap_first"]},
            {"name": "state_gap_max", "value": float(gaps.max()),
             "limit": limits["state_gap_max"]}]
    return checks


def run(env):
    spec = env.spec
    live = start(env)
    try:
        got = window(env, live, spec.traffic, env.seed)
        peak = harness.memory_peak(env.devices)
        held = slot_states(live)
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
    gaps = wanted = finals = None
    if held:
        wanted, finals = reference_states(spec, env.seed,
                                          [s for s, _ in held])
        gaps = state_gaps([st["ssm"] for _, st in held], wanted)
    checks += state_checks(gaps, spec.cell["limits"])
    if gaps is not None:
        got.readings["state_contexts"] = [int(s.size) for s, _ in held]
        got.readings["state_gaps"] = [round(float(g), 6)
                                      for g in gaps.ravel()]
    got.readings["check_s"] = time.perf_counter() - t0
    return {"sample": picked, "rows": rows, "states": held,
            "state_gaps": gaps, "state_wanted": (wanted, finals),
            "window": got.readings,
            "attempted": len(got.due), "failed": len(got.failed),
            "memory_peak_bytes": peak, "checks": checks,
            "counters": got.counters, "calls": calls,
            "compiled_in_window": got.compiled}
