#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a user
would call, at the full published width of a model the repo supports, with
weights made from ``--seed`` and nothing downloaded:

* **serve** — ``models.gpt.gpt2_small()`` (GPT-2 124M: 12 layers x 768,
  12 heads, vocab 50,257, 1,024 positions; no cut) behind
  ``mx.serve.ServeEngine`` with 8 slots: 8 requests of 64-768 prompt tokens
  and 32 new tokens each, half of them behind a shared system prompt and
  arriving while the others decode, streamed to completion. One request is
  then checked against the Gluon block's own full forward on the same device.
* **eva** — the EvaByte family (`serve/eva.py`) at its published widths
  (4096 wide, 32 heads of 128, FFN 11,008, window 2,048, chunk 16) and two
  layers deep, bfloat16: one request whose prefill crosses a window
  boundary and one whose decode does (both kinds of page, the roll program,
  the paged kernel at head size 128), each token checked against the plain
  reference (`chipbench/reference/evabyte.py`).
* **pangu** — the openPangu-Ultra-MoE family (`serve/mla.py`) at its
  published widths (7,680 wide, 128 heads, latent row 512 + 64, experts 2,048
  wide, top-8 of 256 of which 16 are held) and one dense and one expert layer
  deep, bfloat16: three requests prefilled in chunks and decoded together
  (the latent pages, the absorbed kernel ``mx_mla_decode`` at 128 heads, the
  up-projected chunk, the grouped expert product ``mx_moe_experts``), each
  token checked against the plain reference (`chipbench/reference/pangu.py`),
  and the expert layer's counts against the routing the reference makes.
* **train** — ``models.bert.bert_base()`` through
  ``parallel.sharded.DataParallel(...).step`` under ``amp.init("bfloat16")``,
  dropout 0.1, at batch 32 x seq 512 and batch 64 x seq 128, 5 steps each on a
  repeated batch: the loss must be finite and lower at step 5 than at step 1.

``--chips 4`` runs instead, and only, what exists only across chips: the same
serve requests through a ``tp=4`` sharded replica against a one-device engine,
and one BERT-base step on a ``dp=2 x tp=2`` mesh against the one-chip loss.

It refuses to run anywhere but on a TPU (exit code 2, no result line), lets a
failing phase raise, and prints as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--tiny`` shrinks every size to a toy. It exists only to rehearse the control
flow — the CPU test of this file, and a first cheap call on the chip — and
proves nothing about the real sizes; the driver's run uses the defaults.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as onp

# (model factory name, engine + request sizes); one row per --tiny setting
SERVE_SIZES = {
    False: dict(model="gpt2_small", max_slots=8, max_len=1024,
                n_requests=8, prompt_lo=64, prompt_hi=768, shared_prefix=128,
                new_tokens=32),
    True: dict(model="gpt_tiny", max_slots=4, max_len=128,
               n_requests=4, prompt_lo=20, prompt_hi=100, shared_prefix=16,
               new_tokens=6),
}
EVA_SIZES = {   # sizes under EvaByte's own keys; (prompt, new tokens) pairs
    False: dict(cfg=dict(num_hidden_layers=2, hidden_size=4096,
                         num_attention_heads=32, intermediate_size=11008,
                         vocab_size=320, num_pred_heads=8, window_size=2048,
                         chunk_size=16, rope_theta=100000, rms_norm_eps=1e-5,
                         init_std=0.01275, max_position_embeddings=4096),
                dtype="bfloat16", page_tokens=16, prefill_chunk=512,
                requests=((2100, 8), (2040, 24)), gap_limit=0.1),
    True: dict(cfg=dict(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, intermediate_size=96,
                        vocab_size=50, num_pred_heads=2, window_size=32,
                        chunk_size=4, rope_theta=100000, rms_norm_eps=1e-5,
                        init_std=0.2, max_position_embeddings=96),
               dtype="float32", page_tokens=4, prefill_chunk=8,
               requests=((40, 4), (28, 12)), gap_limit=1e-3),
}
_PANGU = dict(num_hidden_layers=2, first_k_dense_replace=1,
              n_shared_experts=1, routed_scaling_factor=2.5,
              rope_theta=25600000, rms_norm_eps=1e-5, init_std=0.02)
PANGU_SIZES = {  # sizes under the release's own keys
    False: dict(cfg=dict(_PANGU, hidden_size=7680, intermediate_size=18432,
                         moe_intermediate_size=2048, num_attention_heads=128,
                         q_lora_rank=1536, kv_lora_rank=512,
                         qk_nope_head_dim=128, qk_rope_head_dim=64,
                         v_head_dim=128, n_routed_experts=256,
                         num_experts_per_tok=8, vocab_size=19200,
                         experts_held=[0, 16], max_position_embeddings=2048),
                dtype="bfloat16", page_tokens=16, prefill_chunk=512,
                max_slots=8, requests=((1100, 24), (300, 24), (700, 16)),
                gap_limit=0.5),
    True: dict(cfg=dict(_PANGU, hidden_size=64, intermediate_size=96,
                        moe_intermediate_size=32, num_attention_heads=4,
                        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16,
                        n_routed_experts=16, num_experts_per_tok=4,
                        vocab_size=50, experts_held=[4, 6],
                        max_position_embeddings=96),
               dtype="float32", page_tokens=4, prefill_chunk=16,
               max_slots=3, requests=((40, 6), (21, 12), (9, 8)),
               gap_limit=1e-3),
}
TRAIN_SIZES = {
    False: dict(model="bert_base", vocab=30522, steps=5,
                shapes=((32, 512), (64, 128)), mesh_shape=(32, 128)),
    True: dict(model="bert_small", vocab=1000, steps=5,
               shapes=((4, 32), (8, 16)), mesh_shape=(4, 16)),
}
# The engine's greedy token must score within this fraction of the largest
# |logit| of the reference's best token. Both sides run the chip's default
# matmul precision (bf16 passes) in a different order — chunked, paged and
# possibly sharded against one full forward — and seeded random weights give
# near-flat logits, so near-ties may flip; a wrong page, mask or position
# costs whole standard deviations, far outside this band.
LOGIT_TOL_FRAC = 0.02


def say(msg):
    print(f"[smoke] {msg}", flush=True)


class CompileWatch:
    """Counts what jax itself reports: backend compile requests and their
    seconds, and how many were answered from the persistent cache."""

    def __init__(self):
        import jax

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.requests, self.seconds, self.cache_hits,
                self.cache_misses)

    def since(self, snap=(0, 0.0, 0, 0)):
        now = self.snapshot()
        return dict(zip(("requests", "seconds", "cache_hits", "cache_misses"),
                        (a - b for a, b in zip(now, snap))))


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def build_gpt(cfg, seed):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import gpt

    mx.random.seed(seed)
    net = getattr(gpt, cfg["model"])()
    net.initialize()
    return net


def make_requests(cfg, vocab, seed):
    """Seeded prompts: lengths spread over [prompt_lo, prompt_hi]; every
    second one starts with the same system prompt (prefix-cache traffic)."""
    rng = onp.random.RandomState(seed)
    system = rng.randint(0, vocab, (cfg["shared_prefix"],)).astype(onp.int32)
    lengths = onp.linspace(cfg["prompt_lo"], cfg["prompt_hi"],
                           cfg["n_requests"]).astype(int)
    rng.shuffle(lengths)
    prompts = []
    for i, n in enumerate(lengths):
        p = rng.randint(0, vocab, (int(n),)).astype(onp.int32)
        if i % 2:
            p[:system.size] = system[:p.size]
        prompts.append(p)
    return prompts


def longest(prompts):
    """Index of the longest prompt: the most chunks, pages and positions."""
    return max(range(len(prompts)), key=lambda i: prompts[i].size)


def warm_lengths(engine_slots):
    """One prompt length per prefill-chunk bucket, so that every program the
    window can touch is compiled before it opens."""
    return [max(2, b - 3) for b in engine_slots.chunk_buckets]


def reference_gap(net, prompt, generated):
    """Teacher-force ``prompt + generated`` through the Gluon block's own
    full forward and return, over the generated positions, the largest
    ``best logit - logit of the engine's token``, the largest |logit|, and
    the share of positions where the engine's token IS the argmax."""
    from incubator_mxnet_tpu import np

    generated = onp.asarray(generated, onp.int32)
    seq = onp.concatenate([prompt, generated])[:-1]
    logits = net(np.array(seq[None, :]))
    z = logits[0, prompt.size - 1:].asnumpy().astype(onp.float32)
    if z.shape != (generated.size, logits.shape[-1]) \
            or not onp.isfinite(z).all():
        raise RuntimeError(f"reference logits malformed: shape {z.shape}")
    chosen = z[onp.arange(generated.size), generated]
    return (float((z.max(-1) - chosen).max()), float(onp.abs(z).max()),
            float((z.argmax(-1) == generated).mean()))


def check_against_reference(net, prompt, generated, what):
    gap, scale, agree = reference_gap(net, prompt, generated)
    tol = LOGIT_TOL_FRAC * scale
    say(f"{what}: vs Gluon full forward over {len(generated)} generated "
        f"positions: worst logit gap {gap:.4g} (tolerance {tol:.4g} = "
        f"{LOGIT_TOL_FRAC} x max|logit| {scale:.4g}), token-for-token "
        f"agreement {agree:.3f}")
    if not gap <= tol:
        raise RuntimeError(
            f"{what}: logits check failed: gap {gap} > tolerance {tol}")


def peak_memory(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def kernel_branches(before):
    """Kernel-site choices made since `before` (a `_dispatch.choices()`
    snapshot), as ``{op: {impl: n}}``."""
    from incubator_mxnet_tpu.ops import _dispatch

    out = {}
    for (op, impl), n in sorted(_dispatch.choices().items()):
        n -= before.get((op, impl), 0)
        if n:
            out.setdefault(op, {})[impl] = n
    return out


# ---------------------------------------------------------------------------
# one chip: serve
# ---------------------------------------------------------------------------

def launch_modes(steps, what):
    """How the decode launches of `steps` (step records) were made: says
    the share queued while the step before was still unfetched, and the
    rows launched for a request whose EOS came a step late. Every request
    here ends by length, and a run in which no launch went ahead has lost
    the mechanism."""
    launches = [r for r in steps if r["decoding"]]
    only = [r for r in launches if not r["chunks"]]
    ahead = sum(r["mode"] == "ahead" for r in only)
    overshoot = sum(r["overshoot"] for r in steps)
    share = 100.0 * ahead / len(only) if only else 0.0
    say(f"{what}: {len(launches)} decode launches, {len(only)} in steps "
        f"without a prefill chunk, {ahead} of those made ahead of the last "
        f"fetch ({share:.1f} %); overshoot rows {overshoot}")
    if not ahead or overshoot:
        raise RuntimeError(
            f"{what}: {ahead} decode launches made ahead, {overshoot} "
            "overshoot rows (expected some, and none)")


def serve_phase(cfg, seed, watch):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.ops import _dispatch
    from incubator_mxnet_tpu.serve.scheduler import (PREFILL_CHUNKS,
                                                     PREFIX_HITS)
    from incubator_mxnet_tpu.telemetry import compiles, tracing

    import jax

    say(f"serve phase: {cfg['model']} behind mx.serve.ServeEngine, "
        f"{cfg['max_slots']} slots, max_len {cfg['max_len']}")
    net = build_gpt(cfg, seed)
    vocab = net.word_embed.weight.shape[0]
    n_params = sum(int(onp.prod(p.shape))
                   for p in net.collect_params().values())
    say(f"model: {n_params / 1e6:.1f}M parameters, vocab {vocab}")
    branches0 = _dispatch.choices()
    compiles.reset()
    compiles.enable()
    t_run = time.perf_counter()
    eng = mx.serve.ServeEngine(net, max_slots=cfg["max_slots"],
                               max_len=cfg["max_len"])
    try:
        # -- set-up: compile every program family the window will use
        snap = watch.snapshot()
        t0 = time.perf_counter()
        rng = onp.random.RandomState(seed + 1)
        for n in warm_lengths(eng._sched.slots):  # noqa: SLF001
            eng.generate(rng.randint(0, vocab, (n,)).astype(onp.int32), 2)
        warm_wall = time.perf_counter() - t0
        for fam, rep in sorted(compiles.ledger_report().items()):
            say(f"compile {fam}: {rep['compiles']} program(s) "
                f"{rep['buckets'] or ''} in {rep['seconds']:.2f} s "
                f"(first call: trace + compile + run)")
        say(f"serve set-up wall {warm_wall:.2f} s; xla {watch.since(snap)}")

        # -- the window: nothing may compile in here
        prompts = make_requests(cfg, vocab, seed)
        n_ledger = sum(len(v) for v in compiles.ledger().values())
        snap = watch.snapshot()
        hits0, chunks0 = PREFIX_HITS.value, PREFILL_CHUNKS.value
        t0 = time.perf_counter()
        half = len(prompts) // 2
        handles = [eng.submit(p, cfg["new_tokens"]) for p in prompts[:half]]
        # the second half arrives once the first is decoding: continuous
        # batching, and their system prompt is in the prefix cache by then
        while not all(h.tokens for h in handles):
            eng.step()
        handles += [eng.submit(p, cfg["new_tokens"]) for p in prompts[half:]]
        outputs = [list(eng.iter_tokens(h)) for h in handles]
        window = time.perf_counter() - t0
        in_window = sum(len(v) for v in compiles.ledger().values()) - n_ledger
        hits = PREFIX_HITS.value - hits0
        chunks = PREFILL_CHUNKS.value - chunks0
        n_tokens = sum(len(o) for o in outputs)
        say(f"window: {len(outputs)} requests completed, prompts "
            f"{sorted(p.size for p in prompts)}, {n_tokens} tokens "
            f"generated, {chunks} prefill chunks, {hits} prefix-cache "
            f"admissions, wall {window:.2f} s (host clock; not a benchmark)")
        say(f"compile ledger: {in_window} compiles inside the request "
            f"window; xla in window {watch.since(snap)}")
        for h, o in zip(handles, outputs):
            if h.error is not None or len(o) != cfg["new_tokens"] \
                    or not all(0 <= t < vocab for t in o):
                raise RuntimeError(
                    f"request {h.id}: {len(o)} tokens, error {h.error!r}")
        if in_window:
            raise RuntimeError(
                f"{in_window} program(s) compiled inside the request "
                f"window: {compiles.ledger_report()}")
        if hits < 1 or chunks <= len(prompts):
            raise RuntimeError(
                f"the window did not exercise the prefix cache ({hits} "
                f"hits) or chunked prefill ({chunks} chunks)")
        # the program's own step timeline (always on): every boundary of
        # the loop is stamped, so the phases cover the steps' wall
        steps = tracing.step_records(t0, t0 + window)
        wall = sum(r["wall"] for r in steps)
        accounted = 100.0 * sum(r[ph] for r in steps for ph in tracing.PHASES
                                if ph != "lock_wait") / wall if wall else 0.0
        say(f"step records in the window: {len(steps)}, phases cover "
            f"{accounted:.2f} % of their wall; request records: "
            f"{len(tracing.request_records(t0, t0 + window))}")
        if not steps or accounted < 99.0:
            raise RuntimeError(
                f"{len(steps)} step records in the window, phases cover "
                f"{accounted:.2f} % of the steps' wall (< 99: a boundary of "
                "Scheduler.step is no longer stamped)")
        # the launch's four parts are stamped inside `decode_launch` and
        # leave only the engine's counters out (2 % of a launch, or the
        # 0.1 ms that is of a chip-size one where the model is a toy); a
        # dry interval is made of two stamps of this run, in order
        launches = [r for r in steps if r["decode_launch"] > 0.0]
        launch = sum(r["decode_launch"] for r in launches)
        rest = launch - sum(r[p] for r in launches
                            for p in tracing.LAUNCH_PARTS)
        dry = [iv for r in steps for iv in r["dry"]]
        by_cause = {c: round(sum(r["dry_" + c] for r in steps), 4)
                    for c in tracing.DRY_CAUSES}
        say(f"launch parts leave {100.0 * rest / launch:.2f} % of "
            f"decode_launch ({1e6 * rest / len(launches):.0f} us a launch); "
            f"{len(dry)} dry intervals, seconds by cause {by_cause}")
        if not -1e-9 <= rest <= max(0.02 * launch, 1e-4 * len(launches)):
            raise RuntimeError(
                f"the launch's parts leave {rest:.6f} s of {launch:.6f} s of "
                "decode_launch (a boundary of SlotDecoder.decode_step is "
                "missing or charged twice)")
        t_end = time.perf_counter()
        if not dry or not all(t_run <= a < b <= t_end for a, b, _ in dry):
            raise RuntimeError(
                f"{len(dry)} dry intervals, not all inside the run "
                f"[{t_run}, {t_end}]: {dry[:4]}")
        launch_modes(steps, "serve")
        net.hybridize()
        i = longest(prompts)
        check_against_reference(net, prompts[i], outputs[i], "serve")
    finally:
        eng.shutdown(drain=False)
        compiles.disable()
    say(f"serve kernel branches: {kernel_branches(branches0) or 'none'} "
        "(the engine's programs are plain XLA; the reference forward "
        "dispatches through npx)")
    say(f"device peak bytes in use after serve: "
        f"{peak_memory(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# one chip: train
# ---------------------------------------------------------------------------

def gaps_to_reference(ref, cfg, seed, prompts, outs):
    """``(served tokens, by how much each one's logit lies below the plain
    reference's best)``, the reference run teacher-forced over the prompts
    and what was served."""
    tokens = onp.zeros((len(prompts), cfg["max_position_embeddings"]),
                       onp.int32)
    rows, served = [], []
    for b, (p, out) in enumerate(zip(prompts, outs)):
        seq = onp.concatenate([p, onp.asarray(out, onp.int32)])
        tokens[b, :seq.size - 1] = seq[:-1]
        rows += [(b, p.size - 1 + j) for j in range(len(out))]
        served += out
    logits = ref.logits_at(cfg, seed, tokens, rows)
    return served, logits.max(-1) - logits[onp.arange(len(served)), served]


def eva_phase(sizes, seed):
    """Two EvaByte requests through `ServeEngine`, one rolling in prefill and
    one in decode; every served token's reference logit within `gap_limit`
    of the reference's best."""
    import incubator_mxnet_tpu as mx
    from chipbench.reference import evabyte as ref
    from chipbench.runners.serve_eva import build_decoder
    from incubator_mxnet_tpu.telemetry import registry, tracing

    cfg = sizes["cfg"]
    t0 = time.perf_counter()
    dec = build_decoder(cfg, seed, ref, sizes["dtype"])
    eng = mx.serve.ServeEngine(
        dec, max_slots=2, max_len=cfg["max_position_embeddings"],
        page_tokens=sizes["page_tokens"], prefill_chunk=sizes["prefill_chunk"])
    rng = onp.random.default_rng([seed, 0xE7A])
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(onp.int32)
               for n, _ in sizes["requests"]]
    rolls = registry.counter("mx_serve_eva_rolls_total")
    before = rolls.value
    eng.start()
    try:
        handles = [eng.submit(p, new)
                   for p, (_, new) in zip(prompts, sizes["requests"])]
        outs = [list(eng.iter_tokens(h, timeout=600.0)) for h in handles]
    finally:
        eng.shutdown(drain=False)
    if rolls.value - before != len(prompts):
        raise AssertionError(f"expected one roll a request, counted "
                             f"{rolls.value - before}")
    launch_modes(tracing.step_records(t0), "eva")
    served, gap = gaps_to_reference(ref, cfg, seed, prompts, outs)
    say(f"eva: {len(served)} tokens of {len(prompts)} requests across "
        f"{rolls.value - before} rolls in {time.perf_counter() - t0:.1f} s; "
        f"widest gap to the reference's best logit {gap.max():.4f} "
        f"(limit {sizes['gap_limit']}), kernel branches {kernel_branches({})}")
    if not gap.max() <= sizes["gap_limit"]:
        raise AssertionError(f"eva: a served token lies {gap.max():.4f} "
                             "below the reference's best")


def pangu_phase(sizes, seed):
    """Three openPangu-Ultra-MoE requests through `ServeEngine`, prefilled
    in chunks and decoded together; every served token's reference logit
    within `gap_limit` of the reference's best, and the expert layer's own
    count of held pairs some, not all, of what was routed."""
    import incubator_mxnet_tpu as mx
    from chipbench.reference import pangu as ref
    from chipbench.runners.serve_pangu import build_decoder
    from incubator_mxnet_tpu.telemetry import registry, tracing

    cfg = sizes["cfg"]
    t0 = time.perf_counter()
    branches0 = kernel_branches({})
    dec = build_decoder(cfg, seed, ref, sizes["dtype"])
    eng = mx.serve.ServeEngine(
        dec, max_slots=sizes["max_slots"],
        max_len=cfg["max_position_embeddings"],
        page_tokens=sizes["page_tokens"], prefill_chunk=sizes["prefill_chunk"],
        prefix_reuse=False)
    rng = onp.random.default_rng([seed, 0x9A6])
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(onp.int32)
               for n, _ in sizes["requests"]]
    pairs = {k: registry.counter("mx_serve_moe_pairs_total",
                                 labels={"kind": k}) for k in ("held", "routed")}
    before = {k: c.value for k, c in pairs.items()}
    eng.start()
    try:
        handles = [eng.submit(p, new)
                   for p, (_, new) in zip(prompts, sizes["requests"])]
        outs = [list(eng.iter_tokens(h, timeout=600.0)) for h in handles]
    finally:
        eng.shutdown(drain=False)
    held, routed = (pairs[k].value - before[k] for k in ("held", "routed"))
    launch_modes(tracing.step_records(t0), "pangu")
    eng = dec = None            # the reference runs on an emptied chip
    served, gap = gaps_to_reference(ref, cfg, seed, prompts, outs)
    branches = {op: impls for op, impls in kernel_branches({}).items()
                if impls != branches0.get(op)}
    say(f"pangu: {len(served)} tokens of {len(prompts)} requests in "
        f"{time.perf_counter() - t0:.1f} s; held pairs {held} of {routed} "
        f"routed; widest gap to the reference's best logit {gap.max():.4f} "
        f"mean {gap.mean():.5f} (limit {sizes['gap_limit']}), kernel "
        f"branches {branches}")
    if not 0 < held < routed:
        raise AssertionError(f"pangu: {held} held pairs of {routed} routed: "
                             "the held share of the routing is all or nothing")
    if not gap.max() <= sizes["gap_limit"]:
        raise AssertionError(f"pangu: a served token lies {gap.max():.4f} "
                             "below the reference's best")


def build_trainer(cfg, seq, seed, mesh=None):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, optimizer
    from incubator_mxnet_tpu.models import bert
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    mx.random.seed(seed)
    net = getattr(bert, cfg["model"])(max_length=seq, dropout=0.1)
    net.initialize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        return ce(out[0], y)

    shardings = bert.tp_param_shardings(net) if mesh is not None else None
    return DataParallel(net, mlm_loss, optimizer.Adam(learning_rate=1e-4),
                        mesh=mesh, param_shardings=shardings)


def train_batch(cfg, batch, seq, seed):
    from incubator_mxnet_tpu import np

    rng = onp.random.RandomState(seed)
    draw = lambda: np.array(  # noqa: E731
        rng.randint(0, cfg["vocab"], (batch, seq)).astype("int32"))
    return draw(), draw()


def run_steps(trainer, tokens, labels, steps):
    from incubator_mxnet_tpu import amp

    amp.init("bfloat16")
    try:
        return [float(trainer.step(tokens, labels).asnumpy())
                for _ in range(steps)]
    finally:
        amp.deinit()


def train_phase(cfg, seed, watch, expect_kernels):
    from incubator_mxnet_tpu.ops import _dispatch
    from incubator_mxnet_tpu.telemetry import compiles

    import jax

    say(f"train phase: {cfg['model']} through DataParallel.step, amp "
        f"bfloat16, dropout 0.1, {cfg['steps']} steps on a repeated batch")
    compiles.reset()
    compiles.enable()
    try:
        for batch, seq in cfg["shapes"]:
            branches0 = _dispatch.choices()
            snap = watch.snapshot()
            trainer = build_trainer(cfg, seq, seed)
            tokens, labels = train_batch(cfg, batch, seq, seed)
            t0 = time.perf_counter()
            losses = run_steps(trainer, tokens, labels, cfg["steps"])
            wall = time.perf_counter() - t0
            entry = compiles.ledger("train.DataParallel.step")[-1]
            say(f"batch {batch} x seq {seq}: loss "
                f"{' '.join(f'{v:.4f}' for v in losses)}; step program "
                f"compiled in {entry['seconds']:.2f} s (first call), "
                f"{entry['tpu_custom_calls']} tpu_custom_call(s); "
                f"{cfg['steps']} steps wall {wall:.2f} s incl. compile")
            say(f"batch {batch} x seq {seq}: kernel branches at trace time "
                f"{kernel_branches(branches0)}; xla {watch.since(snap)}")
            if not onp.isfinite(losses).all() or not losses[-1] < losses[0]:
                raise RuntimeError(
                    f"batch {batch} x seq {seq}: loss not finite and "
                    f"decreasing: {losses}")
            if expect_kernels and not entry["tpu_custom_calls"]:
                raise RuntimeError(
                    f"batch {batch} x seq {seq}: the compiled step holds no "
                    "pallas kernel — every site took a composed path")
            del trainer
    finally:
        compiles.disable()
    say(f"device peak bytes in use after train: "
        f"{peak_memory(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# four chips: what exists only across chips, and what it is compared with
# ---------------------------------------------------------------------------

def shard_devices(array):
    return sorted({s.device.id for s in array.addressable_shards})


def sharded_serve_phase(cfg, seed, n_chips):
    import incubator_mxnet_tpu as mx

    import jax

    say(f"sharded serve: {cfg['model']} through ModelRegistry on a "
        f"tp={n_chips} serve_mesh vs a one-device engine, same requests")
    net = build_gpt(cfg, seed)
    vocab = net.word_embed.weight.shape[0]
    kw = dict(max_slots=cfg["max_slots"], max_len=cfg["max_len"])
    reg = mx.serve.ModelRegistry()
    reg.add("sharded", net, mesh=f"tp={n_chips}", **kw)
    reg.add("one", net, **kw)
    gw = mx.serve.Gateway(reg, seed=seed)
    try:
        engines = {name: gw._models[name].replicas[0].slots  # noqa: SLF001
                   for name in ("sharded", "one")}
        if not isinstance(engines["sharded"], mx.serve.ShardedSlotDecoder):
            raise RuntimeError("the 'sharded' model is not a "
                               "ShardedSlotDecoder")
        rng = onp.random.RandomState(seed + 1)
        for n in warm_lengths(engines["one"]):
            p = rng.randint(0, vocab, (n,)).astype(onp.int32)
            for name in engines:
                gw.generate(name, p, 2)
        prompts = make_requests(cfg, vocab, seed)
        outs = {}
        for name in engines:
            t0 = time.perf_counter()
            hs = [gw.submit(name, p, cfg["new_tokens"]) for p in prompts]
            outs[name] = [list(gw.iter_tokens(h)) for h in hs]
            say(f"{name}: {len(hs)} requests, "
                f"{sum(len(o) for o in outs[name])} tokens, wall "
                f"{time.perf_counter() - t0:.2f} s (host clock)")
        same = [a == b for a, b in zip(outs["sharded"], outs["one"])]
        say(f"greedy streams identical to the one-device engine: "
            f"{sum(same)}/{len(same)} requests")
        net.hybridize()
        for i in sorted({longest(prompts)} | {i for i, s in enumerate(same)
                                              if not s}):
            for name in engines:
                check_against_reference(net, prompts[i], outs[name][i],
                                        f"{name} request {i}")

        # every chip holds its share: KV pools and a column-parallel weight
        sh = engines["sharded"]
        pools = jax.tree.leaves(sh._pools)  # noqa: SLF001
        total = sum(x.nbytes for x in pools)
        per_dev = {}
        for x in pools:
            for s in x.addressable_shards:
                per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                    + s.data.nbytes
        ffn1 = sh._dec._params["layers"][0]["ffn1_w"]  # noqa: SLF001
        say(f"KV pool bytes total {total}, per device {per_dev}; pool leaf "
            f"on devices {shard_devices(pools[0])}, ffn1_w "
            f"{ffn1.sharding.spec} on devices {shard_devices(ffn1)} with "
            f"shard shape {ffn1.addressable_shards[0].data.shape} of "
            f"{ffn1.shape}")
        want = sorted(d.id for d in jax.devices()[:n_chips])
        if sorted(per_dev) != want or shard_devices(ffn1) != want:
            raise RuntimeError(
                f"not every device holds a shard: pools on "
                f"{sorted(per_dev)}, ffn1_w on {shard_devices(ffn1)}, "
                f"mesh {want}")
        if any(b * n_chips != total for b in per_dev.values()) \
                or ffn1.addressable_shards[0].data.shape[1] * n_chips \
                != ffn1.shape[1]:
            raise RuntimeError(
                f"shards are not 1/{n_chips} of the whole: {per_dev} of "
                f"{total}")
    finally:
        gw.shutdown(drain=False)


def mesh_train_phase(cfg, seed):
    import jax

    from incubator_mxnet_tpu.parallel.mesh import make_mesh

    batch, seq = cfg["mesh_shape"]
    say(f"mesh train: one {cfg['model']} step, batch {batch} x seq {seq}, "
        "on a dp=2 x tp=2 mesh vs the same step on one chip")
    tokens, labels = train_batch(cfg, batch, seq, seed)
    one = run_steps(build_trainer(cfg, seq, seed), tokens, labels, 1)[0]
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    trainer = build_trainer(cfg, seq, seed, mesh=mesh)
    four = run_steps(trainer, tokens, labels, 1)[0]
    names = [n for n, p in trainer.net.collect_params().items()
             if p.grad_req != "null"]
    qkv = next(a for n, a in zip(names, trainer.param_arrays)
               if n.endswith("qkv.weight"))
    say(f"loss one chip {one:.5f}, dp=2 x tp=2 {four:.5f} (same seed and "
        f"batch; dropout streams differ by layout); a qkv weight "
        f"{qkv._data.sharding.spec} lives on devices "  # noqa: SLF001
        f"{shard_devices(qkv._data)}")  # noqa: SLF001
    # dropout 0.1 draws differ between the layouts, so the two losses agree
    # to the noise of a mean over batch x seq tokens, not to rounding
    if not (onp.isfinite([one, four]).all() and abs(one - four) < 0.05):
        raise RuntimeError(f"mesh loss {four} vs one-chip loss {one}")
    if len(shard_devices(qkv._data)) != 4:  # noqa: SLF001
        raise RuntimeError("the tp-sharded weight is not on four devices")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip phases (builder's run)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and batches")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, to rehearse the control flow only")
    args = ap.parse_args(argv)

    # Nothing above touches jax. Importing the package places the persistent
    # compile cache (incubator_mxnet_tpu/_startup.py) before anything compiles.
    import incubator_mxnet_tpu  # noqa: F401
    from incubator_mxnet_tpu._startup import configure_compile_cache

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax reports {dev.platform!r} devices; "
              "this script proves nothing anywhere else", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        raise SystemExit(2)

    import os

    cache_dir = configure_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"jax {jax.__version__}, {len(devices)} x {dev.device_kind} "
        f"({dev.platform}); compile cache {cache_dir} holds {entries} "
        "entries at start"
        + ("; TINY sizes: a rehearsal, not the smoke" if args.tiny else ""))
    watch = CompileWatch()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_serve_phase(SERVE_SIZES[args.tiny], args.seed, 4)
        mesh_train_phase(TRAIN_SIZES[args.tiny], args.seed)
    else:
        serve_phase(SERVE_SIZES[args.tiny], args.seed, watch)
        eva_phase(EVA_SIZES[args.tiny], args.seed)
        pangu_phase(PANGU_SIZES[args.tiny], args.seed)
        # the toy widths are below a lane (128): no kernel site admits them
        train_phase(TRAIN_SIZES[args.tiny], args.seed, watch,
                    expect_kernels=not args.tiny)
    total = watch.since()
    say(f"{'warm' if total['cache_hits'] else 'cold'} run: xla backend "
        f"compile {total['seconds']:.2f} s over {total['requests']} "
        f"requests, persistent cache hits {total['cache_hits']} misses "
        f"{total['cache_misses']}; wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
