"""Benchmark driver: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}.

Primary metric (BASELINE.json north star): gluon model_zoo **ResNet-50-v1
training images/sec/chip** — whole fwd+bwd+SGD step jit-compiled through
the framework (DataParallel), batch 128 @ 224². BASELINE.md records no
in-tree reference table, so vs_baseline anchors on the widely-published
MXNet ResNet-50-v1 fp32 V100 figure (~370 img/s, e.g. the reference's
example/image-classification benchmark reports); >1 ⇒ one TPU chip beats
the reference's flagship GPU.

extras:
- bert_base_train_tokens_s / bert_mfu: gluon BERT-base (110M params,
  pallas flash attention) fwd+bwd+Adam, batch 64 @ seq 128, funnel AMP
  bf16; MFU is attention-inclusive: (6·N + 12·L·T·d)·tokens/s over the
  chip's bf16 peak (v5e: 197 TFLOP/s). bert_*_seq512: batch 32 @ seq
  512 — flash attention's regime (the T² term is 8.6% of FLOPs there).
  Round-4 step budget at seq 128 (measured by ablation): dropout ~15%,
  Adam state traffic ~11%, embedding grad+update ~5% of the step — the
  non-matmul floor under the MFU.
- gpt_decode_tokens_s: compiled KV-cache decode (one XLA program per
  shape signature), 8x512 GPT, batch 8, 224 new tokens; the vs_eager
  ratio compares against the per-token full re-forward the serving path
  used before round 4 (directly measured once at 1152x; the in-bench
  proxy times one eager forward, min-of-3).
- gpt_serve_tokens_s + gpt_serve_ttft_p50/p99_ms: mx.serve continuous
  batching under a seeded Poisson arrival trace (32 requests, 8 slots,
  varied prompts/budgets) — aggregate serving throughput incl. queueing
  and per-request time-to-first-token, with mean slot occupancy read
  from the telemetry registry (see SERVING.md).
- gpt_serve_spec_tokens_s (+ _accept_rate, _vs_base): the same trace
  with speculative decoding armed (spec_k=4, host n-gram draft) —
  greedy output is token-for-token identical, accepted drafts ride one
  batched verify program instead of per-token decode steps.
- gpt_serve_decode_step_1x/4x_pages_ms (+ _vs_4x_pages): median decode
  step wall time with the KV pool sized 1x vs 4x — the per-layer
  donated pool layout keeps the ratio ~1 (step cost is O(active
  tokens), not O(n_pages)).
- gpt_serve_prefix_tokens_s (+ _base_tokens_s/_speedup/_hit_rate) and
  gpt_serve_kv_bytes_per_slot: shared-system-prompt workload through the
  paged KV cache with prefix reuse ON vs OFF (same seeded trace) — the
  speedup is the per-request prefill cost the prefix cache removes; the
  bytes/slot figure is the paged pool's resident HBM per decode slot.
- gpt_serve_longprompt_ttft_p99_ms vs _unchunked_ttft_p99_ms: dense
  short-request traffic with long-prompt arrivals, chunked prefill
  (MXNET_SERVE_PREFILL_CHUNK) vs whole-prompt prefill on the same
  arrival trace — chunking bounds how long one long prompt can stall
  everyone else's first token.
- gpt_gateway_{high,normal,low}_ttft_p50/p99_ms + gpt_gateway_preemptions
  + gpt_gateway_<tenant>_tokens_s: multi-tenant gateway trace replay —
  two co-resident GPT models behind one serve.Gateway, three tenants
  across three priority tiers on a seeded bursty (Markov-modulated)
  trace from tools/loadgen; per-tier TTFT, preemption total, per-tenant
  token rates (SERVING.md §gateway).
- gpt_serve_elastic_chips_hours_ratio (+ _scale_events,
  _ttft_compliance, _tokens_s): the elastic replica control plane on a
  seeded diurnal day — controller-live (AutoscaleAdvisor →
  ReplicaSetController spawns/drains mid-replay, every spawn warmed
  before routing) vs a static peak fleet; the ratio is live
  replica-seconds over the static fleet's, gated < 1 (SERVING.md
  §elastic replicas).
- gpt_serve_sharded_tokens_s vs _1dev_tokens_s (+ _ttft_p50/p99_ms,
  _replicas): the same seeded trace through 2 replicas x tp=4
  mesh-sharded engines behind the gateway router vs one unsharded
  single-device replica. Needs 8 devices in THIS process; with fewer
  the full run records it as skipped. `--serve-sharded-only
  --virtual-cpu` runs it alone on eight virtual CPU devices (asked for
  by name, stamped in its JSON line): a layout rehearsal whose wall
  rates mean nothing; the durable numbers are
  gpt_serve_sharded_kv_bytes_per_device (measured: each device holds
  1/tp of the paged KV pools — the HBM-capacity scaling story) and
  gpt_serve_sharded_collective_bytes_per_token (static decode-HLO
  collective traffic — the cost the row/column-parallel layout
  minimizes; gated lower-is-better).
- gpt_serve_traced/untraced_tokens_s + gpt_serve_tracing_overhead_pct:
  the same reduced serve trace with span tracing off then on (adjacent
  runs) — the measured cost of per-request tracing on the serving hot
  path (TELEMETRY.md; the off-path cost with MXNET_TELEMETRY unset is
  gated <3% separately in tests/test_tracing.py).
- collective_step_off/fleet_ms + collective_wrapper_overhead_pct: one
  jitted shard_map step through the `parallel.collectives` wrappers
  (all_reduce + ring_permute) with fleet telemetry off vs armed,
  adjacent legs — the fleet census is a trace-time count, so the armed
  program must execute as a dead branch (<3% contract, TELEMETRY.md
  §fleet; gated structurally in tests/test_fleet.py).
- resnet50_fp32/int8_infer_img_s: batch-64 serving, interleaved
  fp32/int8 rounds (best-of-rounds wall rates + median wall ratio).
  Wall rates include host dispatch; resnet50_int8_vs_fp32_device is
  the XPlane device-time ratio of the same programs.
- dot_framework_ms vs dot_rawjax_ms: (1024²)·(1024²) fp32 matmul through
  the NDArray funnel vs raw jitted jax — the gap is eager per-op dispatch
  overhead (reference opperf anchor: 0.215 ms on V100).
- dispatch_floor_ms: trivial chained jitted op — the per-program
  dispatch floor every per-op latency inherits.

Every JSON line carries `platform`, `device_kind` and `device_count` as jax
reports them. The full run and `--serve-only` refuse to start without a TPU,
MFU refuses a `device_kind` it has no peak for, and any sub-bench that lands
in extras["errors"] makes the exit code non-zero.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as onp

BASELINE_V100_RESNET50_IMG_S = 370.0
# device_kind -> (roofline key of telemetry.kernels/roofline, bf16 peak
# TFLOP/s). Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s.
# A device that is not listed is an error, never a default.
_CHIPS = {"TPU v5 lite": ("v5e", 197.0)}


def _device_stamp():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _require_tpu():
    stamp = _device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax reports {stamp} — a CPU run "
            "yields counts and correctness (the tests), never a speed")
    return stamp


def _chip():
    """(roofline key, bf16 peak TFLOP/s) of the device this process runs
    on; raises for a device_kind the table has no peak for."""
    kind = _device_stamp()["device_kind"]
    if kind not in _CHIPS:
        raise RuntimeError(
            f"no bf16 peak on record for device_kind {kind!r} "
            f"(known: {sorted(_CHIPS)}); MFU is not computed against a "
            "guessed peak")
    return _CHIPS[kind]


def _emit(metric, value, unit, extras, **more):
    """The ONE JSON line, stamped with the device it ran on; a non-empty
    extras["errors"] fails the process after the line is out."""
    line = {"metric": metric, "value": value, "unit": unit, **more,
            **_device_stamp(), "extras": extras}
    print(json.dumps(line))
    if extras.get("errors"):
        raise SystemExit(1)


# Timing discipline: dispatch is asynchronous, so every timed region ends in
# `block_until_ready` (NDArray: `wait_to_read`) on its last result; chained
# iterations keep the device queue full between the two clock reads.


def bench_dot_framework(n=1024, iters=100, warmup=10):
    """dot through the NDArray funnel — measures the full eager path."""
    from incubator_mxnet_tpu import np

    rng = onp.random.RandomState(0)
    a = np.array(rng.uniform(-1, 1, (n, n)).astype("float32"))
    # pre-contracted b: chained dots decay toward zero instead of
    # overflowing, so the loop body is exactly ONE op dispatch
    b = np.array((rng.uniform(-1, 1, (n, n)) / n).astype("float32"))
    acc = a
    for _ in range(warmup):
        acc = np.dot(acc, b)
    acc.wait_to_read()
    t0 = time.perf_counter()
    for _ in range(iters):
        acc = np.dot(acc, b)   # chained: each dot feeds the next
    acc.wait_to_read()
    return (time.perf_counter() - t0) / iters * 1000.0


def bench_dot_rawjax(n=1024, iters=100, warmup=10):
    import jax
    import jax.numpy as jnp

    rng = onp.random.RandomState(0)
    a = jnp.asarray(rng.uniform(-1, 1, (n, n)).astype("float32"))
    b = jnp.asarray((rng.uniform(-1, 1, (n, n)) / n).astype("float32"))
    f = jax.jit(lambda x, y: x @ y)
    acc = a
    for _ in range(warmup):
        acc = f(acc, b)
    acc.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        acc = f(acc, b)
    acc.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1000.0


def bench_dot_pair(rounds=3):
    """Framework-vs-raw dot in INTERLEAVED rounds with a median-of-ratios
    statistic, like the int8/fp32 pair: per-op latency is host dispatch,
    which shares the machine's cores with everything else in the process,
    so adjacent rounds and a median reject a load spike that two benches
    run minutes apart would read as a difference."""
    ratios = []
    fw_best, raw_best = float("inf"), float("inf")
    for _ in range(rounds):
        fw = bench_dot_framework(iters=50)
        raw = bench_dot_rawjax(iters=50)
        fw_best = min(fw_best, fw)
        raw_best = min(raw_best, raw)
        ratios.append(fw / raw)
    ratios.sort()
    return fw_best, raw_best, ratios[len(ratios) // 2]


def bench_dispatch_floor(iters=100):
    """Per-program dispatch+execute floor: a trivial chained jitted op —
    the lower bound every per-op latency metric above inherits."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    acc = jnp.zeros(())
    for _ in range(10):
        acc = f(acc)
    acc.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        acc = f(acc)
    acc.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1000.0


def bench_flash_long_context(T=32768, B=1, H=8, D=64, iters=3):
    """Streaming flash attention in its HOME regime: T=32k, where the
    (B, H, T, T) score matrix is ~17 GB bf16 / ~34 GB f32 and the XLA
    path cannot compile at all (see ops/flash_attention.py
    _XLA_ATTN_BYTES_LIMIT) — the pallas kernel's O(T) memory is the only
    option. Forward-only tokens/s; the long-context capability anchor
    (reference has NO attention kernel at any length — SURVEY §2.4)."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.flash_attention import flash_attention

    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                impl="pallas"))
    o = f(q, k, v)
    o.block_until_ready()                    # compile + warm
    t0 = time.perf_counter()
    acc = q
    for _ in range(iters):
        acc = f(acc, k, v)           # o is (B,H,T,D): chain it as q
    acc.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    return B * T / dt


def bench_input_pipeline(n_images=512, batch=64, epochs=2):
    """Real-JPEG input pipeline images/sec: RecordIO pack → ImageRecordIter
    (cv2 decode, crop/mirror augment, uint8 batch upload, device-side
    cast+NCHW). Reported next to the synthetic-tensor train number; on
    this runner the HOST HAS ONE CPU CORE, so this is the per-core
    pipeline throughput (the reference's C++ pipeline assumes tens of
    vCPUs — scale linearly with cores).

    Methodology / ownership note: this bench runs in a SUBPROCESS
    (`--pipeline-only`, see `_bench_input_pipeline_subprocess`) so
    decode-thread/device contention can't poison the other benches; the
    child re-pays cold imports + thread-pool/JIT warmup inside its own
    wall clock, so its series is not comparable with an in-process
    number. Ownership: the rate is recorded as
    `mx_input_pipeline_images_per_sec` (+ `mx_input_pipeline_host_cores`)
    in the child's telemetry registry, and the child's registry dump is
    round-tripped over stdout into the PARENT registry and BENCH extras
    (`_bench_input_pipeline_subprocess`), so the committed number and the
    registry dump are one artifact — any future drift is attributable
    from the registry, not folklore."""
    import os
    import tempfile

    from incubator_mxnet_tpu import io as mxio
    from incubator_mxnet_tpu import recordio

    import shutil

    rng = onp.random.RandomState(0)
    d = tempfile.mkdtemp(prefix="bench_pipe_")
    rec_path = os.path.join(d, "imgs.rec")
    w = recordio.MXIndexedRecordIO(os.path.join(d, "imgs.idx"),
                                   rec_path, "w")
    for i in range(n_images):
        img = rng.randint(0, 255, (256, 256, 3), dtype=onp.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=90))
    w.close()
    it = mxio.ImageRecordIter(path_imgrec=rec_path,
                              data_shape=(3, 224, 224), batch_size=batch,
                              shuffle=True, rand_crop=True,
                              rand_mirror=True, preprocess_threads=8,
                              prefetch_buffer=4)
    try:
        best = 0.0
        for _ in range(epochs + 1):   # first epoch warms decode pools
            cnt = 0
            t0 = time.perf_counter()
            for b in it:
                b.data[0].wait_to_read()
                cnt += b.data[0].shape[0]
            best = max(best, cnt / (time.perf_counter() - t0))
            it.reset()
    finally:
        it.close()
        shutil.rmtree(d, ignore_errors=True)
    # metric ownership (see docstring): the registry is the audit trail
    from incubator_mxnet_tpu.telemetry import registry as _telem

    _telem.gauge("mx_input_pipeline_images_per_sec",
                 "ImageRecordIter throughput, this host").set(best)
    _telem.gauge("mx_input_pipeline_host_cores",
                 "cpu cores the pipeline had").set(os.cpu_count() or 1)
    return best


def bench_resnet50_train(batch=128, iters=20, warmup=2):
    """images/sec: compiled train step (fwd+bwd+SGD) on gluon ResNet-50."""
    from incubator_mxnet_tpu import gluon, np, optimizer
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    net = resnet50_v1()
    net.initialize()
    rng = onp.random.RandomState(0)
    # deferred shape inference before the compiled step traces
    net(np.array(rng.uniform(-1, 1, (1, 3, 224, 224)).astype("float32")))
    dp = DataParallel(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                      optimizer.SGD(learning_rate=0.01, momentum=0.9))
    x = np.array(rng.uniform(-1, 1, (batch, 3, 224, 224)).astype("float32"))
    y = np.array(rng.randint(0, 1000, (batch,)).astype("int32"))
    loss = None
    for _ in range(warmup):
        loss = dp.step(x, y)
    loss.wait_to_read()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = dp.step(x, y)   # steps chain through the parameters
    loss.wait_to_read()
    dt = (time.perf_counter() - t0) / iters
    return batch / dt


def bench_bert_train(batch=64, seq=128, iters=20, warmup=2,
                     trace_check=False):
    """tokens/sec + MFU: compiled train step on gluon BERT-base (flash),
    funnel-level AMP bf16 (activations bf16, fp32 master params).

    MFU accounting is attention-INCLUSIVE: per token the model spends
    6·N parameter FLOPs (fwd 2N + bwd 4N) PLUS 12·L·T·d attention FLOPs
    (QK^T and PV, each 2·T·d per head-layer fwd, 2x that backward) —
    the r3 formula omitted the attention term, flattering short-seq
    points (VERDICT r3 weak #4)."""
    from incubator_mxnet_tpu import amp, gluon, np, optimizer
    from incubator_mxnet_tpu.models.bert import bert_base
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    vocab = 30522
    net = bert_base(max_length=seq, dropout=0.1)
    net.initialize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        mlm_scores, _ = out
        # 3D CE (axis=-1): same math as reshape(-1, vocab), minus a
        # relayout of the 500 MB logits tensor
        return ce(mlm_scores, y)

    dp = DataParallel(net, mlm_loss, optimizer.Adam(learning_rate=1e-4))
    rng = onp.random.RandomState(0)
    tokens = np.array(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    labels = np.array(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    loss = None
    amp.init("bfloat16")
    try:
        for _ in range(warmup):
            loss = dp.step(tokens, labels)
        loss.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = dp.step(tokens, labels)  # chained through the parameters
        loss.wait_to_read()
        dt = (time.perf_counter() - t0) / iters
    finally:
        amp.deinit()  # AMP scope is local to this bench
    tokens_s = batch * seq / dt
    n_params = sum(onp.prod(p.shape)
                   for p in net.collect_params().values())
    n_layers, units = 12, 768
    flops_per_token = (6.0 * float(n_params)
                       + 12.0 * n_layers * seq * units)
    mfu = flops_per_token * tokens_s / (_chip()[1] * 1e12)
    if trace_check:
        amp.init("bfloat16")
        try:
            _TRACE_CHECK[seq] = _bert_trace_crosscheck(
                dp, tokens, labels, flops_per_token, batch, seq)
        finally:
            amp.deinit()
    return tokens_s, mfu


# per-seq results of the last _bert_trace_crosscheck (main() reads them
# into extras after bench_bert_train returns)
_TRACE_CHECK: dict = {}


def _bert_trace_crosscheck(dp, tokens, labels, flops_per_token, batch,
                           seq, iters=3):
    """Trace-measured MFU vs the hand-derived formula: re-run a few
    steps under the device profiler and divide the formula's FLOPs by
    MEASURED device time (`telemetry.kernels.program_mfu`) — the
    cross-check that catches the formula drifting from what the chip
    actually executes. Returns {"trace_mfu", "top_kernel_gbs",
    "attributed_frac"} or None when the backend yields no ``/device:``
    trace lane (CPU hosts: wall-clock MFU is the only claim there)."""
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.telemetry import kernels

    profiler.start()
    try:
        loss = None
        for _ in range(iters):
            loss = dp.step(tokens, labels)
        loss.wait_to_read()
    finally:
        profiler.stop()
    events = profiler.device_events()
    has_device_lane = any(
        e.get("ph") == "M" and e.get("name") == "process_name"
        and str((e.get("args") or {}).get("name", ""))
        .startswith("/device:") for e in events)
    if not has_device_lane:
        return None
    chip, peak_tflops = _chip()
    c = kernels.census(events, device=chip)
    dev_s = c["meta"]["named_us"] * 1e-6
    trace_mfu = kernels.program_mfu(
        flops_per_token * batch * seq, iters, dev_s,
        peak_tflops=peak_tflops)
    top = kernels.top_bandwidth_bound(c, 1)
    return {"trace_mfu": trace_mfu,
            "top_kernel_gbs": top[0]["achieved_gbs"] if top else None,
            "attributed_frac": c["meta"]["attributed_frac"]}


def bench_train_goodput(steps=24, batch=16):
    """train_goodput_frac: fraction of wall seconds the goodput ledger
    attributes to compute over a short REAL estimator fit (dense net,
    in-memory dataset through the DataLoader) — exercises the lease
    seams end-to-end exactly as production wiring does, so the number
    regressing means the ledger or the loop changed, not the model."""
    from incubator_mxnet_tpu import gluon, np
    from incubator_mxnet_tpu.gluon.contrib.estimator import Estimator
    from incubator_mxnet_tpu.gluon.data.dataloader import DataLoader
    from incubator_mxnet_tpu.gluon.data.dataset import ArrayDataset
    from incubator_mxnet_tpu.telemetry import goodput

    rng = onp.random.RandomState(3)
    X = rng.uniform(-1, 1, (steps * batch, 32)).astype("float32")
    Y = (X @ rng.uniform(-1, 1, (32, 1)).astype("float32"))
    net = gluon.nn.Dense(1)
    net.initialize()
    net(np.array(X[:2]))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    est = Estimator(net, gluon.loss.L2Loss(), trainer=trainer)
    import logging

    est.logger.setLevel(logging.ERROR)   # keep the bench output clean
    loader = DataLoader(ArrayDataset(X, Y), batch_size=batch,
                        num_workers=0)
    was_enabled = goodput.is_enabled()
    goodput.reset()
    goodput.enable()
    try:
        est.fit(loader, epochs=1)
        rep = goodput.report()
    finally:
        if not was_enabled:
            goodput.disable()
        goodput.reset()
    return rep["goodput_frac"]


def _bench_input_pipeline_subprocess(timeout=900):
    """Run the input-pipeline bench in a FRESH process (bench.py
    --pipeline-only): the iterator spawns native decode threads and
    touches the device for batch upload, and isolating that in its own
    process (a) matches how training scripts actually run the pipeline
    and (b) guarantees a pipeline wedge can't poison the remaining
    benches. The child needs the chip, and a chip belongs to one process:
    it must run before this process has imported jax."""
    import subprocess

    if "jax" in sys.modules:
        raise RuntimeError(
            "the input-pipeline child needs the chip: start it before "
            "this process imports jax")

    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--pipeline-only"],
        capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(
            f"pipeline subprocess rc={out.returncode}: {out.stderr[-800:]}")
    rate = cores = None
    for line in reversed(out.stdout.strip().splitlines()):
        if line.startswith("REGISTRY ") and cores is None:
            # re-own the child registry's pipeline gauges in THIS process
            # so the parent's registry dump carries the committed metric
            # (the child's registry dies with it)
            try:
                series = json.loads(line[len("REGISTRY "):])
                cores = series.get("mx_input_pipeline_host_cores")
                from incubator_mxnet_tpu.telemetry import registry as _telem

                for name, value in series.items():
                    if value is not None:
                        _telem.gauge(name).set(value)
            except Exception as e:
                print(f"pipeline registry round-trip failed: {e}",
                      file=sys.stderr)
            continue
        if rate is None:
            try:
                rate = float(line)
            except ValueError:
                continue
    if rate is None:
        raise RuntimeError(
            f"no rate in pipeline output: {out.stdout[-400:]}")
    # a degenerate run (empty/corrupt pack → 0 batches) must land in
    # extras["errors"], not be recorded as a legitimate 0.0 metric
    if not (rate > 0.0 and rate == rate and rate != float("inf")):
        raise RuntimeError(f"degenerate pipeline rate {rate!r}")
    return rate, cores


def bench_gpt_decode(batch=8, prompt=32, new_tokens=224):
    """Compiled KV-cache decode tokens/s on an 8-layer x 512-unit GPT
    (~30M params), batch 8, 224 generated tokens — ONE XLA program
    (prefill + lax.scan decode, models/decoding.py).

    Denominators (VERDICT r5 Do-this #6 — honest baseline first):

    - **nocache compiled** (the headline comparison,
      `gpt_decode_nocache_compiled_tokens_s`): a MEASURED compiled
      no-KV-cache decode — one fixed-shape XLA program re-forwarding the
      full padded (prompt+new_tokens) sequence once per generated token,
      compiled exactly once. This is what a serving loop without a cache
      actually runs on XLA (fixed shapes avoid per-length recompiles),
      so the ratio vs it is the fair cache-vs-no-cache speedup. Measured
      over `_NOCACHE_STEPS` real re-forwards, extrapolated linearly (the
      program is shape-constant, so per-step cost is too).
    - **eager loop estimate** (demoted to a NOTE in extras): new_tokens x
      (one compiled forward at the mean generated length). It models the
      pre-round-4 eager serving loop but ignores its ~new_tokens XLA
      recompiles (measured directly once at 1152x in round 4) and uses an
      estimated, not measured, loop — kept only as provenance for the
      historical `gpt_decode_vs_eager_loop` series."""
    from incubator_mxnet_tpu import np
    from incubator_mxnet_tpu.models.gpt import GPTModel

    vocab = 8000
    total = prompt + new_tokens
    net = GPTModel(vocab, 512, 2048, 8, 8, max_length=total, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(0)
    tokens = np.array(rng.randint(0, vocab, (batch, prompt)).astype("int32"))

    out = net.generate(tokens, new_tokens)      # compile + warm
    out.wait_to_read()
    best_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = net.generate(tokens, new_tokens)
        out.wait_to_read()
        best_dt = min(best_dt, time.perf_counter() - t0)
    tokens_s = batch * new_tokens / best_dt

    # -- nocache compiled decode: fixed-shape full re-forward per token --
    _NOCACHE_STEPS = 24          # shape-constant program: sample + scale
    full = np.array(rng.randint(0, vocab, (batch, total)).astype("int32"))
    logits = net(full)
    logits.wait_to_read()                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(_NOCACHE_STEPS):
        logits = net(full)                      # queued on one stream
    logits.wait_to_read()
    per_fwd = (time.perf_counter() - t0) / _NOCACHE_STEPS
    nocache_tokens_s = batch / per_fwd          # one token per re-forward
    vs_nocache = (per_fwd * new_tokens) / best_dt

    # -- demoted eager-loop estimate (note only) --
    mean_len = prompt + new_tokens // 2
    half = np.array(rng.randint(0, vocab,
                                (batch, mean_len)).astype("int32"))
    lg = net(half)
    lg.wait_to_read()                           # compile + warm
    best_fwd = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        lg = net(half)
        lg.wait_to_read()
        best_fwd = min(best_fwd, time.perf_counter() - t0)
    eager_est_ratio = best_fwd * new_tokens / best_dt
    return tokens_s, nocache_tokens_s, vs_nocache, eager_est_ratio


def bench_gpt_serve(requests=32, max_slots=8, prompt_max=64, new_max=96,
                    mean_interarrival_s=0.03, seed=0, spec_k=0,
                    draft=None, _return_engine_stats=False):
    """Continuous-batching serving (mx.serve) under a SEEDED Poisson
    arrival trace: 32 requests with varied prompt lengths and token
    budgets arrive at exp(λ)-spaced times and share `max_slots` decode
    slots of one persistent compiled program pair (SERVING.md).

    Reported: aggregate generated tokens/s over the whole trace (first
    submit → last completion — includes queueing, so it is a SERVING
    number, not the batch-decode ceiling `gpt_decode_tokens_s`), TTFT
    p50/p99 (submit → first token, prefill-bound + queue wait), and the
    mean slot occupancy sampled from the telemetry registry after every
    step (the registry owns the series; the bench just reads it).

    ``spec_k``/``draft`` arm speculative decoding on the same trace
    (``draft="self"`` drafts with the target net itself — the
    harness-overhead floor; greedy parity makes the token streams
    identical either way). With ``_return_engine_stats`` the return
    grows a 5th element: the engine's ``spec_stats()`` dict.

    Loud-failure contract: a degenerate run (any failed request, zero
    tokens, non-finite rate) raises — it must land in extras["errors"],
    never pass as a small number."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.decoding import GPTDecoder
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.telemetry import registry as _telem

    vocab = 8000
    max_len = 192                       # prompt (≤64) + budget (≤96) + slack
    net = GPTModel(vocab, 512, 2048, 8, 8, max_length=max_len, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, (int(rng.randint(8, prompt_max)),))
               .astype(onp.int32) for _ in range(requests)]
    budgets = [int(rng.randint(new_max // 2, new_max))
               for _ in range(requests)]
    arrivals = onp.cumsum(rng.exponential(mean_interarrival_s, requests))

    kw = {}
    if spec_k:
        kw = {"spec_k": spec_k,
              "draft": GPTDecoder(net) if draft == "self" else draft}
    engine = serve.ServeEngine(net, max_slots=max_slots, max_len=max_len,
                               **kw)
    # warm every program the trace will touch (prefill buckets 32 and 64
    # + the decode program) so compile time stays out of the clock
    for warm_len in (16, 48):
        engine.generate(onp.resize(prompts[0], warm_len), 2)
    occ_gauge = _telem.gauge("mx_serve_slot_occupancy")

    handles = []
    occ_samples = []
    i = 0
    t0 = time.perf_counter()
    while i < requests or not all(h.done for h in handles):
        now = time.perf_counter() - t0
        while i < requests and arrivals[i] <= now:
            handles.append(engine.submit(prompts[i], budgets[i]))
            i += 1
        progressed = engine.step()
        if handles:
            occ_samples.append(float(occ_gauge.value or 0.0))
        if not progressed and i < requests:
            # clamp: the next arrival may have passed between the `now`
            # snapshot above and this recompute (negative sleep raises)
            wait = arrivals[i] - (time.perf_counter() - t0) \
                if arrivals[i] > now else 0.001
            time.sleep(min(0.001, max(0.0, wait)))
    t_total = time.perf_counter() - t0
    spec_stats = engine.spec_stats()
    engine.shutdown(drain=True)

    failed = [h for h in handles if h.error is not None]
    if failed:
        raise RuntimeError(
            f"{len(failed)}/{requests} serve requests failed; first: "
            f"{type(failed[0].error).__name__}: {failed[0].error}")
    total_new = sum(len(h.tokens) for h in handles)
    ttfts = [h.ttft for h in handles]
    if total_new == 0 or any(t is None for t in ttfts) or t_total <= 0:
        raise RuntimeError(
            f"degenerate serve run: tokens={total_new}, ttfts={ttfts[:4]}")
    tokens_s = total_new / t_total
    if not (tokens_s > 0 and tokens_s == tokens_s
            and tokens_s != float("inf")):
        raise RuntimeError(f"degenerate serve rate {tokens_s!r}")
    p50 = float(onp.percentile(ttfts, 50)) * 1e3
    p99 = float(onp.percentile(ttfts, 99)) * 1e3
    mean_occ = float(onp.mean(occ_samples)) if occ_samples else 0.0
    if _return_engine_stats:
        return tokens_s, p50, p99, mean_occ, spec_stats
    return tokens_s, p50, p99, mean_occ


def bench_serve_decode_flat(factor=4, steps=40, seed=0):
    """Per-layer KV-pool layout evidence at the wall clock: median
    decode step time with the serving pool sized 1x vs ``factor``x
    (same model, same single live request). Under the donated
    per-layer layout every pool leaf aliases its output in place, so
    the step cost is O(active tokens) and the ratio stays ~1; the old
    stacked-pool layout rewrote the whole pool each step and the ratio
    tracked n_pages. Returns ``{"1x": ms, "<factor>x": ms, "ratio"}``.

    Loud-failure contract: a degenerate run (no live decode, zero/
    non-finite timings) raises — it lands in extras["errors"]."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import GPTModel

    vocab = 8000
    max_len = 192
    net = GPTModel(vocab, 512, 2048, 8, 8, max_length=max_len,
                   dropout=0.0)
    net.initialize()
    base_pages = 8 * max_len // 16      # the 8-slot default pool
    out = {}
    for tag, n_pages in (("1x", base_pages),
                         (f"{factor}x", base_pages * factor)):
        engine = serve.ServeEngine(net, max_slots=8, max_len=max_len,
                                   n_pages=n_pages)
        rng = onp.random.RandomState(seed)
        prompt = rng.randint(0, vocab, (16,)).astype(onp.int32)
        handle = engine.submit(prompt, max_len - 32)
        for _ in range(3):              # prefill + decode warmup
            engine.step()
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            engine.step()
            times.append(time.perf_counter() - t0)
        still_decoding = not handle.done
        engine.shutdown(drain=False)
        if not still_decoding:
            raise RuntimeError(
                "decode-flat bench retired its request mid-timing — "
                "timings mix decode with idle steps")
        ms = float(onp.median(times)) * 1e3
        if not (ms > 0 and ms == ms and ms != float("inf")):
            raise RuntimeError(f"degenerate decode step time {ms!r}")
        out[tag] = ms
    out["ratio"] = out[f"{factor}x"] / out["1x"]
    return out


def bench_gpt_serve_prefix(requests=16, max_slots=4, prefix_len=128,
                           tail_max=16, new_max=6, seed=0):
    """Shared-prefix reuse (ISSUE 6): every request carries the SAME
    system prompt plus a short unique tail. The same seeded burst runs
    twice — prefix reuse ON (the system prompt's KV pages are prefilled
    once and attached read-only to every later request) and OFF (every
    request pays the full prefill) — and the ratio is the per-request
    prefill cost the prefix cache removes.

    The reuse engine's warmup request intentionally populates the cache
    (steady-state serving of a hot system prompt IS the scenario).
    Returns a dict: reuse/base tokens_s, speedup, hit_rate (prefix hits /
    timed requests, from the registry), kv_bytes_per_slot (paged pool
    HBM per slot). Loud-failure contract: failed requests or degenerate
    rates raise."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.telemetry import registry as _telem

    vocab = 8000
    max_len = prefix_len + tail_max + new_max + 16
    net = GPTModel(vocab, 512, 2048, 8, 8, max_length=max_len, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(seed)
    system = rng.randint(0, vocab, (prefix_len,)).astype(onp.int32)
    prompts = [onp.concatenate([
        system,
        rng.randint(0, vocab, (int(rng.randint(2, tail_max)),))
        .astype(onp.int32)]) for _ in range(requests)]
    budgets = [int(rng.randint(max(2, new_max // 2), new_max + 1))
               for _ in range(requests)]

    def run(prefix_reuse):
        engine = serve.ServeEngine(net, max_slots=max_slots,
                                   max_len=max_len,
                                   prefix_reuse=prefix_reuse)
        # warm every chunk bucket + the decode program out of the clock
        # (for the reuse leg this also caches the system prompt — the
        # hot-prompt steady state the bench measures)
        engine.generate(prompts[0][:7], 2)
        engine.generate(prompts[0][:prefix_len // 2 + 3], 2)
        engine.generate(prompts[0], 2)
        hits0 = _telem.counter("mx_serve_prefix_hits_total").value
        t0 = time.perf_counter()
        handles = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
        while not all(h.done for h in handles):
            engine.step()
        dt = time.perf_counter() - t0
        hits = _telem.counter("mx_serve_prefix_hits_total").value - hits0
        kv_bytes = engine.kv_bytes_per_slot
        engine.shutdown(drain=True)
        failed = [h for h in handles if h.error is not None]
        if failed:
            raise RuntimeError(
                f"{len(failed)}/{requests} prefix-bench requests failed; "
                f"first: {type(failed[0].error).__name__}: "
                f"{failed[0].error}")
        toks = sum(len(h.tokens) for h in handles)
        if toks == 0 or dt <= 0:
            raise RuntimeError(
                f"degenerate prefix-bench run: tokens={toks}, dt={dt}")
        return toks / dt, hits, kv_bytes

    reuse_tok_s, hits, kv_bytes = run(True)
    base_tok_s, _, _ = run(False)
    if not (reuse_tok_s > 0 and base_tok_s > 0):
        raise RuntimeError(
            f"degenerate prefix rates: {reuse_tok_s!r}/{base_tok_s!r}")
    return {"reuse_tokens_s": reuse_tok_s,
            "base_tokens_s": base_tok_s,
            "speedup": reuse_tok_s / base_tok_s,
            "hit_rate": hits / requests,
            "kv_bytes_per_slot": kv_bytes}


def bench_gpt_serve_longprompt(shorts=24, longs=1, max_slots=8,
                               short_max=16, long_len=1152, new_max=4,
                               mean_interarrival_s=0.3, seed=0):
    """Chunked prefill vs whole-prompt prefill under long-prompt traffic
    (ISSUE 6): a steady subcritical stream of short requests with a very
    long prompt mixed in, replayed on the SAME seeded arrival schedule
    with `prefill_chunk=64` (the long prefill interleaves with everyone
    else's steps) and with `prefill_chunk >= long_len` (the pre-paging
    behavior: one monolithic prefill stalls the whole loop for its
    duration — ~1.6 s at 1152 tokens on the CPU test host, vs one
    ~0.2 s chunk step between which every other slot keeps moving).

    Reports TTFT p99 over the SHORT requests — the victims whose first
    token a long arrival delays; the long prompts themselves are the
    perpetrators (their own TTFT is inherently prefill-bound, and
    chunking trades a little of it for everyone else's latency), and at
    production long-prompt fractions (<1%) they sit above the 99th
    percentile anyway. The all-requests percentiles ride along in the
    returned dict for the record.

    Loud-failure contract: failed requests or degenerate TTFTs raise."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import GPTModel

    vocab = 8000
    max_len = long_len + new_max + 48
    net = GPTModel(vocab, 512, 2048, 8, 8, max_length=max_len, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(seed)
    n = shorts + longs
    prompts = [rng.randint(0, vocab, (int(rng.randint(4, short_max)),))
               .astype(onp.int32) for _ in range(n)]
    # the long prompts land mid-trace, with short traffic continuing
    # around them (a trailing long would have nobody left to victimize)
    long_idx = {n * (j + 1) // (longs + 1) for j in range(longs)}
    for i in long_idx:
        prompts[i] = rng.randint(0, vocab, (long_len,)).astype(onp.int32)
    budgets = [int(rng.randint(max(2, new_max // 2), new_max + 1))
               for _ in range(n)]
    arrivals = onp.cumsum(rng.exponential(mean_interarrival_s, n))

    # size the pool to the WORKLOAD, not max_slots × max_len: two long
    # residents plus short traffic — the paged allocator's HBM win (a
    # monolithic-slot engine would reserve max_slots * max_len here)
    pt = 16
    pages = (longs * -(-(long_len + new_max) // pt)
             + (max_slots - longs) * -(-(short_max + new_max) // pt)
             + 8)

    def run(prefill_chunk):
        engine = serve.ServeEngine(net, max_slots=max_slots,
                                   max_len=max_len, page_tokens=pt,
                                   n_pages=pages + 1,
                                   prefill_chunk=prefill_chunk,
                                   prefix_reuse=False)
        # warm every chunk bucket this trace can touch + decode
        for warm in (5, 20, 40, 70, 130, 260, long_len):
            if warm <= max_len - new_max:
                engine.generate(onp.resize(prompts[0], warm), 2)
        handles = []
        i = 0
        t0 = time.perf_counter()
        while i < n or not all(h.done for h in handles):
            now = time.perf_counter() - t0
            while i < n and arrivals[i] <= now:
                handles.append(engine.submit(prompts[i], budgets[i]))
                i += 1
            progressed = engine.step()
            if not progressed and i < n:
                wait = arrivals[i] - (time.perf_counter() - t0)
                time.sleep(min(0.001, max(0.0, wait)))
        engine.shutdown(drain=True)
        failed = [h for h in handles if h.error is not None]
        if failed:
            raise RuntimeError(
                f"{len(failed)}/{n} longprompt-bench requests failed; "
                f"first: {type(failed[0].error).__name__}: "
                f"{failed[0].error}")
        ttfts = [h.ttft for h in handles]
        if any(t is None or t <= 0 for t in ttfts):
            raise RuntimeError(f"degenerate TTFTs: {ttfts[:4]}")
        short_ttfts = [t for j, t in enumerate(ttfts)
                       if j not in long_idx]
        return (float(onp.percentile(short_ttfts, 99)) * 1e3,
                float(onp.percentile(ttfts, 99)) * 1e3)

    chunked_p99, chunked_all = run(64)
    unchunked_p99, unchunked_all = run(long_len)
    return {"chunked_p99_ms": chunked_p99,
            "unchunked_p99_ms": unchunked_p99,
            "chunked_all_p99_ms": chunked_all,
            "unchunked_all_p99_ms": unchunked_all}


def bench_gpt_gateway(requests=30, seed=0):
    """Multi-tenant gateway trace replay (SERVING.md §gateway): two
    co-resident GPT models behind one `serve.Gateway`, three tenants
    across the three priority tiers, driven by a SEEDED bursty trace
    from tools/loadgen (two-state Markov-modulated arrivals, lognormal
    prompt lengths — recorded-traffic shape, not Poisson).

    Reported per tier: TTFT p50/p99 (gateway submit → first token,
    queue wait and preemptions included); plus the preemption total and
    per-tenant tokens/s — the fairness/priority numbers the gateway
    exists to produce.

    Loud-failure contract: any failed request, zero completions, or a
    steady-state recompile (per-engine program counts must be constant
    across the replay) raises — it lands in extras["errors"], never
    passes as a small number."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import GPTModel

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    vocab, max_len = 8000, 128
    reg = serve.ModelRegistry(total_pages=120)
    for name, share in (("gpt-a", 2.0), ("gpt-b", 1.0)):
        net = GPTModel(vocab, 256, 1024, 4, 8, max_length=max_len,
                       dropout=0.0)
        net.initialize()
        reg.add(name, net, share=share, max_slots=4, max_len=max_len)
    gw = serve.Gateway(reg, tenants={
        "acme": {"weight": 3.0}, "beta": {"weight": 2.0},
        "crawl": {"weight": 1.0}})
    rng = onp.random.RandomState(seed)
    # warm every program the trace will touch (prefill chunk buckets
    # 16/32/64 + decode per model) so compile time stays out of the clock
    for name in ("gpt-a", "gpt-b"):
        for warm_len in (12, 24, 48):
            gw.generate(name, rng.randint(0, vocab, (warm_len,)), 2)
    programs_warm = gw.xla_program_counts()

    events = loadgen.synth_trace(
        requests, models={"gpt-a": 2.0, "gpt-b": 1.0},
        tenants={"acme": (3.0, "high"), "beta": (2.0, "normal"),
                 "crawl": (1.0, "low")},
        seed=seed, duration_s=0.8, prompt_mean=20, prompt_max=60,
        max_new_range=(4, 12))
    report = loadgen.replay(gw, events, vocab, timeout=120.0)
    programs_end = gw.xla_program_counts()
    gw.shutdown(drain=True)

    if report["failed"]:
        raise RuntimeError(
            f"{len(report['failed'])}/{requests} gateway requests "
            f"failed; first: {report['failed'][0]}")
    if report["completed"] == 0 or report["wall_s"] <= 0:
        raise RuntimeError(f"degenerate gateway run: {report}")
    if programs_end != programs_warm:
        raise RuntimeError(
            "steady-state recompile during gateway replay: "
            f"{programs_warm} -> {programs_end}")
    out = {"tiers": {}, "preemptions": report["preemptions"],
           "tenants": {}}
    for tier, t in report["per_tier"].items():
        out["tiers"][tier] = {
            "p50_ms": 1e3 * (loadgen.percentile(t["ttft"], 50) or 0.0),
            "p99_ms": 1e3 * (loadgen.percentile(t["ttft"], 99) or 0.0),
            "count": t["count"]}
    for tenant, t in report["per_tenant"].items():
        out["tenants"][tenant] = t["tokens"] / report["wall_s"]
    return out


def bench_gpt_serve_elastic(seed=0, max_replicas=2):
    """Elastic replica control plane on the diurnal day (SERVING.md
    §elastic replicas, ISSUE 18): the SAME seeded
    `loadgen.diurnal_trace` day (trough → steady → surge → flash burst)
    replayed through one tiny GPT model two ways — (a) CONTROLLER-LIVE:
    the fleet starts at ``min_replicas=1`` and the `AutoscaleAdvisor`'s
    recommendations (evaluated over real short-window occupancy/queue
    history) drive `ReplicaSetController` spawns and drains mid-replay;
    (b) STATIC PEAK: ``max_replicas`` engines pinned for the whole day.

    Durable metrics: the **chips·hours ratio** — live replica-seconds
    integrated from the controller's scale-event journal over the
    static fleet's ``max_replicas × wall`` — the capacity the
    controller hands back outside the surge; the live leg's high-tier
    `slo.gateway_ttft` compliance (riding the curve must not melt
    latency — threshold is CPU-generous because a spawn's warmup
    compiles on the step thread here; on TPU the programs come from the
    compile cache); the scale-event count; and the per-replica
    zero-post-publication-compile gate (every spawned replica had BOTH
    program families warmed BEFORE it took traffic).

    Loud-failure contract: failed requests on either leg, zero scale
    events, a post-publication compile on any live replica, SLO
    non-compliance, or a chips·hours ratio that doesn't clear the
    static fleet raises — it lands in extras["errors"], never passes
    as a small number."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import gpt_tiny
    from incubator_mxnet_tpu.serve.advisor import AutoscaleAdvisor
    from incubator_mxnet_tpu.telemetry import slo
    from incubator_mxnet_tpu.telemetry import timeseries as ts

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    vocab, max_len = 1000, 64
    rng = onp.random.RandomState(seed)

    def make_gateway(replicas):
        net = gpt_tiny(vocab_size=vocab, max_length=max_len, dropout=0.0)
        net.initialize()
        reg = serve.ModelRegistry(total_pages=24 * max_replicas)
        reg.add("gpt", net, max_slots=2, max_len=max_len,
                replicas=replicas)
        return serve.Gateway(reg, tenants={"acme": {"weight": 2.0},
                                           "beta": {"weight": 1.0}})

    def warm_all(gw):
        # drive every prefill chunk bucket + decode through EVERY
        # replica directly (the router won't round-robin reliably), out
        # of the measured window — the same families the controller's
        # own warmup covers for spawned replicas
        for rep in gw._models["gpt"].replicas:
            for warm_len in (12, 24, 48):
                seg = rep.sched.submit(
                    rng.randint(0, vocab, (warm_len,)).astype(onp.int32),
                    2)
                while not seg.done:
                    rep.sched.step()

    events, _segments = loadgen.diurnal_trace(
        models={"gpt": 1.0},
        tenants={"acme": (2.0, "high"), "beta": (1.0, "normal")},
        seed=seed, trough_s=4.0, steady_s=4.0, surge_s=4.0, burst_s=1.5,
        trough_rate=0.5, steady_rate=2.0, surge_rate=30.0,
        burst_rate=80.0, prompt_mean=16, prompt_max=36,
        max_new_range=(16, 26))

    # -- leg (a): controller-live from one replica --------------------------
    gw = make_gateway(1)
    ts.enable(interval_s=0.25, samples=8192)
    gw._advisor_period = 0.5
    gw._advisor_next_t = None
    gw._advisors = {"gpt": AutoscaleAdvisor(
        "gpt", up_occupancy=0.65, down_occupancy=0.25, fast_window_s=1.5,
        slow_window_s=4.0, cooldown_s=3.0, burst_queue=6)}
    ctl = gw.enable_elastic(min_replicas=1, max_replicas=max_replicas,
                            warm_lens=(12, 24, 48), warm_new=2)
    warm_all(gw)
    base_programs = {r.label: r.slots.xla_program_count()
                    for r in gw._models["gpt"].replicas}
    obj = slo.gateway_ttft("high", threshold_s=20.0, target=0.6,
                           name="elastic_live_high")
    try:
        t0 = time.monotonic()
        live = loadgen.replay(gw, events, vocab, timeout=300.0)
        t1 = time.monotonic()
        for _ in range(8):
            gw.step()                    # retire finished drains
        if live["failed"]:
            raise RuntimeError(
                f"{len(live['failed'])} live-leg requests failed; "
                f"first: {live['failed'][0]}")
        journal = ctl.scale_log()
        if not journal:
            raise RuntimeError(
                "controller produced zero scale events across the "
                "diurnal day — the advisor loop never closed")
        # zero post-publication compiles: every live replica's program
        # count still equals its publication/warmup snapshot
        for rep in gw._models["gpt"].replicas:
            want = ctl.warm_programs.get(rep.label,
                                         base_programs.get(rep.label))
            got = rep.slots.xla_program_count()
            if want is None or got != want:
                raise RuntimeError(
                    f"replica {rep.label} compiled after publication: "
                    f"{want} -> {got}")
        res = obj.evaluate()
        if not res["ok"]:
            raise RuntimeError(
                f"live-leg high-tier TTFT SLO violated: {res}")
        slo_compliance = res["compliance"]
        # chips·seconds: integrate replica count over the replay wall
        # from the journal (each entry's n is the post-mutation count)
        chip_s, n_prev, t_prev = 0.0, 1, t0
        for ev in journal:
            t = min(max(ev["t"], t0), t1)
            chip_s += n_prev * (t - t_prev)
            n_prev, t_prev = ev["n"], t
        chip_s += n_prev * (t1 - t_prev)
        live_wall = t1 - t0
    finally:
        slo.tracker().remove("elastic_live_high")
        gw.shutdown(drain=False)

    # -- leg (b): static peak fleet -----------------------------------------
    gw2 = make_gateway(max_replicas)
    try:
        warm_all(gw2)
        static = loadgen.replay(gw2, events, vocab, timeout=300.0)
        if static["failed"]:
            raise RuntimeError(
                f"{len(static['failed'])} static-leg requests failed; "
                f"first: {static['failed'][0]}")
    finally:
        gw2.shutdown(drain=False)

    ratio = (chip_s / live_wall) / float(max_replicas)
    if not (0.0 < ratio < 1.0):
        raise RuntimeError(
            f"elastic chips·hours ratio {ratio:.3f} does not clear the "
            f"static {max_replicas}-replica fleet (mean live replicas "
            f"{chip_s / live_wall:.2f})")
    return {
        "chips_hours_ratio": ratio,
        "scale_events": len(journal),
        "scale_ups": sum(1 for e in journal if e["direction"] == "up"),
        "ttft_compliance": slo_compliance,
        "live_completed": live["completed"],
        "static_completed": static["completed"],
        "live_tokens_s": sum(t["tokens"]
                             for t in live["per_tier"].values())
        / live["wall_s"],
    }


def bench_gpt_serve_disagg(seed=0, requests=20):
    """Disaggregated prefill/decode serving on the mixed-length trace
    (SERVING.md §disaggregated serving, ISSUE 19): the SAME seeded
    `loadgen.mixed_length_trace` blend — long-prompt/short-budget
    ``archive`` arrivals interleaved with short-prompt/long-budget
    ``chat`` arrivals — replayed through one tiny GPT at EQUAL
    hardware two ways: (a) DISAGGREGATED: 1 prefill + 1 decode
    replica, KV pages migrating at the prefill/decode boundary;
    (b) HOMOGENEOUS: 2 ``role="both"`` replicas with chunked prefill
    interleaving, same total page budget, same per-replica slots.

    Durable metrics: the **decode residency ratio** — the decode
    replica's time-mean resident decoding slot count over the
    homogeneous leg's per-replica mean (the split's whole point: the
    decode side's ~3x page share and prefill-free step loop hold more
    concurrent decodes on the same chips; gate ≥ 1.5x); the ``chat``
    tier's victim TTFT p99 (short requests must not pay for the long
    prompts ahead of them; gate: no worse than the chunked-prefill
    baseline with a CPU-noise allowance — on TPU the margin is real);
    the exact migration byte audit (bytes counter == pages counter x
    `SlotDecoder.page_bytes`); and the zero-steady-state-recompile
    gate on BOTH legs (per-replica program counts frozen after warmup,
    and the decode replica's ledger shows zero prefill families).

    Loud-failure contract: failed requests on either leg, a residency
    ratio under 1.5x, victim TTFT worse than the allowance, any
    steady-state recompile, a byte-audit mismatch, zero migrations, or
    prefill evidence on the decode replica raises — it lands in
    extras["errors"], never passes as a small number."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import gpt_tiny
    from incubator_mxnet_tpu.serve import disagg
    from incubator_mxnet_tpu.telemetry import registry

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    vocab, max_len, max_slots = 1000, 64, 8
    total_pages = 72            # equal budget both legs

    def make_gateway(disaggregated):
        net = gpt_tiny(vocab_size=vocab, max_length=max_len, dropout=0.0)
        net.initialize()
        reg = serve.ModelRegistry(total_pages=total_pages)
        if disaggregated:
            reg.add("gpt", net, prefill_replicas=1, decode_replicas=1,
                    max_slots=max_slots, max_len=max_len)
        else:
            reg.add("gpt", net, replicas=2, max_slots=max_slots,
                    max_len=max_len)
        return serve.Gateway(reg, tenants={"archive": {"weight": 1.0},
                                           "chat": {"weight": 2.0}})

    rng = onp.random.RandomState(seed)

    def warm(gw, disaggregated):
        # freeze every program family BEFORE the measured window. The
        # homogeneous replicas and the prefill replica warm both
        # families directly (a co-located fallback must not compile);
        # the decode replica warms ONLY through the migration plane so
        # its ledger stays prefill-free.
        m = gw._models["gpt"]
        reps = ([r for r in m.replicas if r.role != "decode"]
                if disaggregated else m.replicas)
        for rep in reps:
            for warm_len in (8, 20, 36):
                seg = rep.sched.submit(
                    rng.randint(0, vocab, (warm_len,)).astype(onp.int32),
                    2)
                while not seg.done:
                    rep.sched.step()
        if disaggregated:
            for warm_len in (8, 20, 36):
                h = gw.submit("gpt", rng.randint(
                    0, vocab, (warm_len,)).astype(onp.int32), 3)
                gw._drive_until([h], timeout=60.0)

    events = loadgen.mixed_length_trace(
        requests, "gpt", seed=seed, duration_s=0.25, long_frac=0.3,
        long_prompt=30, long_jitter=0.1, long_new_range=(2, 4),
        chat_prompt_mean=8, chat_new_range=(20, 28))

    def run_leg(gw, disaggregated):
        m = gw._models["gpt"]
        decode_reps = (m.role_replicas("decode") if disaggregated
                       else m.replicas)
        programs0 = gw.xla_program_counts(per_replica=True)
        handles, samples = [], []
        t0 = time.monotonic()
        i = 0
        while i < len(events) or not all(h.done for _, h in handles):
            now = time.monotonic() - t0
            while i < len(events) and events[i].t <= now:
                e = events[i]
                plen = min(e.prompt_len, max_len - e.max_new - 1)
                handles.append((e, gw.submit(
                    "gpt", onp.random.RandomState(e.seed).randint(
                        0, vocab, (plen,)).astype(onp.int32),
                    e.max_new, tenant=e.tenant, priority=e.priority)))
                i += 1
            gw.step()
            # decoding-resident slots per decode-capable replica (the
            # scheduler's decode-lane census, sampled every step)
            samples.append(sum(r.sched._n_decoding for r in decode_reps)
                           / len(decode_reps))
        wall = time.monotonic() - t0
        failed = [(h.id, h.state) for _, h in handles
                  if h.state != "done"]
        if failed:
            raise RuntimeError(
                f"{'disagg' if disaggregated else 'homogeneous'} leg: "
                f"{len(failed)} requests failed: {failed[:3]}")
        if gw.xla_program_counts(per_replica=True) != programs0:
            raise RuntimeError(
                f"{'disagg' if disaggregated else 'homogeneous'} leg: "
                f"steady-state recompile: {programs0} -> "
                f"{gw.xla_program_counts(per_replica=True)}")
        chat_ttft = [h.ttft for e, h in handles if e.tenant == "chat"
                     and h.ttft is not None]
        tokens = sum(len(h.tokens) for _, h in handles)
        return {
            "resident_mean": (sum(samples) / len(samples)) if samples
            else 0.0,
            "chat_ttft_p99_ms": loadgen.percentile(chat_ttft, 99) * 1e3,
            "tokens_s": tokens / wall,
        }

    def counter(name):
        return registry.report().get(name, {}).get("value", 0) or 0

    # -- leg (a): disaggregated 1p+1d ---------------------------------------
    gw = make_gateway(True)
    try:
        warm(gw, True)
        p0 = counter('mx_serve_page_migration_pages_total{model="gpt"}')
        b0 = counter('mx_serve_page_migration_bytes_total{model="gpt"}')
        dis = run_leg(gw, True)
        moved = counter(
            'mx_serve_page_migration_pages_total{model="gpt"}') - p0
        moved_b = counter(
            'mx_serve_page_migration_bytes_total{model="gpt"}') - b0
        if moved <= 0:
            raise RuntimeError(
                "disagg leg moved zero pages — the migration plane "
                "never engaged")
        page_bytes = gw._models["gpt"].replicas[0].slots.page_bytes
        if moved_b != moved * page_bytes:
            raise RuntimeError(
                f"migration byte audit failed: {moved_b} bytes != "
                f"{moved} pages x {page_bytes} B/page")
        families = disagg.decode_prefill_families(gw, "gpt")
        if families:
            raise RuntimeError(
                f"decode replica compiled prefill programs: {families}")
    finally:
        gw.shutdown(drain=False)

    # -- leg (b): homogeneous chunked-prefill baseline ----------------------
    gw2 = make_gateway(False)
    try:
        warm(gw2, False)
        hom = run_leg(gw2, False)
    finally:
        gw2.shutdown(drain=False)

    ratio = dis["resident_mean"] / max(hom["resident_mean"], 1e-9)
    if ratio < 1.5:
        raise RuntimeError(
            f"decode residency ratio {ratio:.2f} < 1.5x (disagg "
            f"{dis['resident_mean']:.2f} vs homogeneous per-replica "
            f"{hom['resident_mean']:.2f})")
    # victim TTFT: "no worse" with a CPU-generous allowance — here ONE
    # core runs the prefill replica's step loop serially while the
    # homogeneous leg spreads prefills over two, so the disagg leg
    # pays a host-serialization tax the TPU target doesn't have; the
    # gate still catches pathological regressions (queued-behind-long
    # TTFT blowups are order-of-magnitude, not 2x)
    if dis["chat_ttft_p99_ms"] > hom["chat_ttft_p99_ms"] * 2.0:
        raise RuntimeError(
            f"chat victim TTFT p99 regressed under disagg: "
            f"{dis['chat_ttft_p99_ms']:.1f}ms vs baseline "
            f"{hom['chat_ttft_p99_ms']:.1f}ms")
    return {
        "decode_resident_ratio": ratio,
        "decode_resident_mean": dis["resident_mean"],
        "baseline_resident_mean": hom["resident_mean"],
        "chat_ttft_p99_ms": dis["chat_ttft_p99_ms"],
        "baseline_chat_ttft_p99_ms": hom["chat_ttft_p99_ms"],
        "pages_migrated": moved,
        "bytes_migrated": moved_b,
        "tokens_s": dis["tokens_s"],
    }


def bench_gpt_serve_sharded(requests=16, max_slots=4, prompt_max=40,
                            new_max=20, tp=4, n_replicas=2, seed=0):
    """Pod-scale sharded serving (SERVING.md §pod-scale): the SAME
    seeded closed-loop request trace replayed through (a) one unsharded
    single-device replica and (b) ``n_replicas`` mesh-sharded
    `ShardedSlotDecoder` replicas (tp=4 each) behind the gateway's
    `ReplicaRouter` — identical model weights, identical prompts and
    budgets, identical pool sizing.

    Runs ONLY on a >= tp*n_replicas-device process. On the virtual CPU
    devices of ``--serve-sharded-only --virtual-cpu`` the wall rates are
    LAYOUT evidence (the sharded program pays real collective dispatch),
    not chip numbers, so they are report-only in bench_regress; the
    durable metrics are the
    HBM-capacity story (measured per-device KV pool bytes: the pools
    shard tp-way, so each device holds 1/tp of the cache) and the
    static per-token collective bytes read from the decode program's
    own HLO — the cost the row/column-parallel layout was chosen to
    minimize (3 tiny all-reduces per layer, zero hot-path all-gathers).

    Loud-failure contract: any failed request, zero tokens, non-finite
    rate, a steady-state recompile during either replay, traffic that
    never reaches one of the replicas, or a dirty `shardcheck_report`
    on the sharded decode family raises — it lands in
    extras["errors"], never passes as a small number."""
    import jax

    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models.gpt import GPTModel

    need = tp * n_replicas
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"bench_gpt_serve_sharded needs >= {need} devices, have "
            f"{len(jax.devices())} (`--serve-sharded-only --virtual-cpu` "
            "rehearses the layout on virtual CPU devices)")

    vocab, max_len = 8000, 80
    # d_model 256 / 4 heads / ffn 1024: every sharded axis divides tp=4
    net = GPTModel(vocab, 256, 1024, 4, 4, max_length=max_len,
                   dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, (int(rng.randint(8, prompt_max)),))
               .astype(onp.int32) for _ in range(requests)]
    budgets = [int(rng.randint(new_max // 2, new_max))
               for _ in range(requests)]

    def run(mesh, replicas):
        reg = serve.ModelRegistry()
        reg.add("m", net, replicas=replicas, mesh=mesh,
                max_slots=max_slots, max_len=max_len, n_pages=32)
        gw = serve.Gateway(reg, seed=seed)
        try:
            # warm every program the trace will touch on EVERY replica
            # (prefill chunk buckets 16/32/64 + decode) through each
            # replica's own scheduler — router spread during warmup is
            # not guaranteed, and a cold replica would compile inside
            # the timed window
            wrng = onp.random.RandomState(seed + 1)
            for rep in gw._models["m"].replicas:
                warm = [rep.sched.submit(
                    wrng.randint(0, vocab, (n,)).astype(onp.int32), 2,
                    temperature=1.0) for n in (12, 24, 40)]
                for _ in range(2000):
                    rep.sched.step()
                    if all(w.done for w in warm):
                        break
                if not all(w.done for w in warm):
                    raise RuntimeError("replica warmup did not complete")
            programs_warm = gw.xla_program_counts()

            t0 = time.perf_counter()
            reqs = [gw.submit("m", p, b)
                    for p, b in zip(prompts, budgets)]
            while not all(r.done for r in reqs):
                gw.step()
                if time.perf_counter() - t0 > 600:
                    raise RuntimeError("sharded serve replay timed out")
            t_total = time.perf_counter() - t0

            if gw.xla_program_counts() != programs_warm:
                raise RuntimeError(
                    "steady-state recompile during sharded replay: "
                    f"{programs_warm} -> {gw.xla_program_counts()}")
            total_new = sum(len(r.result()) for r in reqs)  # raises on err
            ttfts = [r.ttft for r in reqs]
            if total_new == 0 or any(t is None for t in ttfts) \
                    or t_total <= 0:
                raise RuntimeError(
                    f"degenerate sharded serve run: tokens={total_new}")
            tokens_s = total_new / t_total
            if not (tokens_s > 0 and tokens_s == tokens_s
                    and tokens_s != float("inf")):
                raise RuntimeError(f"degenerate serve rate {tokens_s!r}")
            out = {
                "tokens_s": tokens_s,
                "p50_ms": float(onp.percentile(ttfts, 50)) * 1e3,
                "p99_ms": float(onp.percentile(ttfts, 99)) * 1e3,
                "replicas_used": len({r.replica for r in reqs}),
            }
            if replicas > 1 and out["replicas_used"] < replicas:
                raise RuntimeError(
                    f"router starved a replica: {out['replicas_used']}"
                    f"/{replicas} saw traffic")
            if mesh is not None:
                eng = gw._models["m"].replicas[0].slots
                report = eng.shardcheck_report()
                for fam in ("prefill", "decode"):
                    if report[fam].findings:
                        raise RuntimeError(
                            f"dirty shardcheck on sharded {fam}: "
                            f"{[(f.rule, f.message) for f in report[fam].findings]}")
                # static HLO truth: bytes every decode step moves through
                # collectives, / max_slots = per-token at full occupancy
                step_bytes = sum(
                    rec["bytes"]
                    for rec in report["decode"].collectives.values())
                out["collective_bytes_per_token"] = step_bytes / max_slots
                # HBM-capacity story: each device holds 1/tp of the pools
                pools = jax.tree.leaves(eng._pools)
                out["kv_bytes_total"] = sum(x.nbytes for x in pools)
                out["kv_bytes_per_device"] = sum(
                    x.addressable_shards[0].data.nbytes for x in pools)
            return out
        finally:
            gw.shutdown(drain=False)

    base = run(mesh=None, replicas=1)
    shard = run(mesh=f"tp={tp}", replicas=n_replicas)
    shard["1dev_tokens_s"] = base["tokens_s"]
    shard["vs_1dev"] = shard["tokens_s"] / base["tokens_s"]
    return shard


def bench_gpt_serve_traced(requests=12, max_slots=4, prompt_max=48,
                           new_max=48, mean_interarrival_s=0.02, seed=0):
    """Tracing-overhead pair: the SAME reduced serve trace twice,
    span tracing off then on (adjacent runs — the interleaved-pair
    methodology of `bench_dot_pair`). Reports (tokens/s traced, tokens/s untraced,
    overhead %). The loud-failure contract rides on `bench_gpt_serve`
    itself: any failed request / degenerate rate raises out of here and
    lands in extras["errors"]."""
    from incubator_mxnet_tpu.telemetry import tracing

    kw = dict(requests=requests, max_slots=max_slots,
              prompt_max=prompt_max, new_max=new_max,
              mean_interarrival_s=mean_interarrival_s, seed=seed)
    assert not tracing.is_enabled(), \
        "tracing already armed: the off-leg would measure the on-path"
    off_tok_s = bench_gpt_serve(**kw)[0]
    tracing.enable()
    try:
        on_tok_s = bench_gpt_serve(**kw)[0]
        n_spans = len(tracing.finished_spans())
    finally:
        tracing.disable()
        tracing.reset()
    if n_spans == 0:
        raise RuntimeError(
            "traced serve run recorded zero spans — the tracer was not "
            "armed through the request path")
    overhead_pct = (off_tok_s - on_tok_s) / off_tok_s * 100.0
    return on_tok_s, off_tok_s, overhead_pct


def bench_gpt_serve_timeseries(requests=12, max_slots=4, prompt_max=48,
                               new_max=48, mean_interarrival_s=0.02,
                               seed=0):
    """Capacity-observatory cost on the serving hot path (TELEMETRY.md
    §capacity observatory): the SAME reduced serve trace twice —
    history sampler + cost ledger disarmed, then armed with an
    aggressive 10 ms sampling interval (100× the default rate, so the
    measured figure bounds the production cost from above). Adjacent
    runs, `bench_gpt_serve_traced` methodology. The armed leg must
    actually observe the run: nonzero history samples AND nonzero
    per-tenant device-seconds, else the observatory wasn't wired
    through the step loop. Returns (tokens/s armed, tokens/s disarmed,
    overhead %)."""
    from incubator_mxnet_tpu.telemetry import capacity, timeseries

    kw = dict(requests=requests, max_slots=max_slots,
              prompt_max=prompt_max, new_max=new_max,
              mean_interarrival_s=mean_interarrival_s, seed=seed)
    assert not timeseries.is_enabled() and not capacity.is_enabled(), \
        "observatory already armed: the off-leg would measure the on-path"
    off_tok_s = bench_gpt_serve(**kw)[0]
    timeseries.enable(interval_s=0.01, samples=4096)
    capacity.enable()
    try:
        on_tok_s = bench_gpt_serve(**kw)[0]
        n_samples = timeseries.sample_count()
        ledger = capacity.ledger_report()
    finally:
        timeseries.disable()
        timeseries.reset()
        capacity.disable()
        capacity.reset()
    if n_samples == 0:
        raise RuntimeError(
            "armed serve run recorded zero history samples — the "
            "sampler thread never ticked")
    if ledger["device_seconds_sum"] <= 0:
        raise RuntimeError(
            "armed serve run attributed zero device-seconds — the cost "
            "ledger is not wired through the scheduler step loop")
    overhead_pct = (off_tok_s - on_tok_s) / off_tok_s * 100.0
    return on_tok_s, off_tok_s, overhead_pct


def bench_gpt_serve_anatomy(requests=12, max_slots=4, prompt_max=48,
                            new_max=48, mean_interarrival_s=0.02,
                            seed=0):
    """Request-anatomy ledger cost on the serving hot path
    (TELEMETRY.md §request anatomy): the SAME reduced serve trace
    twice — anatomy disarmed, then armed at sample rate 1.0 (every
    request archived, 20× the default rate, so the figure bounds the
    production cost from above). Adjacent runs, the
    `bench_gpt_serve_traced` methodology. The armed leg must actually
    observe the run: nonzero completed requests in the ledger AND a
    non-empty archive, else the anatomy seams are not wired through
    the gateway/scheduler. Returns (tokens/s armed, tokens/s
    disarmed, overhead %)."""
    from incubator_mxnet_tpu.telemetry import anatomy

    kw = dict(requests=requests, max_slots=max_slots,
              prompt_max=prompt_max, new_max=new_max,
              mean_interarrival_s=mean_interarrival_s, seed=seed)
    assert not anatomy.is_enabled(), \
        "anatomy already armed: the off-leg would measure the on-path"
    off_tok_s = bench_gpt_serve(**kw)[0]
    sample0 = anatomy.sample_rate()
    anatomy.enable()
    anatomy.reset()
    anatomy.set_sample(1.0)
    try:
        on_tok_s = bench_gpt_serve(**kw)[0]
        rep = anatomy.report()
    finally:
        anatomy.set_sample(sample0)
        anatomy.disable()
        anatomy.reset()
    if rep["requests_completed"] == 0:
        raise RuntimeError(
            "armed serve run completed zero anatomy records — the "
            "begin/complete seams are not wired through the gateway")
    if not rep["archive"]:
        raise RuntimeError(
            "armed serve run archived nothing at sample rate 1.0 — "
            "the tail-sampling ring is not wired")
    overhead_pct = (off_tok_s - on_tok_s) / off_tok_s * 100.0
    return on_tok_s, off_tok_s, overhead_pct


def bench_gpt_serve_lockwitness(requests=12, max_slots=4, prompt_max=48,
                                new_max=48, mean_interarrival_s=0.02,
                                seed=0):
    """Lock-order-witness cost on the serving hot path (ANALYSIS.md
    §racecheck): the SAME reduced serve trace twice, witness disarmed
    then armed, adjacent runs (the `bench_gpt_serve_traced`
    methodology). Arming must happen BEFORE the on-leg constructs its
    engine — `tracked_lock` decides raw-vs-instrumented at the factory,
    so the off-leg's engine lock is the raw primitive (zero overhead by
    construction) and the on-leg's is tracked. The armed leg must also
    finish with zero witnessed RC005 inversions — this doubles as the
    under-load clean gate. Returns (tokens/s armed, tokens/s disarmed,
    overhead %)."""
    from incubator_mxnet_tpu.telemetry import locks

    kw = dict(requests=requests, max_slots=max_slots,
              prompt_max=prompt_max, new_max=new_max,
              mean_interarrival_s=mean_interarrival_s, seed=seed)
    assert not locks.is_enabled(), \
        "witness already armed: the off-leg would measure the on-path"
    off_tok_s = bench_gpt_serve(**kw)[0]
    locks.enable()
    locks.reset()
    try:
        on_tok_s = bench_gpt_serve(**kw)[0]
        inversions = locks.inversions()
        tracked = [n for n in locks.known_locks()
                   if n.startswith("serve.")]
    finally:
        locks.reset()
        locks.disable()
    if not tracked:
        raise RuntimeError(
            "armed serve run tracked no serve.* locks — the engine "
            "lock did not go through tracked_lock")
    if inversions:
        raise RuntimeError(
            f"armed serve run witnessed lock-order inversions: "
            f"{[i['pair'] for i in inversions]}")
    overhead_pct = (off_tok_s - on_tok_s) / off_tok_s * 100.0
    return on_tok_s, off_tok_s, overhead_pct


def bench_collective_overhead(n=256, iters=40, warmup=5, rounds=2):
    """Fleet-telemetry cost on a jitted collective step: the SAME
    shard_map program (wrapper all_reduce + ring_permute over the local
    mesh) with fleet off vs armed, in INTERLEAVED (off,on) rounds with
    min-of-rounds per leg — the `bench_resnet50_infer_pair` rationale:
    each leg freshly traces+compiles, and on a shared CPU runner the
    off-leg's own round-to-round wall variance exceeds 3%, so adjacent
    rounds + min reject load spikes that adjacent single legs cannot.
    Each leg re-jits so the armed leg's program embeds anything the
    census might have inserted at trace time — it must price as a dead
    branch at execution (TELEMETRY.md's <3% wrapper contract, gated
    structurally in tests/test_fleet.py; this is the measured
    end-to-end figure). Returns (off_ms, on_ms, overhead_pct)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from incubator_mxnet_tpu.parallel import collectives
    from incubator_mxnet_tpu.telemetry import fleet, registry

    devs = jax.devices()
    mesh = Mesh(onp.asarray(devs), ("dp",))
    rng = onp.random.RandomState(0)
    x = jnp.asarray(
        rng.uniform(-1, 1, (len(devs) * n, n)).astype("float32"))

    def leg(armed):
        if armed:
            fleet.enable()
        try:
            # fresh jit per leg: no program reuse across legs
            @jax.jit
            @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                               out_specs=P("dp"), check_vma=False)
            def step(a):
                g = collectives.all_reduce(a.sum(axis=0), "dp")
                h = collectives.ring_permute(a, "dp")
                return a + 0.1 * h + g / collectives.axis_size("dp")

            y = step(x)
            for _ in range(warmup):
                y = step(y)
            y.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                y = step(y)
            y.block_until_ready()
            return (time.perf_counter() - t0) * 1e3 / iters
        finally:
            if armed:
                fleet.disable()

    assert not fleet.is_enabled(), \
        "fleet already armed: the off-legs would measure the on-path"
    offs, ons = [], []
    try:
        for _ in range(rounds):
            offs.append(leg(False))
            ons.append(leg(True))
        counted = any(k.startswith("mx_collective_trace_calls_total")
                      for k in registry.report())
    finally:
        fleet.disable()
        fleet.reset()
    if not counted:
        raise RuntimeError(
            "armed legs recorded no collective census counts — the "
            "fleet hook was not live through the wrappers")
    off_ms, on_ms = min(offs), min(ons)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0
    return off_ms, on_ms, overhead_pct


def bench_resnet50_infer_pair(batch=64, iters=10, rounds=3):
    """fp32 AND int8 inference measured in INTERLEAVED rounds
    (fp32,int8,fp32,int8,...) with best-of-rounds throughput and the
    median per-round ratio: adjacent rounds share the host's load, and
    median-of-ratios rejects a single bad round."""
    from incubator_mxnet_tpu import np
    from incubator_mxnet_tpu.contrib.quantization import quantize_net
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    rng = onp.random.RandomState(0)
    x = np.array(rng.uniform(-1, 1, (batch, 3, 224, 224)).astype("float32"))

    net32 = resnet50_v1()
    net32.initialize()
    net32(x[:1])
    net32.hybridize()
    net8 = resnet50_v1()
    net8.initialize()
    net8(x[:1])
    quantize_net(net8, calib_data=[x[:8]], calib_mode="naive")
    net8.hybridize()

    def timed(net):
        y = net(x)
        y.wait_to_read()           # compiled + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            y = net(x)
        y.wait_to_read()
        return batch * iters / (time.perf_counter() - t0)

    timed(net32)
    timed(net8)                     # both warm before any timed round
    f_rates, i_rates, ratios = [], [], []
    for _ in range(rounds):
        f = timed(net32)
        i = timed(net8)
        f_rates.append(f)
        i_rates.append(i)
        ratios.append(i / f)
    ratios.sort()

    # DEVICE time from the profiler's XPlane trace: what the chip spent,
    # without the host dispatch the wall rates above include
    def device_ms(net, n=8):
        from incubator_mxnet_tpu import profiler

        prev = profiler._CONFIG.get("profile_imperative", True)  # noqa: SLF001
        profiler.set_config(profile_imperative=False)
        profiler.start()
        try:
            y = None
            for _ in range(n):
                y = net(x)
            y.wait_to_read()
        finally:
            profiler.stop()
            profiler.set_config(profile_imperative=prev)
        # /device: lanes ONLY (host launch events carry 'jit_' names too
        # and would re-import the host time this statistic must exclude)
        totals = profiler.device_op_totals()
        profiler.dumps(reset=True)
        tot_us = sum(t for name, (_c, t) in totals.items()
                     if str(name).startswith("jit_"))
        return tot_us / 1e3 / n if tot_us else None

    dev32 = device_ms(net32)
    dev8 = device_ms(net8)
    dev_ratio = (dev32 / dev8) if dev32 and dev8 else None
    return (max(f_rates), max(i_rates), ratios[len(ratios) // 2],
            dev32, dev8, dev_ratio)


def _collect_serve_extras(extras, _fail):
    """The mx.serve benchmark family (shared by the full round and
    ``--serve-only``): continuous batching, speculative decoding,
    pool-size decode-cost flatness, tracing overhead, prefix reuse,
    chunked long prompts, and the multi-tenant gateway trace."""
    try:
        s_tok, s_p50, s_p99, s_occ = bench_gpt_serve()
        # the serving story next to the batch-decode ceiling: aggregate
        # tokens/s + TTFT under a seeded Poisson trace (32 reqs, 8 slots)
        extras["gpt_serve_tokens_s"] = round(s_tok, 1)
        extras["gpt_serve_ttft_p50_ms"] = round(s_p50, 1)
        extras["gpt_serve_ttft_p99_ms"] = round(s_p99, 1)
        extras["gpt_serve_mean_slot_occupancy"] = round(s_occ, 3)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve", e)
    try:
        sp = bench_gpt_serve(
            spec_k=4, draft="ngram", _return_engine_stats=True)
        # speculative decoding on the SAME trace: the n-gram draft costs
        # no model compute, so every accepted draft token rides the one
        # batched verify program instead of its own decode step
        extras["gpt_serve_spec_tokens_s"] = round(sp[0], 1)
        extras["gpt_serve_spec_accept_rate"] = \
            round(sp[4]["accept_rate"], 3)
        if "gpt_serve_tokens_s" in extras:
            extras["gpt_serve_spec_vs_base"] = \
                round(sp[0] / extras["gpt_serve_tokens_s"], 3)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_spec", e)
    try:
        df = bench_serve_decode_flat()
        # per-layer pool layout evidence: decode step wall time must not
        # move as the pool quadruples (the donated per-layer leaves
        # alias in place — cost is O(active tokens), not O(n_pages))
        extras["gpt_serve_decode_step_1x_ms"] = round(df["1x"], 3)
        extras["gpt_serve_decode_step_4x_pages_ms"] = round(df["4x"], 3)
        extras["gpt_serve_decode_step_vs_4x_pages"] = \
            round(df["ratio"], 3)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_decode_flat", e)
    try:
        on_tok, off_tok, ovh = bench_gpt_serve_traced()
        # span-tracing cost on the serving hot path (TELEMETRY.md):
        # same reduced trace, adjacent off/on runs
        extras["gpt_serve_traced_tokens_s"] = round(on_tok, 1)
        extras["gpt_serve_untraced_tokens_s"] = round(off_tok, 1)
        extras["gpt_serve_tracing_overhead_pct"] = round(ovh, 2)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_traced", e)
    try:
        ts_on, ts_off, ts_ovh = bench_gpt_serve_timeseries()
        # capacity-observatory cost (TELEMETRY.md §capacity
        # observatory): same reduced trace, history sampler + cost
        # ledger disarmed then armed at a 100×-production sampling rate
        extras["gpt_serve_timeseries_tokens_s"] = round(ts_on, 1)
        extras["gpt_serve_unsampled_tokens_s"] = round(ts_off, 1)
        extras["gpt_serve_timeseries_overhead_pct"] = round(ts_ovh, 2)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_timeseries", e)
    try:
        an_on, an_off, an_ovh = bench_gpt_serve_anatomy()
        # request-anatomy ledger cost (TELEMETRY.md §request anatomy):
        # same reduced trace, anatomy disarmed then armed at sample
        # rate 1.0 — the acceptance gate wants this under 3%
        extras["gpt_serve_anatomy_tokens_s"] = round(an_on, 1)
        extras["gpt_serve_unanatomized_tokens_s"] = round(an_off, 1)
        extras["gpt_serve_anatomy_overhead_pct"] = round(an_ovh, 2)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_anatomy", e)
    try:
        won, woff, wovh = bench_gpt_serve_lockwitness()
        # lock-order-witness cost on the serving hot path (ANALYSIS.md
        # §racecheck): same reduced trace, witness disarmed then armed;
        # the armed leg also gates zero RC005 inversions under load
        extras["gpt_serve_lockwitness_tokens_s"] = round(won, 1)
        extras["gpt_serve_unwitnessed_tokens_s"] = round(woff, 1)
        extras["gpt_serve_lockwitness_overhead_pct"] = round(wovh, 2)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_lockwitness", e)
    try:
        coff, con, covh = bench_collective_overhead()
        # fleet collective-wrapper cost (TELEMETRY.md §fleet): same
        # jitted shard_map step, fleet census off then armed
        extras["collective_step_off_ms"] = round(coff, 3)
        extras["collective_step_fleet_ms"] = round(con, 3)
        extras["collective_wrapper_overhead_pct"] = round(covh, 2)
    except Exception as e:  # pragma: no cover
        _fail("collective_overhead", e)
    try:
        pr = bench_gpt_serve_prefix()
        extras["gpt_serve_prefix_tokens_s"] = round(pr["reuse_tokens_s"], 1)
        extras["gpt_serve_prefix_base_tokens_s"] = \
            round(pr["base_tokens_s"], 1)
        extras["gpt_serve_prefix_speedup"] = round(pr["speedup"], 3)
        extras["gpt_serve_prefix_hit_rate"] = round(pr["hit_rate"], 3)
        extras["gpt_serve_kv_bytes_per_slot"] = \
            int(pr["kv_bytes_per_slot"])
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_prefix", e)
    try:
        lp = bench_gpt_serve_longprompt()
        extras["gpt_serve_longprompt_ttft_p99_ms"] = \
            round(lp["chunked_p99_ms"], 1)
        extras["gpt_serve_longprompt_unchunked_ttft_p99_ms"] = \
            round(lp["unchunked_p99_ms"], 1)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_longprompt", e)
    try:
        gwr = bench_gpt_gateway()
        # the multi-tenant story: per-tier TTFT under a bursty recorded
        # trace, preemption count, per-tenant token rates (SERVING.md)
        for tier, t in gwr["tiers"].items():
            extras[f"gpt_gateway_{tier}_ttft_p50_ms"] = \
                round(t["p50_ms"], 1)
            extras[f"gpt_gateway_{tier}_ttft_p99_ms"] = \
                round(t["p99_ms"], 1)
        extras["gpt_gateway_preemptions"] = int(gwr["preemptions"])
        for tenant, rate in gwr["tenants"].items():
            extras[f"gpt_gateway_{tenant}_tokens_s"] = round(rate, 1)
    except Exception as e:  # pragma: no cover
        _fail("gpt_gateway", e)
    try:
        el = bench_gpt_serve_elastic()
        # the elastic control plane on the diurnal day: capacity handed
        # back vs a static peak fleet, with the live leg's latency SLO
        # and the zero-post-publication-compile gate (SERVING.md
        # §elastic replicas)
        extras["gpt_serve_elastic_chips_hours_ratio"] = \
            round(el["chips_hours_ratio"], 3)
        extras["gpt_serve_elastic_scale_events"] = int(el["scale_events"])
        extras["gpt_serve_elastic_ttft_compliance"] = \
            round(el["ttft_compliance"], 3)
        extras["gpt_serve_elastic_tokens_s"] = \
            round(el["live_tokens_s"], 1)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_elastic", e)
    try:
        dg = bench_gpt_serve_disagg()
        # disaggregated prefill/decode pod on the mixed-length trace:
        # decode residency vs the homogeneous chunked-prefill baseline
        # at equal hardware, the chat tier's victim TTFT, and the
        # exact migration byte audit (SERVING.md §disaggregated)
        extras["gpt_serve_disagg_resident_ratio"] = \
            round(dg["decode_resident_ratio"], 2)
        extras["gpt_serve_disagg_chat_ttft_p99_ms"] = \
            round(dg["chat_ttft_p99_ms"], 1)
        extras["gpt_serve_disagg_baseline_ttft_p99_ms"] = \
            round(dg["baseline_chat_ttft_p99_ms"], 1)
        extras["gpt_serve_disagg_pages_migrated"] = \
            int(dg["pages_migrated"])
        extras["gpt_serve_disagg_tokens_s"] = round(dg["tokens_s"], 1)
    except Exception as e:  # pragma: no cover
        _fail("gpt_serve_disagg", e)
    # pod-scale replicated+sharded serving: 2 replicas x tp=4 need eight
    # devices in THIS process (a parent that holds the chip starts no
    # child that needs it). With fewer it is skipped, and says so.
    import jax

    if len(jax.devices()) < 8:
        extras["gpt_serve_sharded_skipped"] = (
            f"needs 8 devices, jax reports {len(jax.devices())}; "
            "`bench.py --serve-sharded-only --virtual-cpu` rehearses the "
            "layout on virtual CPU devices")
    else:
        try:
            extras.update(_serve_sharded_extras())
        except Exception as e:  # pragma: no cover
            _fail("gpt_serve_sharded", e)


def _fail_into(extras):
    def _fail(name, e):
        # loud failure contract (VERDICT r4 weak #1): every dead
        # sub-bench lands in extras["errors"] in the emitted JSON —
        # and `_emit` then exits non-zero: a missing metric never passes.
        print(f"{name} bench failed: {e}", file=sys.stderr)
        extras.setdefault("errors", {})[name] = \
            f"{type(e).__name__}: {e}"[:300]
    return _fail


def serve_main():
    """``--serve-only``: run just the mx.serve family and emit
    gpt_serve_tokens_s as the headline metric — the serving-round
    counterpart of the full-round resnet50 headline."""
    _require_tpu()
    extras = {}
    _collect_serve_extras(extras, _fail_into(extras))
    headline = extras.get("gpt_serve_tokens_s")
    if headline is None:  # pragma: no cover - loud-failure contract
        _emit("bench_failed", 0, "none", extras)
        raise SystemExit(1)
    _emit("gpt_serve_tokens_s", headline, "tokens/sec", extras)


def _serve_sharded_extras():
    sh = bench_gpt_serve_sharded()
    return {
        "gpt_serve_sharded_tokens_s": round(sh["tokens_s"], 1),
        "gpt_serve_sharded_1dev_tokens_s": round(sh["1dev_tokens_s"], 1),
        "gpt_serve_sharded_vs_1dev": round(sh["vs_1dev"], 3),
        "gpt_serve_sharded_ttft_p50_ms": round(sh["p50_ms"], 1),
        "gpt_serve_sharded_ttft_p99_ms": round(sh["p99_ms"], 1),
        "gpt_serve_sharded_replicas": int(sh["replicas_used"]),
        "gpt_serve_sharded_collective_bytes_per_token":
            int(sh["collective_bytes_per_token"]),
        "gpt_serve_sharded_kv_bytes_per_device":
            int(sh["kv_bytes_per_device"]),
        "gpt_serve_sharded_kv_bytes_total": int(sh["kv_bytes_total"]),
    }


def serve_sharded_main(virtual_cpu):
    """``--serve-sharded-only``: the pod-scale sharded serving bench alone,
    in-process on the eight devices jax reports. With ``--virtual-cpu``
    (asked for by name; `__main__` pinned the platform before jax was
    imported) those are virtual CPU devices: the JSON line says so, and
    its wall rates are a layout rehearsal, not a measurement."""
    extras = {"virtual_cpu": bool(virtual_cpu)}
    try:
        extras.update(_serve_sharded_extras())
    except Exception as e:  # pragma: no cover
        _fail_into(extras)("gpt_serve_sharded", e)
    headline = extras.get("gpt_serve_sharded_tokens_s")
    if headline is None:  # pragma: no cover - loud-failure contract
        _emit("bench_failed", 0, "none", extras)
        raise SystemExit(1)
    _emit("gpt_serve_sharded_tokens_s", headline, "tokens/sec", extras)


def main():
    extras = {}
    _fail = _fail_into(extras)

    # FIRST, while this process has not touched jax: the one child that
    # needs the chip (asserted inside)
    try:
        rate, cores = _bench_input_pipeline_subprocess()
        extras["input_pipeline_img_s_per_core"] = round(rate, 1)
        if cores is not None:
            extras["input_pipeline_host_cores"] = int(cores)
    except Exception as e:  # pragma: no cover
        _fail("input_pipeline", e)
    _require_tpu()

    try:
        fw, raw, med_ratio = bench_dot_pair()
        extras["dot_framework_ms"] = round(fw, 4)
        extras["dot_rawjax_ms"] = round(raw, 4)
        # eager-dispatch statistic (median of per-round ratios over
        # interleaved rounds); the r5 target is ≤1.05
        extras["dot_framework_vs_rawjax"] = round(med_ratio, 3)
    except Exception as e:  # pragma: no cover
        _fail("dot_pair", e)
    try:
        extras["dispatch_floor_ms"] = round(bench_dispatch_floor(), 4)
    except Exception as e:  # pragma: no cover
        _fail("dispatch_floor", e)
    try:
        tokens_s, mfu = bench_bert_train()
        extras["bert_base_train_tokens_s"] = round(tokens_s, 1)
        extras["bert_mfu"] = round(mfu, 4)
    except Exception as e:  # pragma: no cover
        _fail("bert_seq128", e)
    try:
        # flash attention's regime: the T² term is 8.6% of total FLOPs
        tokens_s512, mfu512 = bench_bert_train(batch=32, seq=512, iters=10,
                                     trace_check=True)
        extras["bert_seq512_train_tokens_s"] = round(tokens_s512, 1)
        extras["bert_mfu_seq512"] = round(mfu512, 4)
        tc = _TRACE_CHECK.get(512)
        if tc and tc.get("trace_mfu") is not None:
            extras["bert_trace_mfu_seq512"] = round(tc["trace_mfu"], 4)
            drift = abs(tc["trace_mfu"] - mfu512) / max(mfu512, 1e-12)
            extras["bench_mfu_formula_drift"] = round(drift, 4)
            if drift > 0.10:
                print(f"WARNING: bert seq512 MFU formula "
                      f"({mfu512:.4f}) disagrees with the trace-"
                      f"measured MFU ({tc['trace_mfu']:.4f}) by "
                      f"{drift * 100:.1f}% — the hand-derived FLOPs "
                      "formula has drifted from what the chip executes",
                      file=sys.stderr)
        if tc and tc.get("top_kernel_gbs") is not None:
            # achieved GB/s of the top bandwidth-bound kernel — the
            # number the seq512 fusion work should push toward the roof
            extras["bert_seq512_top_kernel_gbs"] = \
                round(tc["top_kernel_gbs"], 1)
    except Exception as e:  # pragma: no cover
        _fail("bert_seq512", e)
    try:
        extras["train_goodput_frac"] = round(
            bench_train_goodput(), 4)
    except Exception as e:  # pragma: no cover
        _fail("train_goodput", e)
    try:
        extras["flash_T32k_fwd_tokens_s"] = round(
            bench_flash_long_context(), 1)
    except Exception as e:  # pragma: no cover
        _fail("flash_long_context", e)
    try:
        (dec_tokens_s, nocache_tokens_s, vs_nocache,
         eager_est_ratio) = bench_gpt_decode()
        extras["gpt_decode_tokens_s"] = round(dec_tokens_s, 1)
        # the honest denominator: MEASURED compiled no-KV-cache re-forward
        # decode (fixed-shape program — see bench_gpt_decode docstring)
        extras["gpt_decode_nocache_compiled_tokens_s"] = \
            round(nocache_tokens_s, 1)
        extras["gpt_decode_vs_nocache_compiled"] = round(vs_nocache, 2)
        # demoted to a note (VERDICT Do-this #6): estimated, compute-only,
        # ignores the real eager loop's per-length recompiles
        extras["gpt_decode_vs_eager_loop_note"] = (
            f"~{eager_est_ratio:.0f}x vs an ESTIMATED per-token eager "
            "re-forward loop (compute-only; ignores ~new_tokens XLA "
            "recompiles, once measured directly at 1152x) — superseded "
            "by gpt_decode_vs_nocache_compiled")
    except Exception as e:  # pragma: no cover
        _fail("gpt_decode", e)

    _collect_serve_extras(extras, _fail)

    try:
        (fp32_rate, int8_rate, ratio, dev32, dev8,
         dev_ratio) = bench_resnet50_infer_pair()
        extras["resnet50_fp32_infer_img_s"] = round(fp32_rate, 1)
        extras["resnet50_int8_infer_img_s"] = round(int8_rate, 1)
        extras["resnet50_int8_vs_fp32_wall"] = round(ratio, 3)
        if dev32:
            extras["resnet50_fp32_device_ms"] = round(dev32, 3)
        if dev8:
            extras["resnet50_int8_device_ms"] = round(dev8, 3)
        if dev_ratio:
            # device-time ratio: the speedup without host dispatch
            extras["resnet50_int8_vs_fp32_device"] = round(dev_ratio, 3)
    except Exception as e:  # pragma: no cover
        _fail("resnet50_infer_pair", e)

    try:
        img_s = bench_resnet50_train()
    except Exception as e:  # pragma: no cover
        _fail("resnet50_train", e)
        _emit("bench_failed", 0, "none", extras, vs_baseline=0)
        raise
    _emit("resnet50_train_img_s_per_chip", round(img_s, 1), "images/sec",
          extras,
          vs_baseline=round(img_s / BASELINE_V100_RESNET50_IMG_S, 3))


if __name__ == "__main__":
    if "--pipeline-only" in sys.argv:
        print(bench_input_pipeline())
        # ship the child registry's pipeline series to the parent (the
        # metric's owner of record — see bench_input_pipeline docstring)
        from incubator_mxnet_tpu.telemetry import registry as _telem

        _series = {
            k.split("{")[0]: v.get("value")
            for k, v in _telem.report().items()
            if k.startswith("mx_input_pipeline_")}
        print("REGISTRY " + json.dumps(_series))
    elif "--serve-only" in sys.argv:
        serve_main()
    elif "--serve-sharded-only" in sys.argv:
        _virtual = "--virtual-cpu" in sys.argv
        if _virtual:
            # decided here, before anything imports jax: eight virtual
            # CPU devices, because the caller asked for them by name
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
        serve_sharded_main(_virtual)
    else:
        main()
