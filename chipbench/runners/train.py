"""Masked-language-model training through `parallel.sharded.DataParallel.step`
as a user's loop drives it: a host thread makes the next seeded batch one step
ahead, the loss is read back every `read_every` steps, nothing else
synchronises.

Set-up builds ONE trainer, drives it from the seed through its first
`check_steps` steps (through the same loop and feed as the window, on rows
that all differ), takes the readings the check needs, warms up until chunks
of steps take the same time, and hands that same trainer to the window. The
window starts at a step launch on a drained device and ends when the last
whole step launched before `seconds` were up has finished; the rate divides
by the time that really passed.
"""
from __future__ import annotations

import contextlib
import queue
import statistics
import threading
import time

import numpy as onp

from chipbench.lib import harness, seeded


def batch_of(seed, step, rows, seq, vocab):
    """Step `step`'s token ids and labels: every row of every step differs."""
    rng = onp.random.default_rng([int(seed), 0xB, int(step)])
    return (rng.integers(0, vocab, (rows, seq), dtype=onp.int32),
            rng.integers(0, vocab, (rows, seq), dtype=onp.int32))


class Feed(threading.Thread):
    """Makes batches one step ahead of the loop, on a thread of its own."""

    def __init__(self, seed, rows, seq, vocab):
        super().__init__(daemon=True, name="cb-feed")
        self.args = (rows, seq, vocab)
        self.seed = seed
        self.q = queue.Queue(maxsize=1)
        self.stop_event = threading.Event()

    def run(self):
        from incubator_mxnet_tpu import np

        step = 0
        while not self.stop_event.is_set():
            step += 1
            x, y = batch_of(self.seed, step, *self.args)
            item = (np.array(x), np.array(y))
            while not self.stop_event.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def stop(self):
        self.stop_event.set()
        self.join(timeout=10.0)


def build_trainer(spec, seed, devices):
    from incubator_mxnet_tpu import gluon, optimizer
    from incubator_mxnet_tpu.models import bert
    from incubator_mxnet_tpu.parallel.mesh import make_mesh
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    cfg = spec.config
    ref = harness.module_of("reference", cfg["family"], spec.root)
    n_layer, c, n_head, f, v, n_pos, _ = ref.sizes(cfg)
    net = bert.BERTModel(v, c, f, n_layer, n_head, n_pos,
                         dropout=cfg["dropout_prob"])
    seeded.fill(net, ref.leaves(cfg), seed)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = cfg["trainer"]["adam"]
    mesh = make_mesh({"dp": len(devices)}, devices=list(devices)) \
        if len(devices) > 1 else None
    trainer = DataParallel(
        net, lambda out, y: ce(out[0], y),
        optimizer.Adam(learning_rate=opt["learning_rate"], beta1=opt["beta1"],
                       beta2=opt["beta2"], epsilon=opt["epsilon"]), mesh=mesh)
    names = [n for n, p in net.collect_params().items()
             if p.grad_req != "null"]
    return net, trainer, names


def first_gradient(trainer, beta1):
    """The first gradient as the optimizer got it, on the host: after one
    step Adam's first moment is ``(1 - beta1) g``. The state holds a list
    ``[m, v]`` per leaf, or the two stacked on a leading axis (small leaves)."""
    import jax

    fn = jax.jit(lambda ms: [m / (1 - beta1) for m in ms])
    return [onp.asarray(g) for g in fn([s[0] for s in trainer.opt_states])]


def delta_norms(trainer, names, spec, seed):
    """Norm of each leaf's change since the seed's weights, which are made
    again here (the step donated the first ones)."""
    import jax
    import jax.numpy as jnp

    ref = harness.module_of("reference", spec.config["family"], spec.root)
    first = seeded.values(ref.leaves(spec.config), seed)
    now = [a._data for a in trainer.param_arrays]  # noqa: SLF001
    if trainer.mesh is not None:
        repl = jax.sharding.NamedSharding(trainer.mesh,
                                          jax.sharding.PartitionSpec())
        first = {k: jax.device_put(v, repl) for k, v in first.items()}
    fn = jax.jit(lambda a, b: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in zip(a, b)]))
    return onp.asarray(fn(now, [first[n] for n in names]))


class Loop:
    """The user's loop: next batch from the feed, one `step`, the loss read
    back every `read_every` steps."""

    def __init__(self, trainer, feed, spans, read_every):
        self.trainer, self.feed, self.spans = trainer, feed, spans
        self.read_every = read_every
        self.steps = 0
        self.losses = []          # (step, loss) as read back
        self.last = None

    def one(self):
        with self.spans.span("cb.train.input_wait"):
            x, y = self.feed.q.get()
        with self.spans.span("cb.train.step"):
            self.last = self.trainer.step(x, y)
        self.steps += 1
        if self.steps % self.read_every == 0:
            self.read()

    def drain(self):
        """Wait until the last step launched has finished."""
        self.last.asnumpy()

    def read(self):
        with self.spans.span("cb.train.read_loss"):
            self.losses.append((self.steps, float(self.last.asnumpy())))
        return self.losses[-1][1]


def first_steps(loop, trainer, names, spec, seed, mark=lambda what: None):
    """The steps the reference follows, through the window's own loop and
    feed: each step's loss, the first gradient (kept on the host) and its
    norm by leaf, and the norm of every leaf's change after the last step."""
    cfg, traffic = spec.config, spec.traffic
    prog = {"loss": []}
    for k in range(1, traffic["check_steps"] + 1):
        loop.one()
        prog["loss"].append(float(loop.last.asnumpy()))
        mark(f"step {k} done")
        if k == 1:
            prog["grad"] = first_gradient(trainer,
                                          cfg["trainer"]["adam"]["beta1"])
            prog["grad_norm"] = onp.asarray(
                [onp.linalg.norm(g.ravel()) for g in prog["grad"]])
    prog["delta_norm"] = delta_norms(trainer, names, spec, seed)
    return prog


@contextlib.contextmanager
def checked_trainer(spec, seed, devices, spans, mark=lambda what: None):
    """Set-up as far as the checked steps: one trainer under amp, its feed
    running, driven through its first steps. Yields ``(loop, prog, names,
    rows)``; on the way out the feed stops, amp is undone and the trainer's
    buffers are given back."""
    from incubator_mxnet_tpu import amp

    traffic = spec.traffic
    rows = traffic["rows_per_chip"] * len(devices)
    net, trainer, names = build_trainer(spec, seed, devices)
    mark("block filled from the seed, trainer built")
    feed = Feed(seed, rows, traffic["seq"], spec.config["vocab_size"])
    feed.start()
    amp.init(spec.config["trainer"]["amp"])
    try:
        loop = Loop(trainer, feed, spans, traffic["read_every"])
        prog = first_steps(loop, trainer, names, spec, seed, mark)
        mark("checked steps done and read")
        yield loop, prog, names, rows
    finally:
        amp.deinit()
        feed.stop()
        free(net, trainer)


def program_readings(spec, seed, devices, spans):
    """The checked steps and no window: what `control.py` reads over many
    seeds in one process."""
    with checked_trainer(spec, seed, devices, spans) as (_, prog, names, rows):
        return prog, names, rows


def timed_window(loop, seconds, on_open=lambda: None,
                 clock=time.perf_counter):
    """Whole steps over the time that really passed: drain the device, start
    the clock at a step launch, launch steps until `seconds` are up, wait
    for the last one, stop the clock. No step is cut, none goes uncounted,
    and the caller divides by ``t_close - t_open``, not by `seconds`."""
    loop.drain()
    on_open()
    first = loop.steps
    t_open = clock()
    while clock() - t_open < seconds:
        loop.one()
    loop.drain()
    return loop.steps - first, t_open, clock()


def run(env):
    spec, traffic = env.spec, env.spec.traffic
    seq = traffic["seq"]
    with checked_trainer(spec, env.seed, env.devices, env.spans,
                         env.mark) as (loop, prog, names, rows):
        # -- warm-up: until chunks of steps take the same time ---------------
        chunk_s = []
        while len(chunk_s) < traffic["warm_chunks_max"]:
            t0 = time.perf_counter()
            while True:
                loop.one()
                if loop.steps % loop.read_every == 0:
                    break
            chunk_s.append(time.perf_counter() - t0)
            last = chunk_s[-3:]
            if len(chunk_s) >= traffic["warm_chunks_min"] and \
                    max(last) - min(last) <= traffic["warm_tolerance"] * min(last):
                break
        # -- the window ------------------------------------------------------
        in_window = env.watch.snapshot()
        steps, t_open, t_close = timed_window(loop, env.seconds,
                                              env.open_window)
        compiled = env.watch.since(in_window)
        peak = harness.memory_peak(env.devices)
    read = [l for s, l in loop.losses if s > loop.steps - steps]
    window = {"wall_s": t_close - t_open, "t_open": t_open,
              "t_close": t_close, "steps": steps,
              "tokens": steps * rows * seq, "rows": rows, "seq": seq,
              "warm_chunks": len(chunk_s), "warm_chunk_s": chunk_s,
              "losses_read": read}
    del loop
    checks = check(spec, env.seed, prog, names, rows, seq, env.devices)
    for k, v in checks.pop("_worst").items():
        print(f"chipbench not compared, {k}: {v}", flush=True)
    return {"window": window, "attempted": steps,
            "failed": sum(1 for l in read if l != l or abs(l) == float("inf")),
            "memory_peak_bytes": peak, "checks": checks["list"],
            "counters": {}, "calls": {}, "compiled_in_window": compiled}


def free(net, trainer):
    import gc

    import jax

    for leaf in jax.tree.leaves(trainer.opt_states):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()
    for p in net.collect_params().values():
        nd = p.data()
        for arr in (nd._data, getattr(nd._grad, "_data", None)):  # noqa: SLF001
            if arr is not None and not arr.is_deleted():
                arr.delete()
    gc.collect()


def reference_readings(spec, seed, rows, seq, matmul="float32", part=None,
                       devices=None):
    cfg, traffic = spec.config, spec.traffic
    ref = harness.module_of("reference", cfg["family"], spec.root)
    batches = [batch_of(seed, k, rows, seq, cfg["vocab_size"])
               for k in range(1, traffic["check_steps"] + 1)]
    return ref.train_readings(cfg, seed, batches, cfg["trainer"]["adam"],
                              block_rows=traffic["check_block_rows"],
                              matmul=matmul, rows=part, devices=devices)


def compare(prog, ref, names):
    """The numbers of a training cell: each step's loss, and by the worst
    leaf the first gradient's norm and the norm of the change after the
    steps. A leaf's gap is between the two norms (not the norm of a
    difference), against the reference's norm of that leaf or of the median
    leaf, whichever is larger. A gap of norms is second order in zero-mean
    rounding error (bfloat16 and int8 read alike on it), so the first
    gradient is also compared as a vector: the norm of the difference, by the
    worst leaf and over all leaves together, which is first order and is
    what separates the precisions. Leaves whose reference gradient is under a
    thousandth of the median leaf's (the unused segment table and next-
    sentence head; a key's bias under softmax) move by round-off alone and
    are left out of the change."""
    out = {}
    for k, (a, b) in enumerate(zip(prog["loss"], ref["loss"]), start=1):
        out[f"loss_gap_step{k}"] = abs(a - b) / abs(b)
    g_ref = onp.asarray([ref["grad_norm"][n] for n in names])
    d_ref = onp.asarray([ref["delta_norm"][n] for n in names])
    g_med, d_med = statistics.median(g_ref), statistics.median(d_ref)
    g_gap = onp.abs(onp.asarray(prog["grad_norm"]) - g_ref) \
        / onp.maximum(g_ref, g_med)
    moved = g_ref >= 1e-3 * g_med
    d_gap = onp.abs(onp.asarray(prog["delta_norm"]) - d_ref) \
        / onp.maximum(d_ref, d_med)
    diff = onp.asarray([onp.linalg.norm((p - ref["grad"][n]).ravel())
                        for p, n in zip(prog["grad"], names)])
    out["grad_diff_all_leaves"] = float(
        onp.sqrt((diff ** 2).sum() / (g_ref ** 2).sum()))
    out["grad_diff_worst_leaf"] = float(
        (diff / onp.maximum(g_ref, g_med)).max())
    out["grad_norm_gap_worst_leaf"] = float(g_gap.max())
    out["delta_norm_gap_worst_leaf"] = float(d_gap[moved].max())
    out["_worst"] = {"grad": names[int(g_gap.argmax())],
                     "grad_diff": names[int(
                         (diff / onp.maximum(g_ref, g_med)).argmax())],
                     "delta": names[int(onp.where(moved, d_gap, -1).argmax())]}
    return out


def judged(got, limits):
    """`compare`'s numbers beside the limits of the cell's file: the ones it
    gives a limit are compared (``list``); the others (PERF.md says which
    have no upper reading, and why) are printed and decide nothing. The
    program's readings and a control's go through the same lines."""
    got = dict(got)
    worst = got.pop("_worst")
    worst.update({k: v for k, v in got.items() if k not in limits})
    return {"_worst": worst,
            "list": [{"name": k, "value": v, "limit": limits[k]}
                     for k, v in got.items() if k in limits]}


def check(spec, seed, prog, names, rows, seq, devices=None):
    ref = reference_readings(spec, seed, rows, seq, devices=devices)
    return judged(compare(prog, ref, names), spec.cell["limits"])
