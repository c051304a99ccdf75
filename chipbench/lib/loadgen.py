"""One general traffic generator, driven by a traffic file.

A traffic file fixes the *set* of request sizes and of arrival gaps (stratified
quantiles of the stated distributions, so every run has exactly the same
work); ``--seed`` only orders them and draws the token ids. Runs with
different seeds therefore differ in order, never in amount.

One kind, ``open_loop`` (independent users): arrivals on a schedule fixed
before the run, each request timed from the moment it was *due*, lateness of
the generator reported. The schedule is cut in cycles. A traffic file with
`ramp_sizes` starts with one short ramp cycle (that many requests over
`ramp_s` seconds) and holds the first full cycle until the runner says that
the window has opened, so that a window as long as a cycle (`sizes` /
`rate_rps` seconds) gets exactly the cycle's requests, whatever the seed.
"""
from __future__ import annotations

import math
import statistics
import threading
import time
from dataclasses import dataclass, field

import numpy as onp


def _quantiles(spec, n):
    """n stratified draws of a clipped log-normal, in increasing order."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["lo"]), spec["hi"])))
    return out


def size_set(traffic, n=None):
    """A cycle's ``(prompt tokens, output tokens, shared)`` triples, `n` of
    them (the file's `sizes` unless said): fixed by the traffic file alone."""
    n = n or traffic["sizes"]
    fixed = onp.random.default_rng(traffic.get("shape_seed", 0))
    prompts = _quantiles(traffic["prompt"], n)
    outputs = [_quantiles(traffic["output"], n)[j] for j in fixed.permutation(n)]
    pre = traffic.get("shared_prefix", {"tokens": 0, "share": 0.0})
    every = round(1 / pre["share"]) if pre["share"] else 0
    out = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        shared = bool(every) and i % every == 0
        if shared:   # a shared request says something after the system prompt
            p = max(p, pre["tokens"] + traffic.get("page_tokens", 16))
        out.append((p, o, shared))
    return out


def gap_set(traffic, n=None, span_s=None):
    """A cycle's inter-arrival gaps in seconds: `n` stratified quantiles of
    the exponential, scaled to add up to `span_s` exactly (the file's
    `sizes` over `sizes / rate_rps` seconds unless said)."""
    n = n or traffic["sizes"]
    span_s = span_s or n / traffic["rate_rps"]
    gaps = [-math.log(1 - (i + 0.5) / n) for i in range(n)]
    return [g * span_s / sum(gaps) for g in gaps]


def lead_s(traffic):
    """Every arrival comes this much before its place in the cycle (half the
    smallest gap): the last of a cycle then falls short of the cycle's end,
    and none sits on the edge of a window as long as the cycle."""
    return min(gap_set(traffic)) / 2


@dataclass
class Req:
    index: int
    prompt: onp.ndarray
    max_new: int
    shared: bool
    gap_s: float = 0.0
    ramp: bool = False            # of the ramp cycle, before the window
    # filled in as the run goes; all times are time.perf_counter() seconds
    due: float = None
    started: float = None         # the call to `submit` began
    submitted: float = None       # ... and returned
    handle: object = None
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    error: BaseException = None
    done: threading.Event = field(default_factory=threading.Event)


def requests(traffic, seed, vocab):
    """The endless, seeded sequence of requests: the ramp cycle where the
    file has one, then cycle after cycle over the fixed sets, each in an
    order of its own."""
    rng = onp.random.default_rng([int(seed), 0x5EED])
    pre = traffic.get("shared_prefix", {"tokens": 0})
    system = rng.integers(0, vocab, pre["tokens"]).astype(onp.int32)
    n_ramp = traffic.get("ramp_sizes", 0)
    cycles = [(size_set(traffic, n_ramp),
               gap_set(traffic, n_ramp, traffic["ramp_s"]), True)] \
        if n_ramp else []
    cycles.append((size_set(traffic), gap_set(traffic), False))
    index = 0
    while True:
        sizes, gaps, ramp = cycles[0]
        order = rng.permutation(len(sizes))
        gap_order = rng.permutation(len(sizes))
        for k, j in enumerate(order):
            p, o, shared = sizes[j]
            prompt = rng.integers(0, vocab, p).astype(onp.int32)
            if shared:
                prompt[:system.size] = system
            yield Req(index, prompt, o, shared, gaps[gap_order[k]], ramp)
            index += 1
        cycles = cycles[-1:]


class Client:
    """Submits requests to an engine and streams their tokens, one small
    thread a request (each sleeps in `submit` or `iter_tokens`; none
    computes). An open loop's users are independent: each request is
    submitted from its own thread, so that a `submit` the engine holds up
    delays that request alone and not the arrivals behind it."""

    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.sent = []
        self._threads = []

    def submit(self, req, due=None, wait=True):
        """Hand `req` to the engine and stream its tokens. `wait`: return
        once the engine has taken (or refused) it; else at once."""
        req.due = self.clock() if due is None else due
        self.sent.append(req)
        if wait:
            self._submit(req)
        t = threading.Thread(target=self._run, args=(req, not wait),
                             daemon=True, name=f"cb-client-{req.index}")
        self._threads.append(t)
        t.start()
        return req

    def _submit(self, req):
        req.started = self.clock()
        try:
            req.handle = self.engine.submit(req.prompt, req.max_new)
        except Exception as e:   # a refusal is a failed request, not a crash
            req.error = e
        req.submitted = self.clock()

    def _run(self, req, submit_first):
        try:
            if submit_first:
                self._submit(req)
            if req.error is None:
                for tok in self.engine.iter_tokens(req.handle, timeout=120.0):
                    req.token_times.append(self.clock())
                    req.tokens.append(int(tok))
        except Exception as e:
            req.error = e
        finally:
            req.done.set()

    def join(self, timeout):
        end = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.monotonic()))


class Generator(threading.Thread):
    """Feeds a `Client` on the schedule until told to stop. After a ramp
    cycle it holds the first full cycle until `open(t)` says when the window
    opened, and counts that cycle's places from `t`."""

    def __init__(self, client, traffic, stream, clock=time.perf_counter,
                 sleep=time.sleep):
        super().__init__(daemon=True, name="cb-generator")
        self.client, self.traffic, self.stream = client, traffic, stream
        self.clock, self.sleep = clock, sleep
        self.stop_event = threading.Event()
        self.opened = threading.Event()
        self.t_open = None
        self.late_s = []          # when the generator got to a request,
        #                           minus when it was due

    def open(self, t_open):
        self.t_open = t_open
        self.opened.set()

    def run(self):
        lead = lead_s(self.traffic)
        due = self.clock() - lead
        held = bool(self.traffic.get("ramp_sizes"))
        for req in self.stream:
            if held and not req.ramp:
                while not self.opened.wait(0.02):
                    if self.stop_event.is_set():
                        return
                due, held = self.t_open - lead, False
            due += req.gap_s
            while not self.stop_event.is_set():
                wait = due - self.clock()
                if wait <= 0:
                    break
                self.sleep(min(wait, 0.05))
            if self.stop_event.is_set():
                return
            self.late_s.append(self.clock() - due)
            self.client.submit(req, due=due, wait=False)

    def stop(self):
        self.stop_event.set()
        self.join(timeout=10.0)
