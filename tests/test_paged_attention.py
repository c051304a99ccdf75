"""`ops.paged_attention` — decode's attention over live pages only.

The pallas kernel (`mx_paged_decode`) runs here in interpret mode on the CPU
and is held against the XLA expression of the same op (the engine's gather of
every slot's whole view): on random pools, tables and lengths; with pages
shared between slots; with every page that is not alive poisoned. Then the
op's place in the engine: the counters that show the kernel engaged, and
`ServeEngine` serving the same greedy tokens through the kernel as through
the XLA expression. What the chip's compiler makes of the kernel is
`tests/test_chip_compile.py`'s; its name in a TPU lowering,
`tests/test_kernel_names.py`'s.
"""
import numpy as onp
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu import serve
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.ops import _dispatch
from incubator_mxnet_tpu.ops import paged_attention as pa
from incubator_mxnet_tpu.telemetry import registry, tracing

VOCAB = 97
H = 3


def _pools(rng, n_pages, pt, d):
    shape = (n_pages, H, pt, d)
    return (rng.normal(size=shape).astype(onp.float32),
            rng.normal(size=shape).astype(onp.float32))


def _live_pages(table, lengths, pt):
    return {int(table[s, i]) for s in range(table.shape[0])
            for i in range(-(-int(lengths[s]) // pt))}


def _both(q, kp, vp, table, lengths, poison=False):
    """The XLA expression on the pools as they are, the kernel on the same
    pools — with `poison`, every page no slot holds alive (the trash page
    0 among them) filled with NaN first. `kp` / `vp` are ``(n_pages, H,
    page_tokens, d)``; both sides get them as the engine stores them."""
    args = [jnp.asarray(a) for a in (table, lengths)]
    ref = pa._xla_paged_decode(jnp.asarray(q), pa.pack_pages(jnp.asarray(kp)),
                               pa.pack_pages(jnp.asarray(vp)), *args,
                               None, None)
    if poison:
        kp, vp = kp.copy(), vp.copy()
        live = _live_pages(table, lengths, kp.shape[2])
        dead = [p for p in range(kp.shape[0]) if p not in live]
        kp[dead] = onp.nan
        vp[dead] = onp.nan
    out = pa._pallas_paged_decode(jnp.asarray(q),
                                  pa.pack_pages(jnp.asarray(kp)),
                                  pa.pack_pages(jnp.asarray(vp)), *args, True)
    return onp.asarray(out), onp.asarray(ref)


LENGTHS = {"one": lambda pt, full: 1, "a_page": lambda pt, full: pt,
           "a_page_and_one": lambda pt, full: pt + 1,
           "full_view": lambda pt, full: full, "inactive": lambda pt, full: 0}


@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("d", [32, 64, 128])
@pytest.mark.parametrize("pt", [8, 16])
def test_kernel_matches_the_xla_expression(pt, d, length):
    P, S = 12, 3                       # 12 pages a slot: two blocks of 6
    rng = onp.random.default_rng([pt, d, len(length)])
    kp, vp = _pools(rng, S * P + 1, pt, d)
    table = rng.permutation(onp.arange(1, S * P + 1)).reshape(S, P) \
        .astype(onp.int32)
    n = LENGTHS[length](pt, P * pt)
    lengths = onp.asarray([n, int(rng.integers(1, P * pt)), n], onp.int32)
    q = rng.normal(size=(S, H, d)).astype(onp.float32)
    out, ref = _both(q, kp, vp, table, lengths)
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    if n == 0:
        assert not out[0].any() and not out[2].any()     # zeros, not NaN


def test_slots_sharing_prefix_pages():
    pt, d, P = 8, 64, 6
    rng = onp.random.default_rng(7)
    kp, vp = _pools(rng, 20, pt, d)
    table = onp.zeros((3, P), onp.int32)
    table[0, :4] = [3, 4, 5, 9]          # pages 3, 4, 5: one shared prefix
    table[1, :5] = [3, 4, 5, 11, 12]
    table[2, :2] = [3, 7]
    lengths = onp.asarray([4 * pt - 3, 5 * pt, pt + 1], onp.int32)
    q = rng.normal(size=(3, H, d)).astype(onp.float32)
    out, ref = _both(q, kp, vp, table, lengths, poison=True)
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("lengths", [
    (0, 33, 0, 100, 192, 0, 1, 0),       # free slots between live ones
    (0, 0, 0, 0, 0, 0, 0, 5),            # one short slot, last
    (17, 0, 0, 0, 0, 0, 0, 0),           # ... first
    (0, 0, 0, 0, 0, 0, 0, 0),            # nothing decodes
], ids=["mixed", "last_only", "first_only", "none"])
def test_dead_pages_and_the_trash_page_never_reach_the_output(lengths):
    """Not masked after the fact: a page past a slot's length, a free
    slot's row and the trash page hold NaN, and the output is the clean
    pools' to the last bit of the comparison."""
    pt, d, P, S = 16, 64, 12, 8
    rng = onp.random.default_rng(len(lengths) + sum(lengths))
    kp, vp = _pools(rng, S * P + 1, pt, d)
    table = rng.permutation(onp.arange(1, S * P + 1)).reshape(S, P) \
        .astype(onp.int32)
    lengths = onp.asarray(lengths, onp.int32)
    for s in range(S):                   # as the engine keeps a table: the
        table[s, -(-int(lengths[s]) // pt):] = 0     # unmapped tail -> trash
    q = rng.normal(size=(S, H, d)).astype(onp.float32)
    out, ref = _both(q, kp, vp, table, lengths, poison=True)
    assert not onp.isnan(out).any()
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_work_list_names_live_blocks_and_live_pages_only():
    """One grid step per live block; an operand whose page is past the
    length keeps the page it held the step before (an unchanged index
    fetches nothing), so no dead page, no free slot's row and not the
    trash page is ever named."""
    pt, G = 16, 4
    table = onp.arange(1, 25, dtype=onp.int32).reshape(3, 8)
    table[1] = 0                         # a free slot's row: the trash page
    lengths = onp.asarray([5 * pt, 0, 2 * pt + 1], onp.int32)
    n, slot, block, page = (onp.asarray(a) for a in pa._work_list(
        jnp.asarray(table), jnp.asarray(lengths), pt, G))
    assert n == 3                        # blocks 0, 1 of slot 0; 0 of slot 2
    onp.testing.assert_array_equal(slot[:3], [0, 0, 2])
    onp.testing.assert_array_equal(block[:3], [0, 1, 0])
    page = page.reshape(-1, G)
    onp.testing.assert_array_equal(page[0], [1, 2, 3, 4])
    onp.testing.assert_array_equal(page[1], [5, 2, 3, 4])    # 2-4 repeat
    onp.testing.assert_array_equal(page[2], [17, 18, 19, 4])
    # nothing decodes: one step all the same, and it attends nothing
    n0, slot0, _, _ = pa._work_list(jnp.asarray(table),
                                    jnp.zeros(3, jnp.int32), pt, G)
    assert n0 == 1 and slot0[0] == 0


def test_length_past_the_view_is_held_to_the_view():
    pt, d, P = 8, 64, 4
    rng = onp.random.default_rng(3)
    kp, vp = _pools(rng, 9, pt, d)
    table = onp.arange(1, 9, dtype=onp.int32).reshape(2, P)
    q = rng.normal(size=(2, H, d)).astype(onp.float32)
    out, ref = _both(q, kp, vp, table, onp.asarray([P * pt + 5, 3],
                                                   onp.int32))
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


# -- the op's choice, on record ----------------------------------------------

def _dispatch_count(impl):
    return _dispatch.choices().get(("paged_decode_attention", impl), 0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_dispatch_counter_ticks_for_the_branch_taken(impl, monkeypatch):
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: impl == "pallas")
    rng = onp.random.default_rng(1)
    kp, vp = _pools(rng, 9, 8, 64)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    before = _dispatch_count(impl)
    out = pa.paged_decode_attention(
        jnp.asarray(rng.normal(size=(2, H, 64)).astype(onp.float32)),
        pa.pack_pages(jnp.asarray(kp)), pa.pack_pages(jnp.asarray(vp)), table,
        jnp.asarray([9, 0], jnp.int32))
    assert out.shape == (2, H, 64) and not onp.asarray(out[1]).any()
    assert _dispatch_count(impl) == before + 1


def test_int8_pools_keep_the_xla_expression(monkeypatch):
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    assert pa.takes_kernel(jnp.float32) and pa.takes_kernel(jnp.bfloat16)
    assert not pa.takes_kernel(jnp.int8)


@pytest.mark.parametrize("pt,d,stored", [
    (16, 64, (8, 128)), (8, 64, (4, 128)), (16, 32, (4, 128)),
    (16, 128, (16, 128)), (16, 256, (16, 256)), (16, 96, (16, 96)),
    (1, 64, (1, 64))])
def test_a_page_is_stored_packed_to_128_lanes(pt, d, stored):
    """A head narrower than 128 lanes: ``128 // d`` tokens side by side in
    a row, where whole tokens fit a row and whole rows a page; else as it
    is. Packing and unpacking are reshapes."""
    assert pa.page_store_shape(pt, d) == stored
    x = jnp.arange(3 * 2 * pt * d, dtype=jnp.float32).reshape(3, 2, pt, d)
    packed = pa.pack_pages(x)
    assert packed.shape == (3, 2) + stored
    onp.testing.assert_array_equal(packed.reshape(-1), x.reshape(-1))
    onp.testing.assert_array_equal(pa.unpack_pages(packed, d), x)


# -- the op in the engine ----------------------------------------------------

@pytest.fixture(scope="module")
def net():
    n = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    n.initialize()
    return n


def _prompt(n, seed):
    return onp.random.RandomState(seed).randint(0, VOCAB, (n,)) \
        .astype(onp.int32)


def _serve(net, prompts, budget):
    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                          page_tokens=8, prefill_chunk=16)
    try:
        handles = [e.submit(p, budget) for p in prompts]
        e._drive_until(handles)
        return [list(h.result()) for h in handles], e._sched.slots
    finally:
        e.shutdown(drain=False)


def test_engine_serves_the_same_tokens_through_the_kernel(net, monkeypatch):
    """Multi-request, shared system prompt, more requests than slots: the
    kernel (interpret mode) against the XLA expression, greedy."""
    system = _prompt(24, seed=42)                        # 3 shared pages
    prompts = [onp.concatenate([system, _prompt(2 + i, seed=100 + i)])
               for i in range(5)] + [_prompt(5, seed=9)]
    want, slots_x = _serve(net, prompts, 6)

    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    hits0 = registry.counter("mx_serve_prefix_hits_total").value
    before = _dispatch_count("pallas")
    got, slots_k = _serve(net, prompts, 6)
    assert _dispatch_count("pallas") >= before + 2       # a call a layer
    assert registry.counter("mx_serve_prefix_hits_total").value > hits0
    assert got == want


def test_draft_program_takes_the_kernel_and_spec_tokens_hold(net,
                                                             monkeypatch):
    """The decode step's cache access (`serve.pages.TokenCache`) is also
    each unrolled step of the draft program's: with the target as its own
    draft (everything accepted, the draft pool tracking the committed
    prefix) the kernel path serves the tokens the XLA path serves."""
    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    def spec(prompts):
        e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8,
                              page_tokens=8, spec_k=2, draft=GPTDecoder(net))
        try:
            handles = [e.submit(p, 8) for p in prompts]
            e._drive_until(handles)
            return [list(h.result()) for h in handles], e.spec_stats()
        finally:
            e.shutdown(drain=False)

    prompts = [_prompt(9, seed=3), _prompt(5, seed=4), _prompt(12, seed=5)]
    want, stats_x = spec(prompts)
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    before = _dispatch_count("pallas")
    got, stats = spec(prompts)
    assert _dispatch_count("pallas") >= before + 4       # 2 steps x 2 layers
    assert got == want
    assert stats["accept_rate"] == stats_x["accept_rate"] > 0.8


def test_decode_step_counts_live_and_view_pages(net):
    live = registry.counter("mx_serve_decode_pages_total",
                            labels={"kind": "live"})
    view = registry.counter("mx_serve_decode_pages_total",
                            labels={"kind": "view"})
    l0, v0 = live.value, view.value
    t0 = tracing.stamp()
    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=8,
                          page_tokens=8)
    try:
        e.generate(_prompt(10, seed=1), 4)
    finally:
        e.shutdown(drain=False)
    recs = [r for r in tracing.step_records(since=t0) if r["decoding"]]
    # one slot decodes at positions 10, 11, 12 of 8-token pages: 2 pages
    # a step, against 3 slots x 8 pages of view
    assert [r["pages_live"] for r in recs] == [2, 2, 2]
    assert {r["pages_view"] for r in recs} == {24}
    assert live.value - l0 == 6 and view.value - v0 == 72
    assert all(r["pages_live"] == 0 for r in tracing.step_records(since=t0)
               if not r["decoding"])
