"""`ops.paged_attention` — decode's attention over live pages only.

The pallas kernel (`mx_paged_decode`) runs here in interpret mode on the CPU
and is held against the XLA expression of the same op (the engine's gather of
every slot's whole view): on random pools, tables and lengths; in the three
forms the benchmark's cells give it (narrow float32 heads packed two to a
row, bfloat16 heads of 128 with and without grouped query heads: the last
two multiply on the MXU); with pages shared between slots; with every page
that is not alive poisoned; and the grid both kernels of the file are handed
(`_block_list`). Then the
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
    P, S = 12, 3                       # 12 pages a slot: one block
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


# the three cells' forms: query heads, stored heads, head size, dtype, and
# what the shape gives them: the pages of a block, and the body (the MXU's
# products or the VPU's passes)
FORMS = {"gpt2xl": (25, 25, 64, jnp.float32, 8, False),
         "evabyte": (32, 32, 128, jnp.bfloat16, 8, True),
         "nemotron": (32, 2, 128, jnp.bfloat16, 32, True)}
# where a slot's length ends, in pages of `pt` and blocks of `G` pages
ENDS = {"empty": lambda pt, G: 0, "inside_a_page": lambda pt, G: pt + 3,
        "a_pages_edge": lambda pt, G: 3 * pt,
        "a_blocks_edge": lambda pt, G: G * pt,
        "past_a_block": lambda pt, G: G * pt + 1,
        "full_view": lambda pt, G: 2 * G * pt}


def _form_case(form, length, seed=0):
    """Pools, a table whose dead entries name the trash page, lengths and
    queries in one cell's form: three slots of two blocks each, the middle
    one of another length; every page no live entry names holds NaN."""
    hq, hk, d, dtype, G, on_mxu = FORMS[form]
    pt, S, P = 16, 3, 2 * G
    assert pa._block_pages(P, hk * pt * d * jnp.dtype(dtype).itemsize) == G
    rng = onp.random.default_rng([seed, len(form), len(length)])
    n_pages = S * P + 1
    k, v = (rng.normal(size=(n_pages, hk, pt, d)).astype(onp.float32)
            for _ in range(2))
    table = rng.permutation(onp.arange(1, n_pages)).reshape(S, P) \
        .astype(onp.int32)
    n = ENDS[length](pt, G)
    lengths = onp.asarray([n, int(rng.integers(1, P * pt)), n], onp.int32)
    for s in range(S):
        table[s, -(-int(lengths[s]) // pt):] = 0      # unmapped -> trash
    dead = sorted(set(range(n_pages)) - _live_pages(table, lengths, pt))
    k[dead] = v[dead] = onp.nan
    q = rng.normal(size=(S, hq, d)).astype(onp.float32)
    q, k, v = (jnp.asarray(a).astype(dtype) for a in (q, k, v))
    return (q, pa.pack_pages(k), pa.pack_pages(v), jnp.asarray(table),
            jnp.asarray(lengths)), (G, P, pt, on_mxu)


@pytest.mark.parametrize("length", sorted(ENDS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_kernel_in_the_three_cells_forms(form, length):
    """Against the XLA expression on the clean pools. The MXU's body hands
    the weights to the second product in the pool's dtype, as the XLA
    expression does: what differs is where the sums round, inside bfloat16's
    own step; the VPU's body is exact float32."""
    args, (G, P, pt, on_mxu) = _form_case(form, length)
    q, k, v, table, lengths = args
    assert pa._on_mxu(q, k) is on_mxu
    clean = [jnp.nan_to_num(a) for a in (k, v)]
    ref = onp.asarray(pa._xla_paged_decode(q, *clean, table, lengths, None,
                                           None), onp.float32)
    out = onp.asarray(pa._pallas_paged_decode(*args, True), onp.float32)
    assert not onp.isnan(out).any()
    tol = dict(rtol=2e-2, atol=2e-2) if on_mxu else dict(rtol=2e-5, atol=2e-6)
    onp.testing.assert_allclose(out, ref, **tol)
    if int(lengths[0]) == 0:
        assert not out[0].any() and not out[2].any()     # zeros, not NaN


def test_mxu_body_is_plain_attention_in_float32_to_bfloat16s_step():
    """The grouped bfloat16 form against dense float32 attention over the
    same (bfloat16-valued) rows: the kernel's own rounding (`p` handed to the
    second product in bfloat16, sums in float32) stays inside the stated
    precision, whatever the XLA expression rounds."""
    args, (G, P, pt, _) = _form_case("nemotron", "past_a_block", seed=1)
    q, k, v, table, lengths = (onp.asarray(a, onp.float32) for a in args)
    out = onp.asarray(pa._pallas_paged_decode(*args, True), onp.float32)
    hq, hk, d = q.shape[1], k.shape[1], q.shape[2]
    for s, n in enumerate(lengths.astype(int)):
        pages = table[s].astype(int)[:-(-n // pt)]
        ks = onp.concatenate([k[p] for p in pages], 1)[:, :n]
        vs = onp.concatenate([v[p] for p in pages], 1)[:, :n]
        for h in range(hq):
            sc = ks[h // (hq // hk)] @ q[s, h] / onp.sqrt(d)
            w = onp.exp(sc - sc.max())
            want = (w / w.sum()) @ vs[h // (hq // hk)]
            onp.testing.assert_allclose(out[s, h], want, atol=8e-3)


def test_block_list_names_live_blocks_and_counts_their_live_pages():
    """The grid both kernels are handed: one step per live block, slot after
    slot, with the slot's live pages from the block's first on; a free slot
    has no step, and nothing alive leaves one step that attends nothing. No
    page is named: the kernels read the table themselves, a live page at a
    time, so a dead entry (the trash page) is never fetched."""
    pt, G, NB = 16, 4, 2
    lengths = jnp.asarray([5 * pt, 0, 2 * pt + 1, 8 * pt], jnp.int32)
    n, slot, block, pages = (onp.asarray(a) for a in pa._block_list(
        lengths, pt, G, NB))
    assert n == 5            # blocks 0, 1 of slot 0; 0 of slot 2; 0, 1 of 3
    onp.testing.assert_array_equal(slot[:5], [0, 0, 2, 3, 3])
    onp.testing.assert_array_equal(block[:5], [0, 1, 0, 0, 1])
    onp.testing.assert_array_equal(pages[:5], [5, 1, 3, 8, 4])
    n0, slot0, block0, pages0 = pa._block_list(jnp.zeros(4, jnp.int32), pt,
                                               G, NB)
    assert n0 == 1 and slot0[0] == 0 and block0[0] == 0 and pages0[0] == 0
    # what both kernels' callers hand on (`_prefetched`): that grid, the
    # lengths held to the view, and the table flat and clipped to the pool,
    # because the kernels' copies carry no range checks
    table = jnp.arange(32, dtype=jnp.int32).reshape(4, 8) - 3
    n1, (lens, slot1, block1, pages1, flat) = pa._prefetched(
        table, lengths.at[3].set(99 * pt), n_pages=20, page_tokens=pt,
        block_pages=G)
    assert n1 == n and int(lens[3]) == 8 * pt
    for got, want in ((slot1, slot), (block1, block), (pages1, pages)):
        onp.testing.assert_array_equal(got, want)
    onp.testing.assert_array_equal(flat, onp.clip(onp.arange(32) - 3, 0, 19))


@pytest.mark.parametrize("table_pages,page_bytes,want", [
    (192, 8192, 32),          # nemotron3super.turns: 32 pages of 8 KB
    (248, 131072, 8),         # evabyte.docs: 8 pages of 128 KB, 1 MB of K
    (64, 102400, 8),          # gpt2xl.chat: 8 pages of 100 KB
    (768, 20480, 32),         # pangu718b.think's latent pages
    (7, 8192, 7), (62, 8192, 31), (5, 2 ** 21, 1)])
def test_pages_a_block_come_from_the_pages_bytes_and_the_tables_width(
        table_pages, page_bytes, want):
    assert pa._block_pages(table_pages, page_bytes) == want
    assert pa._block_pages(table_pages, page_bytes, at_most=4) == max(
        g for g in range(1, min(want, 4) + 1) if table_pages % g == 0)


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


@pytest.mark.parametrize("form", sorted(FORMS))
def test_products_counter_says_which_body_ran(form, monkeypatch):
    """``mx_kernel_dispatch_total{op="paged_decode_products"}``: one tick a
    traced call of the kernel, `impl` the unit the shape chose; none where
    the XLA expression is taken."""
    args, (_, _, _, on_mxu) = _form_case(form, "inside_a_page")

    def count():
        got = _dispatch.choices()
        return [got.get(("paged_decode_products", impl), 0)
                for impl in ("mxu", "vpu")]

    before = count()
    pa.paged_decode_attention(*args)                 # the CPU: XLA
    assert count() == before
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    pa.paged_decode_attention(*args)
    assert count() == [before[0] + on_mxu, before[1] + (not on_mxu)]


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
