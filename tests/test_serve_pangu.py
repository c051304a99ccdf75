"""`mx.serve` for the openPangu-Ultra-MoE family (`serve/mla.py`,
`models/pangu.py`, `ops/moe.py`, `ops/paged_attention.py`'s latent pages):
the program against the plain reference through `ServeEngine` (chunked
prefill, then decode, through the latent cache), absorbed against
up-projected attention, the kernels in interpret mode against their XLA
expressions, the expert layer's share arithmetic, its dropless guarantee, a
step with no held pair, prefix reuse, and the refusals."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from chipbench.reference import pangu as ref
from chipbench.runners import serve_pangu
from incubator_mxnet_tpu.models import pangu
from incubator_mxnet_tpu.ops import _dispatch, moe, paged_attention
from incubator_mxnet_tpu.serve import ShardedSlotDecoder, SlotDecoder
from incubator_mxnet_tpu.serve.api import slots_class
from incubator_mxnet_tpu.serve.mla import MLASlotDecoder
from incubator_mxnet_tpu.telemetry import registry, tracing

# 1 dense + 2 expert layers x 64; 4 heads (nope 16, rope 8, v 16); latent
# row 32 + 8; 16 routed experts, top-4, ids 4-9 held
CFG = dict(num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
           intermediate_size=96, moe_intermediate_size=32,
           num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
           routed_scaling_factor=2.5, vocab_size=50, rope_theta=25600000,
           rms_norm_eps=1e-5, max_position_embeddings=160,
           experts_held=[4, 6], init_std=0.02)
ENGINE = dict(max_slots=3, max_len=160, page_tokens=4, prefill_chunk=16)
MLA_DECODE = paged_attention.mla_decode_attention


@pytest.fixture(scope="module")
def dec():
    return serve_pangu.build_decoder(CFG, 7, ref, "float32")


def drive(eng, handles, limit=4000):
    for _ in range(limit):
        if all(h.done for h in handles):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def gaps_to_reference(cfg, seed, prompts, outs, pad=160):
    tokens = onp.zeros((len(prompts), pad), onp.int32)
    rows = []
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = onp.concatenate([p, onp.asarray(o, onp.int32)])
        tokens[b, :seq.size - 1] = seq[:-1]
        rows += [(b, p.size - 1 + j) for j in range(len(o))]
    logits = ref.logits_at(cfg, seed, tokens, rows)
    served = onp.concatenate([onp.asarray(o) for o in outs])
    return logits.max(-1) - logits[onp.arange(served.size), served]


# -- (a) the program against the reference ------------------------------------

def test_served_logits_are_the_references_through_the_latent_cache(dec):
    """Three requests of unlike lengths in the slots at once (a fourth
    queued behind them): prefill in chunks of 16 and 4, then decode through
    the latent pages; the served token is the reference's best at every
    position (float32: to 1e-4), and the expert layers' counts came home in
    the tokens' fetch."""
    registry.reset()
    tracing.reset()
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    assert type(eng._sched.slots) is MLASlotDecoder
    rng = onp.random.default_rng(0)
    prompts = [rng.integers(0, 50, n).astype(onp.int32)
               for n in (70, 33, 90, 12)]
    new = [40, 35, 10, 60]
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    drive(eng, handles)
    outs = [h.result() for h in handles]
    gap = gaps_to_reference(CFG, 7, prompts, outs)
    assert gap.size == sum(new) and gap.max() <= 1e-4
    rep = {k: v["value"] for k, v in registry.report().items()
           if "value" in v}
    held = rep['mx_serve_moe_pairs_total{kind="held"}']
    routed = rep['mx_serve_moe_pairs_total{kind="routed"}']
    assert 0 < held < routed
    assert 0 < rep["mx_serve_moe_experts_hit_total"] <= held
    assert rep['mx_serve_decode_rows_total{kind="latent"}'] > 0
    assert rep['mx_kernel_dispatch_total{impl="xla",'
               'op="mla_decode_attention"}'] >= 3
    assert rep['mx_kernel_dispatch_total{impl="xla",op="moe_experts"}'] >= 2
    recs = [r for r in tracing.step_records() if "moe_pairs_held" in r]
    assert recs and sum(r["moe_pairs_held"] for r in recs) == held
    assert sum(r["moe_pairs_routed"] for r in recs) == routed
    # two expert layers, four experts a token: a decode-only step over n
    # slots routed 8 n pairs
    for r in recs:
        if r.get("decoding") and not r.get("chunks"):
            assert r["moe_pairs_routed"] % 8 == 0
            assert r["moe_experts_hit"] <= 2 * 6
    eng.shutdown(drain=False)


def test_latent_rows_in_the_pool_are_the_layers_own(dec):
    """What a slot's pages hold after prefill is ``[c_kv ; k_rope]`` of
    every position, as `PanguDecoder.project` makes them."""
    slots = MLASlotDecoder(dec, **ENGINE)
    prompt = onp.random.default_rng(1).integers(0, 50, 23).astype(onp.int32)
    pages = slots.allocator.alloc(slots.pages_needed(23))
    slots.set_slot_pages(1, pages)
    key = jax.random.key(0)
    for t0 in (0, 16):
        slots.prefill_chunk_step(1, prompt[t0:t0 + 16], t0, key)
    got = slots.slot_kv(1, 23)                         # (L, 23, 40)
    params = dec._params
    x = dec.embed(params, jnp.asarray(prompt)[None], None)
    _, _, latent = dec.project(params["layers"][0], x, jnp.arange(23))
    assert got.shape == (3, 23, 40)
    onp.testing.assert_allclose(got[0], onp.asarray(latent), atol=1e-5)


def test_prefix_reuse_serves_the_same_tokens(dec):
    """Latent pages are exact rows keyed by the token prefix: a second
    request behind a shared prefix is admitted on a hit and served the
    tokens a cold engine serves."""
    rng = onp.random.default_rng(4)
    system = rng.integers(0, 50, 24).astype(onp.int32)
    prompts = [onp.concatenate([system, rng.integers(0, 50, n).astype(
        onp.int32)]) for n in (9, 14)]
    outs = {}
    for reuse in (True, False):
        eng = mx.serve.ServeEngine(dec, prefix_reuse=reuse, **ENGINE)
        got = []
        for p in prompts:
            h = eng.submit(p, 10)
            drive(eng, [h])
            got.append((h.result(), h.shared_tokens))
        outs[reuse] = got
        eng.shutdown(drain=False)
    assert outs[True][1][1] == 24 and outs[False][1][1] == 0
    assert [o for o, _ in outs[True]] == [o for o, _ in outs[False]]


# -- (b) the two forms of attention -------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_absorbed_and_up_projected_attention_agree(dec, impl, monkeypatch):
    """One slot's rows written by a chunk of 16 at positions 8..23 (behind
    8 rows of an earlier chunk), attended up-projected by the chunk's cache;
    then each of three of those positions attended absorbed by the decode
    step's cache (the XLA expression, and the kernel in interpret mode) over
    the same pool: the same output."""
    from incubator_mxnet_tpu.serve import mla

    monkeypatch.setattr(
        paged_attention, "mla_decode_attention",
        lambda *a, **kw: MLA_DECODE(*a, **dict(kw, impl=impl)))
    slots = MLASlotDecoder(dec, **ENGINE)
    slots._ensure_pool()
    slots.set_slot_pages(1, slots.allocator.alloc(8))
    lp = dec._params["layers"][1]
    rng = onp.random.default_rng(2)
    q_nope = jnp.asarray(rng.normal(size=(24, 4, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(24, 4, 8)), jnp.float32)
    latent = jnp.asarray(rng.normal(size=(24, 40)), jnp.float32)
    pools = slots._pools
    for t0, n in ((0, 8), (8, 16)):
        cache = mla._ChunkCache(slots, pools, *slots._chunk_pages(1, t0, n),
                                jnp.int32(t0), jnp.int32(n))
        up = cache.attend(1, lp, q_nope[t0:t0 + n], q_rope[t0:t0 + n],
                          latent[t0:t0 + n])
        pools = cache.pools()
    assert up.shape == (16, 4, 16)
    for pos in (8, 15, 23):
        at = jnp.asarray([0, pos, 0], jnp.int32)
        active = jnp.asarray([False, True, False])
        cache = slots._token_cache(pools, jnp.asarray(slots._table), at,
                                   active)
        pick = lambda a: jnp.stack([a[0], a[pos], a[0]])  # noqa: E731
        ab = cache.attend(1, lp, pick(q_nope), pick(q_rope), pick(latent))
        onp.testing.assert_allclose(onp.asarray(ab[1]),
                                    onp.asarray(up[pos - 8]), atol=2e-5)
        assert not onp.asarray(ab[0]).any()      # a slot that attends nothing


def test_stored_form_is_the_checkpoints_product(dec):
    """`models.pangu.stored`: ``q_b_proj`` regrouped and ``kv_b_proj``
    split give what the checkpoint layout's own products give."""
    cfg = pangu.PanguConfig.from_dict(CFG)
    rng = onp.random.default_rng(3)
    wq = jnp.asarray(rng.normal(size=(4 * 24, 48)), jnp.float32)
    cq = jnp.asarray(rng.normal(size=(3, 48)), jnp.float32)
    want = (cq @ wq.T).reshape(3, 4, 24)
    got = cq @ pangu.stored(cfg, "w_qb", wq)
    onp.testing.assert_allclose(got[:, :64].reshape(3, 4, 16),
                                want[..., :16], atol=1e-5)
    onp.testing.assert_allclose(got[:, 64:].reshape(3, 4, 8),
                                want[..., 16:], atol=1e-5)
    wkv = jnp.asarray(rng.normal(size=(4 * 32, 32)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    kv = (c @ wkv.T).reshape(3, 4, 32)
    onp.testing.assert_allclose(
        jnp.einsum("rc,hnc->rhn", c, pangu.stored(cfg, "w_uk", wkv)),
        kv[..., :16], atol=1e-5)
    onp.testing.assert_allclose(
        jnp.einsum("rc,hcv->rhv", c, pangu.stored(cfg, "w_uv", wkv)),
        kv[..., 16:], atol=1e-5)


# -- (c) the kernels in interpret mode ----------------------------------------

# (id, lengths, pages a block at most, pool dtype[, widths[, hot]]): of a
# table of 8 pages of 4 rows a slot, 4 heads against rows of 32 + 8 stored
# 128 wide. The kernel fetches a block's pages itself into one of a few
# buffers, so the cases walk every way a buffer can be left and found. It
# works a block of an even number of pages in two halves, each its own
# softmax update: lengths end at a half's edge, a row past it and a row
# before it. `hot` is a range of the second slot's pages whose rows are
# scaled x100, a later half's scores far above an earlier one's, so that
# the update rescales a sum that is not empty
MLA_CASES = [
    ("ragged", (5, 0, 29), 32, jnp.float32),
    ("full-and-one", (32, 17, 1), 32, jnp.float32),
    ("nothing-alive", (0, 0, 0), 32, jnp.float32),
    # four and eight blocks of one slot: every buffer used, and used again
    ("four-blocks", (5, 0, 29), 2, jnp.float32),
    ("eight-blocks-each", (32, 32, 32), 1, jnp.float32),
    # a short slot straight after a long one whose rows are large: the rows
    # the long one left in the buffers must not reach the short one's sum
    ("short-after-long", (32, 3, 6), 4, jnp.float32),
    ("short-after-long-2", (31, 1, 9), 2, jnp.float32),
    ("one-whole-block", (0, 16, 0), 4, jnp.float32),
    ("whole-blocks", (8, 16, 24), 2, jnp.float32),
    ("one-live-among-free", (0, 9, 0), 2, jnp.float32),
    ("last-slot-alone", (0, 0, 32), 4, jnp.float32),
    ("one-row-in-all", (0, 1, 0), 2, jnp.float32),
    ("bf16-ragged", (5, 0, 29), 2, jnp.bfloat16),
    ("bf16-full", (32, 17, 1), 4, jnp.bfloat16),
    # blocks of 4 pages: halves of 8 rows, in the first block and a later
    ("half-edge", (8, 9, 7), 4, jnp.float32),
    ("later-half-edge", (24, 25, 23), 4, jnp.float32),
    ("bf16-half-edge", (8, 9, 7), 4, jnp.bfloat16),
    # the whole table a block: halves of 16 rows
    ("half-edge-one-block", (16, 17, 15), 32, jnp.float32),
    ("later-half-far-above", (5, 16, 0), 4, jnp.float32, "toy", (2, 4)),
    ("later-block-far-above", (0, 30, 9), 4, jnp.float32, "toy", (6, 8)),
] + [(f"cell-{name}", lengths, 32, jnp.bfloat16, "cell", *hot)
     for name, lengths, *hot in [
    # the cell's widths: 128 heads against rows of 512 + 64 stored 640 wide,
    # pages of 16 rows, 96 a slot, the kernel's own block of 32 (512 rows),
    # where the MXU holds the slot's queries and weights, not the block
    ("nothing-alive", (0, 0, 0)),
    ("one-row", (0, 1, 0)),
    ("one-block", (512, 0, 0)),
    ("one-block-and-a-row", (0, 0, 513)),
    ("blocks-partial-last", (1300, 0, 0)),
    # the slot changes between grid steps, a short one after a long one
    ("slots-change", (513, 1, 1300)),
    # halves of 256 rows
    ("half-edge", (256, 257, 255)),
    ("later-half-edge", (768, 769, 767)),
    ("later-half-far-above", (0, 700, 0), (16, 32))]]
# heads, rank, rope, page rows, table pages
MLA_WIDTHS = {"toy": (4, 32, 8, 4, 8), "cell": (128, 512, 64, 16, 96)}


@pytest.mark.parametrize("lengths,block_pages,dtype,widths,hot",
                         [(c + ("toy", None)[len(c) - 4:])[1:]
                          for c in MLA_CASES],
                         ids=[c[0] for c in MLA_CASES])
def test_mla_decode_kernel_is_its_xla_expression(lengths, block_pages, dtype,
                                                 widths, hot):
    rng = onp.random.default_rng(0)
    S = 3
    H, rank, dr, pt, P = MLA_WIDTHS[widths]
    W = paged_attention.latent_store_width(rank + dr)
    assert paged_attention.latent_store_width(40) == 128
    assert paged_attention.latent_store_width(576) == 640
    n_pages = S * P + 16
    table = jnp.asarray(rng.permutation(onp.arange(1, n_pages))[:S * P]
                        .reshape(S, P), jnp.int32)
    pool = rng.normal(size=(n_pages, pt, W))
    pool[onp.asarray(table[0])] *= 100.0          # the first slot's rows
    if hot is not None:
        pool[onp.asarray(table[1, slice(*hot)])] *= 100.0
    pool = jnp.asarray(pool, dtype).at[..., rank + dr:].set(0)
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    q = q.at[..., rank + dr:].set(0)
    n = jnp.asarray(lengths, jnp.int32)
    scale = 0.2 if widths == "toy" else 192 ** -0.5
    a = paged_attention.mla_decode_attention(q, pool, table, n, impl="xla",
                                             rank=rank, sm_scale=scale)
    b = paged_attention._pallas_mla_decode(q, pool, table, n, rank, scale,
                                           True, block_pages=block_pages)
    assert a.shape == b.shape == (S, H, rank) and b.dtype == q.dtype
    # bfloat16: the kernel rounds exp(s - m) of a running maximum, the
    # expression a softmax already normalised
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    if widths == "toy":
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b), atol=tol,
                                    rtol=tol)
    for s, ln in enumerate(lengths):
        if widths == "cell":
            # on the slot's own scale: the first slot's outputs reach ~400
            # at these widths, where the parent's body was ~0.9 off too
            atol = tol * max(1.0, float(onp.abs(onp.asarray(a[s])).max()))
            onp.testing.assert_allclose(onp.asarray(a[s]), onp.asarray(b[s]),
                                        atol=atol, rtol=tol)
        if ln == 0:
            assert not onp.asarray(b[s]).any()


def test_mla_decode_products_counter_says_which_form_ran(monkeypatch):
    """``mx_kernel_dispatch_total{op="mla_decode_products"}``: one tick a
    traced call of the kernel, `impl` the form its products take; none
    where the XLA expression is taken."""
    def count():
        return _dispatch.choices().get(("mla_decode_products",
                                        "queries_held_overlapped"), 0)

    rng = onp.random.default_rng(3)
    args = (jnp.asarray(rng.normal(size=(1, 2, 128)), jnp.float32),
            jnp.asarray(rng.normal(size=(9, 4, 128)), jnp.float32),
            jnp.arange(1, 9, dtype=jnp.int32).reshape(1, 8),
            jnp.asarray([30], jnp.int32))
    before = count()
    MLA_DECODE(*args, rank=32, sm_scale=0.2)        # the CPU: XLA
    assert count() == before
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    MLA_DECODE(*args, rank=32, sm_scale=0.2)
    assert count() == before + 1


def test_mla_decode_op_takes_the_kernel_by_name():
    """`impl="pallas"` of the op is the kernel at its own block size."""
    rng = onp.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(9, 4, 128)), jnp.float32)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(1, 8)
    q = jnp.asarray(rng.normal(size=(1, 2, 128)), jnp.float32)
    n = jnp.asarray([30], jnp.int32)
    kw = dict(rank=32, sm_scale=0.2)
    onp.testing.assert_allclose(
        onp.asarray(paged_attention.mla_decode_attention(
            q, pool, table, n, impl="pallas", **kw)),
        onp.asarray(paged_attention.mla_decode_attention(
            q, pool, table, n, impl="xla", **kw)), atol=1e-5)


def _plain_experts(u, ids, w, ws, held, valid):
    out = onp.zeros(u.shape, onp.float64)
    u = onp.asarray(u, onp.float64)
    for t in range(u.shape[0]):
        if not valid[t]:
            continue
        for k in range(ids.shape[1]):
            e = int(ids[t, k]) - held[0]
            if 0 <= e < held[1]:
                g = u[t] @ onp.asarray(ws[0][e], onp.float64)
                g = g / (1 + onp.exp(-g)) * (u[t] @ onp.asarray(ws[1][e]))
                out[t] += float(w[t, k]) * (g @ onp.asarray(ws[2][e]))
    return out


@pytest.mark.parametrize("t,top_k", [(12, 4), (160, 4)],
                         ids=["tile-16", "tile-128"])
def test_moe_kernel_is_its_xla_expression_and_a_plain_loop(t, top_k):
    rng = onp.random.default_rng(1)
    C, F, E, held = 16, 24, 32, (4, 6)
    u = jnp.asarray(rng.normal(size=(t, C)), jnp.float32)
    ids, w = moe.route(u, jnp.asarray(rng.normal(size=(C, E)), jnp.float32),
                       top_k, 2.5)
    ws = tuple(jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
               for s in ((6, C, F), (6, C, F), (6, F, C)))
    valid = jnp.arange(t) < t - 2
    assert moe._tile_rows(t * top_k, E) == (16 if t == 12 else 128)
    ya, sa = moe.held_experts(u, ids, w, ws, held, valid, impl="xla",
                              routed=E)
    yb, sb = moe.held_experts(u, ids, w, ws, held, valid, impl="pallas",
                              routed=E)
    want = _plain_experts(u, onp.asarray(ids), onp.asarray(w), ws, held,
                          onp.asarray(valid))
    onp.testing.assert_allclose(onp.asarray(ya), want, atol=1e-4)
    onp.testing.assert_allclose(onp.asarray(yb), want, atol=1e-4)
    ok = (onp.asarray(ids) >= 4) & (onp.asarray(ids) < 10) \
        & onp.asarray(valid)[:, None]
    hit = len(set(onp.asarray(ids)[ok].tolist()))
    assert list(sa) == list(sb) == [int(ok.sum()), hit]
    onp.testing.assert_allclose(
        onp.asarray(w).sum(-1), 2.5, atol=1e-5)    # norm_topk_prob x scale


def test_a_step_with_no_held_pair_adds_nothing():
    rng = onp.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(10, 32, (8, 4)), jnp.int32)
    w = jnp.full((8, 4), 0.625, jnp.float32)
    ws = tuple(jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((6, 16, 24), (6, 16, 24), (6, 24, 16)))
    for impl in ("xla", "pallas"):
        y, stats = moe.held_experts(u, ids, w, ws, (4, 6), impl=impl)
        assert not onp.asarray(y).any() and list(stats) == [0, 0]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_dropless_when_every_token_chooses_one_held_expert(impl):
    """Routing planted so that all 40 tokens choose expert 7 (and three
    that are not held): a layer with a capacity would drop most of them;
    here every pair is computed (three tiles of 16 rows for one expert)."""
    rng = onp.random.default_rng(3)
    t, C, F = 40, 16, 24
    u = jnp.asarray(rng.normal(size=(t, C)), jnp.float32)
    ids = jnp.tile(jnp.asarray([[7, 0, 12, 15]], jnp.int32), (t, 1))
    w = jnp.asarray(rng.uniform(0.2, 1.0, (t, 4)), jnp.float32)
    ws = tuple(jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
               for s in ((6, C, F), (6, C, F), (6, F, C)))
    y, stats = moe.held_experts(u, ids, w, ws, (4, 6), impl=impl)
    want = _plain_experts(u, onp.asarray(ids), onp.asarray(w), ws, (4, 6),
                          onp.ones(t, bool))
    assert list(stats) == [40, 1]
    assert onp.abs(want).min(axis=1).max() > 0      # every token got its part
    onp.testing.assert_allclose(onp.asarray(y), want, atol=1e-4)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_whole_layer():
    """Sixteen chips a layer, each told which experts it holds: the routed
    parts the 16 shares compute (here 4 shares of 4), added up, plus the
    shared expert counted once, are the uncut reference layer's ``F``."""
    whole = dict(CFG, experts_held=None)
    s = ref.sizes(whole)
    key = ref.seeded.key_of(11)
    pr = ref._programs(s, "float32")
    li = 2
    rng = onp.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    # the reference's F over all 16 experts
    p = pr["shared"](key, jnp.int32(li))
    ids, w, f = pr["route_step"](p, u)
    shared_part = f
    for e in ref.held_ids(s):
        f = pr["expert_step"](pr["expert"](key, jnp.int32(
            ref.expert_code(li, e))), u, ids, w, f, jnp.int32(e))
    # the program's routed part, share by share
    cfg = pangu.PanguConfig.from_dict(whole)
    router = pangu.stored(cfg, "w_router", p["mlp.gate.weight"])
    total, pairs = 0.0, 0
    for first in (0, 4, 8, 12):
        ws = [jnp.stack([pangu.stored(cfg, name, pr["expert"](
            key, jnp.int32(ref.expert_code(li, e)))[tag])
            for e in range(first, first + 4)])
            for name, tag in (("we_gate", "mlp.experts.gate_proj.weight"),
                              ("we_up", "mlp.experts.up_proj.weight"),
                              ("we_down", "mlp.experts.down_proj.weight"))]
        pid, pw = moe.route(u, router, 4, 2.5)
        part, stats = moe.held_experts(u, pid, pw, ws, (first, 4))
        total = total + part
        pairs += int(stats[0])
    assert pairs == 9 * 4                   # every pair is some share's
    onp.testing.assert_allclose(onp.asarray(shared_part + total),
                                onp.asarray(f), atol=2e-4)


# -- (d) the refusals ---------------------------------------------------------

def test_the_family_table_picks_the_slots_class_and_names_what_it_knows(dec):
    assert slots_class(dec) is MLASlotDecoder

    class Unknown:
        family = "mamba"

    with pytest.raises(ValueError, match="evabyte.*pangu_moe"):
        slots_class(Unknown())
    with pytest.raises(ValueError, match="mamba"):
        mx.serve.ServeEngine(Unknown())


@pytest.mark.parametrize("kwargs,what", [
    (dict(spec_k=2), "speculative"), (dict(draft="ngram"), "speculative"),
    (dict(kv_dtype="int8"), "kv_dtype"),
], ids=["spec_k", "draft", "int8"])
def test_refused_settings_say_so(dec, kwargs, what):
    with pytest.raises(NotImplementedError, match=what):
        mx.serve.ServeEngine(dec, **ENGINE, **kwargs)


def test_handoff_adoption_and_sharding_are_refused(dec):
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng._sched.submit(onp.arange(10, dtype=onp.int32), 4,
                          prefill_only=True)
    with pytest.raises(NotImplementedError, match="adoption"):
        eng._sched.adopt_page_plan(10, 4)
    eng.shutdown(drain=False)
    with pytest.raises(NotImplementedError, match="sharded"):
        ShardedSlotDecoder(dec, 2)
    with pytest.raises(TypeError):
        SlotDecoder(dec)
    with pytest.raises(TypeError):
        MLASlotDecoder(object())
    with pytest.raises(NotImplementedError, match="int8"):
        from incubator_mxnet_tpu.serve.pages import make_pools

        make_pools(4, 4, dec.kv_geometry(), "int8")


def test_a_decoder_is_held_to_its_configurations_shapes(dec):
    cfg = pangu.PanguConfig.from_dict(CFG)
    params = dict(dec._params, layers=list(dec._params["layers"]))
    with pytest.raises(ValueError, match="layers given"):
        pangu.PanguDecoder(cfg, dict(params, layers=params["layers"][:2]))
    bad = dict(params["layers"][1], we_gate=params["layers"][1]["we_gate"][:5])
    with pytest.raises(ValueError, match="layers.1.we_gate"):
        pangu.PanguDecoder(cfg, dict(params, layers=[
            params["layers"][0], bad, params["layers"][2]]))
    with pytest.raises(ValueError, match="experts_held"):
        pangu.PanguDecoder(pangu.PanguConfig.from_dict(
            dict(CFG, experts_held=[12, 6])), params)
