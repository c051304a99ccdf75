"""`ops/ssm.py`: the chunked scan over any split of a sequence against the
one-token step token by token against a plain sequential scan, the pallas
kernel (interpret) against its XLA expression, what a step leaves of an
inactive slot, the convolution's tail, and the kernel's name; and the two
changes to shared ops that came with the family — grouped heads in
`mx_paged_decode`, and `ops/moe.py`'s score bias, ungated experts and tile
rule."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.ops import moe, paged_attention, ssm
from incubator_mxnet_tpu.telemetry import registry

H, P, G, N, K = 8, 16, 2, 128, 4


def draw(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def sequence(rng, t):
    """``(x, B, C, dt, A, D, state0)`` of a `t`-token sequence."""
    return (draw(rng, t, H, P), draw(rng, t, G, N), draw(rng, t, G, N),
            jax.nn.softplus(draw(rng, t, H) - 2.0), -jnp.exp(draw(rng, H)),
            draw(rng, H), draw(rng, H, P, N))


def plain_scan(x, B, C, dt, A, D, state):
    """The definition, a token at a time, in float64 numpy."""
    x, B, C, dt, A, D, state = (onp.asarray(a, onp.float64)
                                for a in (x, B, C, dt, A, D, state))
    rep = H // G
    ys = []
    for t in range(x.shape[0]):
        Bh, Ch = onp.repeat(B[t], rep, 0), onp.repeat(C[t], rep, 0)
        state = onp.exp(dt[t] * A)[:, None, None] * state \
            + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
        ys.append((state * Ch[:, None, :]).sum(-1) + D[:, None] * x[t])
    return onp.stack(ys), state


@pytest.mark.parametrize("splits", [(40,), (16, 16, 8), (32, 8), (3, 37),
                                    (8, 8, 8, 8, 8)],
                         ids=lambda s: "-".join(map(str, s)))
def test_chunk_over_any_split_is_decode_token_by_token_is_the_scan(splits):
    """Each part padded to whole blocks of 8 with rows that are not valid
    (their values are junk, not zeros): the state goes from part to part."""
    rng = onp.random.default_rng(0)
    x, B, C, dt, A, D, s0 = sequence(rng, 40)
    want_y, want_s = plain_scan(x, B, C, dt, A, D, s0)
    state, ys = ssm.pack_state(s0, G)[None], []
    for t in range(40):
        y, state = ssm.ssm_decode(state, x[t][None], B[t][None], C[t][None],
                                  dt[t][None], A, D, jnp.array([True]),
                                  impl="xla")
        ys.append(y[0])
    onp.testing.assert_allclose(onp.stack(ys), want_y, atol=2e-4)
    onp.testing.assert_allclose(ssm.unpack_state(state[0], P), want_s,
                                atol=2e-5)
    state, out, at = ssm.pack_state(s0, G), [], 0
    for n in splits:
        rows = -(-(n + 5) // 8) * 8

        def part(a):
            return jnp.pad(a[at:at + n],
                           [(0, rows - n)] + [(0, 0)] * (a.ndim - 1),
                           constant_values=3.0)

        y, state = ssm.ssm_chunk(state, part(x), part(B), part(C), part(dt),
                                 A, D, jnp.arange(rows) < n, block=8)
        out.append(y[:n])
        at += n
    onp.testing.assert_allclose(jnp.concatenate(out), want_y, atol=2e-4)
    onp.testing.assert_allclose(ssm.unpack_state(state, P), want_s,
                                atol=2e-5)


def test_a_chunk_shorter_than_a_block_is_one_block_and_others_are_whole():
    rng = onp.random.default_rng(1)
    x, B, C, dt, A, D, s0 = sequence(rng, 4)
    y, s = ssm.ssm_chunk(ssm.pack_state(s0, G), x, B, C, dt, A, D,
                         jnp.ones(4, bool), block=128)
    want_y, want_s = plain_scan(x, B, C, dt, A, D, s0)
    onp.testing.assert_allclose(y, want_y, atol=1e-4)
    onp.testing.assert_allclose(ssm.unpack_state(s, P), want_s, atol=1e-5)
    x, B, C, dt, A, D, s0 = sequence(rng, 12)
    with pytest.raises(ValueError, match="whole blocks"):
        ssm.ssm_chunk(ssm.pack_state(s0, G), x, B, C, dt, A, D,
                      jnp.ones(12, bool), block=8)


@pytest.mark.parametrize("heads,p,groups,shape", [
    (128, 64, 8, (64, 128, 128)), (8, 16, 2, (2, 128, 64)),
    (8, 8, 2, (2, 128, 32)), (4, 256, 2, (4, 128, 256))],
    ids=["published", "tests", "tiny", "wide-head"])
def test_the_state_as_stored_is_a_relabelling(heads, p, groups, shape):
    """Heads of one group side by side in a row's lanes, as many as fit
    128; packing and unpacking are each other's inverse."""
    assert ssm.state_store_shape(heads, p, 128, groups) == shape
    state = draw(onp.random.default_rng(8), 2, heads, p, 128)
    stored = ssm.pack_state(state, groups)
    assert stored.shape == (2,) + shape
    assert onp.array_equal(ssm.unpack_state(stored, p), state)
    k = shape[-1] // p
    assert onp.array_equal(stored[1, 0, 5, p * (k - 1):],
                           state[1, k - 1, :, 5])


def test_decode_kernel_is_its_xla_expression_and_spares_inactive_slots():
    rng = onp.random.default_rng(2)
    S = 4
    state = ssm.pack_state(draw(rng, S, H, P, N), G)
    args = (draw(rng, S, H, P), draw(rng, S, G, N), draw(rng, S, G, N),
            jax.nn.softplus(draw(rng, S, H)), -jnp.exp(draw(rng, H)),
            draw(rng, H))
    active = jnp.array([True, False, True, False])
    ya, sa = ssm.ssm_decode(state, *args, active, impl="xla")
    yb, sb = ssm.ssm_decode(state, *args, active, impl="pallas")
    on = onp.asarray(active)
    onp.testing.assert_allclose(onp.asarray(ya)[on], onp.asarray(yb)[on],
                                atol=1e-4)
    onp.testing.assert_allclose(sa, sb, atol=1e-5)
    for got in (sa, sb):            # bit for bit what they were
        assert onp.array_equal(onp.asarray(got)[~on], onp.asarray(state)[~on])
        assert not onp.array_equal(onp.asarray(got)[on],
                                   onp.asarray(state)[on])


def test_decode_op_counts_its_choice_and_names_its_kernel():
    registry.reset()
    rng = onp.random.default_rng(3)
    state = ssm.pack_state(draw(rng, 2, H, P, N), G)
    args = (draw(rng, 2, H, P), draw(rng, 2, G, N), draw(rng, 2, G, N),
            jax.nn.softplus(draw(rng, 2, H)), -jnp.exp(draw(rng, H)),
            draw(rng, H), jnp.ones(2, bool))
    ssm.ssm_decode(state, *args)
    rep = registry.report()
    assert rep['mx_kernel_dispatch_total{impl="xla",op="ssm_decode"}'][
        "value"] == 1
    sds = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (state,) + args]
    text = jax.jit(lambda *a: ssm._pallas_decode(*a, False)).trace(
        *sds).lower(lowering_platforms=("tpu",)).as_text()
    assert ssm.KERNEL_NAME == "mx_ssm_decode" and ssm.KERNEL_NAME in text


@pytest.mark.parametrize("splits", [(11,), (2, 1, 8), (1, 1, 1, 8)],
                         ids=lambda s: "-".join(map(str, s)))
def test_conv_tail_carries_over_any_split_and_through_decode(splits):
    """A chunk's tail is the last K - 1 rows before its length, from the
    tail handed in where the chunk is shorter; a decode step shifts it."""
    rng = onp.random.default_rng(4)
    C = 24
    rows, w, b = draw(rng, 11, C), draw(rng, K, C), draw(rng, C)
    ext = onp.concatenate([onp.zeros((K - 1, C)), onp.asarray(rows)])
    want = onp.asarray(b) + sum(
        onp.asarray(w)[j] * ext[j:j + 11] for j in range(K))
    tail, out, at = jnp.zeros((K - 1, C)), [], 0
    for n in splits:
        padded = jnp.pad(rows[at:at + n], ((0, 8 - n % 8), (0, 0)),
                         constant_values=7.0)
        y, tail = ssm.conv_chunk(tail, padded, w, b, n)
        out.append(y[:n])
        at += n
    onp.testing.assert_allclose(jnp.concatenate(out), want, atol=1e-5)
    onp.testing.assert_allclose(tail, rows[-(K - 1):], atol=0)
    # one more row by the decode form, for an active and an inactive slot
    new = draw(rng, 2, C)
    y, tails = ssm.conv_decode(jnp.stack([tail, tail]), new, w, b,
                               jnp.array([True, False]))
    ext = onp.concatenate([onp.asarray(rows)[-(K - 1):], onp.asarray(new[:1])])
    onp.testing.assert_allclose(
        y[0], onp.asarray(b) + (onp.asarray(w) * ext).sum(0), atol=1e-5)
    onp.testing.assert_allclose(tails[0], ext[1:], atol=0)
    assert onp.array_equal(tails[1], tail)


# -- grouped heads in the paged decode kernel ---------------------------------

@pytest.mark.parametrize("hq,hk,d,dtype", [
    (8, 2, 128, jnp.float32), (4, 2, 64, jnp.float32),
    (2, 2, 64, jnp.float32), (32, 2, 128, jnp.bfloat16)],
    ids=["8over2x128", "4over2x64", "ungrouped", "32over2x128-bf16"])
def test_paged_decode_grouped_heads_kernel_is_xla_is_plain_attention(
        hq, hk, d, dtype):
    """The last form is the cell's own (16 query heads a stored head of 128,
    bfloat16): the kernel's two products go to the MXU there, the weights
    handed over in bfloat16 as the XLA expression hands them; the float32
    forms keep the VPU's exact products."""
    rng = onp.random.default_rng(5)
    S, pages, pt = 3, 4, 8
    n_pages = S * pages + 1
    k, v, q = (draw(rng, *shape).astype(dtype) for shape in (
        (n_pages, hk, pt, d), (n_pages, hk, pt, d), (S, hq, d)))
    table = jnp.asarray(1 + onp.arange(S * pages).reshape(S, pages),
                        jnp.int32)
    lengths = jnp.asarray([pt * pages, 11, 0], jnp.int32)
    kp, vp = paged_attention.pack_pages(k), paged_attention.pack_pages(v)
    assert paged_attention._on_mxu(q, kp) is (dtype == jnp.bfloat16)
    tol = 8e-3 if dtype == jnp.bfloat16 else 2e-5
    a, b = (onp.asarray(x, onp.float32) for x in (
        paged_attention._xla_paged_decode(q, kp, vp, table, lengths, None,
                                          None),
        paged_attention._pallas_paged_decode(q, kp, vp, table, lengths,
                                             True)))
    onp.testing.assert_allclose(a, b, atol=tol)
    k, v, q = (onp.asarray(x, onp.float32) for x in (k, v, q))
    rep = hq // hk
    for s, n in enumerate(onp.asarray(lengths)):
        if not n:
            assert not a[s].any() and not b[s].any()
            continue
        ks = onp.concatenate([k[p] for p in table[s]], 1)[:, :n]
        vs = onp.concatenate([v[p] for p in table[s]], 1)[:, :n]
        for h in range(hq):
            sc = ks[h // rep] @ q[s, h] / onp.sqrt(d)
            w = onp.exp(sc - sc.max())
            for got in (a, b):
                onp.testing.assert_allclose(
                    got[s, h], (w / w.sum()) @ vs[h // rep], atol=tol)


# -- ops/moe.py: the score bias, ungated experts, the tile rule ---------------

def test_route_without_a_bias_is_bit_identical_and_a_bias_moves_the_choice():
    rng = onp.random.default_rng(6)
    u, w = draw(rng, 40, 32), draw(rng, 32, 64)
    ids, wt = moe.route(u, w, 6, 2.5)
    # today's arithmetic, written out
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.matmul(u, w))
    chosen, want_ids = jax.lax.top_k(s, 6)
    want = 2.5 * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    assert onp.array_equal(ids, want_ids) and onp.array_equal(wt, want)
    zero_ids, zero_w = moe.route(u, w, 6, 2.5, bias=jnp.zeros(64))
    assert onp.array_equal(zero_ids, ids) and onp.array_equal(zero_w, wt)
    bias = jnp.zeros(64).at[7].set(10.0)        # expert 7 always chosen
    b_ids, b_w = moe.route(u, w, 6, 2.5, bias=bias)
    assert (onp.asarray(b_ids) == 7).any(-1).all()
    # ... and its weight is from its score, not from score + bias
    at = onp.asarray(b_ids) == 7
    picked = onp.take_along_axis(onp.asarray(s), onp.asarray(b_ids), -1)
    onp.testing.assert_allclose(
        onp.asarray(b_w)[at],
        (2.5 * picked / picked.sum(-1, keepdims=True))[at], rtol=1e-6)
    onp.testing.assert_allclose(onp.asarray(b_w).sum(-1), 2.5, atol=1e-5)


@pytest.mark.parametrize("pairs,routed,tile", [
    (512, 256, 16), (1024, 256, 128), (4096, 256, 128),      # 16 of 256
    (1408, 512, 16), (2816, 512, 128), (11264, 512, 128)],   # 128 of 512
    ids=lambda v: str(v))
def test_tile_rule_follows_the_rows_an_expert_can_expect(pairs, routed, tile):
    assert moe._tile_rows(pairs, routed) == tile


def _plain_relu2(u, ids, w, ws, held, valid):
    out = onp.zeros((u.shape[0], ws[1].shape[-1]), onp.float64)
    u = onp.asarray(u, onp.float64)
    for t in range(u.shape[0]):
        for k in range(ids.shape[1]):
            e = int(ids[t, k]) - held[0]
            if valid[t] and 0 <= e < held[1]:
                h = onp.maximum(u[t] @ onp.asarray(ws[0][e], onp.float64), 0)
                out[t] += float(w[t, k]) * ((h * h) @ onp.asarray(ws[1][e]))
    return out


@pytest.mark.parametrize("t,routed", [(12, 32), (160, 32)],
                         ids=["tile-16", "tile-128"])
def test_ungated_experts_kernel_is_its_xla_expression_and_a_plain_loop(
        t, routed):
    rng = onp.random.default_rng(7)
    C, F, held = 16, 24, (4, 6)
    u = draw(rng, t, C)
    ids, w = moe.route(u, draw(rng, C, routed), 4, 5.0,
                       bias=draw(rng, routed) * 0.1)
    ws = (draw(rng, 6, C, F) * 0.2, draw(rng, 6, F, C) * 0.2)
    valid = jnp.arange(t) < t - 2
    ya, sa = moe.held_experts(u, ids, w, ws, held, valid, impl="xla",
                              routed=routed)
    yb, sb = moe.held_experts(u, ids, w, ws, held, valid, impl="pallas",
                              routed=routed)
    want = _plain_relu2(u, onp.asarray(ids), onp.asarray(w), ws, held,
                        onp.asarray(valid))
    onp.testing.assert_allclose(ya, want, atol=1e-4)
    onp.testing.assert_allclose(yb, want, atol=1e-4)
    assert list(sa) == list(sb) and int(sa[0]) > 0
