"""A sliding window and grouped key-value heads in the flash kernels
(interpret mode) and in ``reference_attention``, against a dense masked
softmax written out here: forward and all three gradients; the loop
bounds visit the blocks that hold an allowed pair and no other; without
a window and with equal head counts the kernels are the ones from
before."""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import (reference_attention,
                                                 ring_attention)

fa = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")

T, BLOCK = 64, 16


def dense(q, k, v, window=None):
    """Causal softmax attention, every mask an explicit ``where``; k and
    v repeated to q's heads."""
    t, h = q.shape[1:3]
    group = h // k.shape[2]
    k, v = (jnp.repeat(u, group, axis=2) for u in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    allowed = behind >= 0
    if window is not None:
        allowed = allowed & (behind < window)
    s = jnp.where(allowed, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def inputs(heads, kv_heads, d_qk=8, d_v=8, t=T, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (2, t, heads, d_qk)),
            jax.random.normal(keys[1], (2, t, kv_heads, d_qk)),
            jax.random.normal(keys[2], (2, t, kv_heads, d_v)),
            jax.random.normal(keys[3], (2, t, heads, d_v)))


def value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v) * w), (0, 1, 2))(q, k, v)


def flash(window):
    return lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=BLOCK, block_k=BLOCK)


def reference(window):
    return lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=window)


# smaller than a block, a block, between one and two, two blocks, T
# (every key before the query), larger than T, and none
WINDOWS = [1, 7, BLOCK, BLOCK + 8, 2 * BLOCK, T, T + 36, None]
# query heads over key-value heads: groups of 1, 6 and 9 (Laguna's)
HEADS = [(2, 2), (6, 1), (9, 1), (12, 2)]


@pytest.mark.parametrize("make", [flash, reference],
                         ids=["flash", "reference"])
@pytest.mark.parametrize("heads,kv_heads", HEADS,
                         ids=[f"{h}over{g}" for h, g in HEADS])
@pytest.mark.parametrize("window", WINDOWS, ids=[f"w{w}" for w in WINDOWS])
def test_window_and_grouped_heads_against_a_dense_masked_softmax(
        make, heads, kv_heads, window):
    q, k, v, w = inputs(heads, kv_heads)
    want, want_grads = value_and_grads(
        lambda q, k, v: dense(q, k, v, window), q, k, v, w)
    fn = make(window)
    np.testing.assert_allclose(fn(q, k, v), dense(q, k, v, window),
                               rtol=1e-5, atol=2e-6)
    got, got_grads = value_and_grads(fn, q, k, v, w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, wg in zip(got_grads, want_grads):
        # dk and dv come back with the key-value heads' count: the sum
        # over a group's query heads
        assert g.shape == wg.shape
        np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (6, 2)],
                         ids=["4over4", "6over2"])
def test_two_widths_without_a_window_and_with_one(heads, kv_heads):
    """Latent attention's shape (score heads wider than value heads)
    still passes, grouped or not, and so does a window over it."""
    q, k, v, w = inputs(heads, kv_heads, d_qk=24, d_v=16)
    for window in (None, 24):
        want, want_grads = value_and_grads(
            lambda q, k, v: dense(q, k, v, window), q, k, v, w)
        got, got_grads = value_and_grads(flash(window), q, k, v, w)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        for g, wg in zip(got_grads, want_grads):
            np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [None, 24], ids=["full", "w24"])
def test_thirty_two_heads_of_64_over_eight(window):
    """Grouped heads of 64 (32 query heads over 8 key-value heads,
    groups of 4: ``lfm2_24b_a2b-spmd-1chip``'s full-attention layer; PR
    25 swept heads of 64 with as many key-value heads as query heads, PR
    38's index map ran at 128): forward and the three gradients, dk and
    dv the sums over their four query heads."""
    q, k, v, w = inputs(32, 8, d_qk=64, d_v=64, seed=3)
    want, want_grads = value_and_grads(
        lambda q, k, v: dense(q, k, v, window), q, k, v, w)
    np.testing.assert_allclose(flash(window)(q, k, v),
                               dense(q, k, v, window), rtol=1e-5, atol=2e-6)
    got, got_grads = value_and_grads(flash(window), q, k, v, w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, wg in zip(got_grads, want_grads):
        assert g.shape == wg.shape
        np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-5)
    assert got_grads[1].shape == (2, T, 8, 64)


@pytest.mark.parametrize("window", [None, 24], ids=["full", "w24"])
def test_twenty_heads_of_64_over_ten_with_values_of_128(window):
    """Values wider than keys (``d_qk`` 64, ``d_v`` 128; everything
    before ran equal widths or 192 over 128) with 20 query heads over 10
    key-value heads: one of the two calls of a differential-attention
    layer (``phi4_mini_flash-spmd-1chip``), with a window and without.
    Forward and the three gradients; dk ``[.., 10, 64]`` and dv ``[..,
    10, 128]`` are the sums over their two query heads."""
    q, k, v, w = inputs(20, 10, d_qk=64, d_v=128, seed=4)
    want, want_grads = value_and_grads(
        lambda q, k, v: dense(q, k, v, window), q, k, v, w)
    np.testing.assert_allclose(flash(window)(q, k, v),
                               dense(q, k, v, window), rtol=1e-5, atol=2e-6)
    got, got_grads = value_and_grads(flash(window), q, k, v, w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, wg in zip(got_grads, want_grads):
        assert g.shape == wg.shape
        np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-5)
    assert got_grads[1].shape == (2, T, 10, 64)
    assert got_grads[2].shape == (2, T, 10, 128)


def test_blocks_that_differ_and_the_lse():
    """block_q != block_k, and the logsumexp of a windowed row."""
    q, k, v, _ = inputs(6, 2, t=96)
    for bq, bk, window in ((32, 16, 40), (16, 32, 40), (32, 32, 33)):
        out, lse = flash_attention(q, k, v, causal=True, window=window,
                                   block_q=bq, block_k=bk, return_lse=True)
        np.testing.assert_allclose(out, dense(q, k, v, window), rtol=1e-5,
                                   atol=2e-6)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 3, 2)) / np.sqrt(8)
        behind = jnp.arange(96)[:, None] - jnp.arange(96)[None, :]
        s = jnp.where((behind >= 0) & (behind < window), s, -jnp.inf)
        np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1), rtol=1e-5)


def blocks_with_an_allowed_pair(t, bq, bk, window):
    """``{(iq, ik)}`` that hold a pair ``i - window < j <= i`` and, of
    those, the ones every pair of which is allowed, by counting."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    allowed = (j <= i) & (i - j < window)
    some, every = set(), set()
    for iq, ik in itertools.product(range(t // bq), range(t // bk)):
        tile = allowed[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
        if tile.any():
            some.add((iq, ik))
        if tile.all():
            every.add((iq, ik))
    return some, every


def visited(bounds):
    seen, unmasked = [], []
    for lo, hi, masked in bounds:
        blocks = list(range(int(lo), int(hi)))
        seen += blocks
        unmasked += [] if masked else blocks
    assert len(seen) == len(set(seen))  # no block twice
    return set(seen), set(unmasked)


@pytest.mark.parametrize("t,bq,bk,window", [
    (128, 16, 16, 16), (128, 16, 16, 1), (128, 16, 16, 17),
    (128, 16, 16, 40), (128, 16, 16, 128), (128, 16, 16, 500),
    (128, 32, 16, 24), (128, 16, 32, 24), (128, 32, 16, 48),
    (128, 16, 32, 64), (96, 32, 32, 33)])
def test_the_loop_bounds_visit_the_blocks_with_an_allowed_pair_only(
        t, bq, bk, window):
    """Forward (k blocks of a q block) and backward (q blocks of a k
    block): visited are exactly the blocks that hold an allowed pair,
    and run without a mask are only blocks every pair of which is
    allowed."""
    some, every = blocks_with_an_allowed_pair(t, bq, bk, window)
    for iq in range(t // bq):
        seen, unmasked = visited(fa._k_bounds(
            iq, causal=True, block_q=bq, block_k=bk, t_kv=t, window=window))
        assert seen == {ik for q, ik in some if q == iq}
        assert unmasked <= {ik for q, ik in every if q == iq}
    for ik in range(t // bk):
        seen, unmasked = visited(fa._q_bounds(
            ik, causal=True, block_q=bq, block_k=bk, nq=t // bq,
            window=window))
        assert seen == {iq for iq, k in some if k == ik}
        assert unmasked <= {iq for iq, k in every if k == ik}


def test_a_sliding_layer_visits_two_key_blocks_a_query_block():
    """At the cell's sizes (T 8192, blocks of 512, a window of 512) a
    query block visits at most 2 of up to 16 key blocks, both masked,
    where the causal bounds visit 1 to 16; a key block's backward visits
    at most 2 query blocks."""
    for iq in range(16):
        bounds = fa._k_bounds(iq, causal=True, block_q=512, block_k=512,
                              t_kv=8192, window=512)
        seen, unmasked = visited(bounds)
        assert seen == {iq - 1, iq} - {-1} and not unmasked
        causal, _ = visited(fa._k_bounds(
            iq, causal=True, block_q=512, block_k=512, t_kv=8192))
        assert causal == set(range(iq + 1))
        seen, unmasked = visited(fa._q_bounds(
            iq, causal=True, block_q=512, block_k=512, nq=16, window=512))
        assert seen == {iq, iq + 1} - {16} and not unmasked


def k_bounds_before(iq, *, causal, block_q, block_k, t_kv, window=None,
                    stairs=None):
    """``_k_bounds`` from before it knew a window, written out."""
    if not causal:
        return [(0, t_kv // block_k, False)]
    seen = jnp.minimum((iq + 1) * block_q + block_k - 1, t_kv) // block_k
    whole = jnp.minimum((iq * block_q + 1) // block_k, seen)
    return [(0, whole, False), (whole, seen, True)]


def q_bounds_before(ik, *, causal, block_q, block_k, nq, window=None,
                    stairs=None):
    """The backward's bounds from before, written out."""
    if not causal:
        return [(0, nq, False)]
    first = (ik * block_k) // block_q
    whole = jnp.clip(((ik + 1) * block_k + block_q - 2) // block_q,
                     first, nq)
    return [(first, whole, True), (whole, nq, False)]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_without_a_window_and_with_equal_heads_the_kernels_are_unchanged(
        causal, monkeypatch):
    """The traced kernels (the ``pallas_call``'s jaxpr: bounds, masks,
    grid, block shapes, index maps) are the ones the bounds from before
    give, equation for equation, and so are the results, bit for bit."""
    q, k, v, w = inputs(4, 4, t=128)

    def traced():
        return str(jax.make_jaxpr(lambda q, k, v: value_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=32, block_k=32,
                interpret=False), q, k, v, w))(q, k, v))

    def results():
        return value_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32), q, k, v, w)

    now, got = traced(), results()
    monkeypatch.setattr(fa, "_k_bounds", k_bounds_before)
    monkeypatch.setattr(fa, "_q_bounds", q_bounds_before)
    fa._fwd_once.clear_cache(), fa._bwd_once.clear_cache()
    try:
        before, want = traced(), results()
    finally:
        fa._fwd_once.clear_cache(), fa._bwd_once.clear_cache()
    assert now == before and "pallas_call" in now
    for g, wg in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, wg)


def test_what_a_window_and_a_group_need():
    q, k, v, _ = inputs(6, 4)
    with pytest.raises(ValueError, match="divide the query heads"):
        flash_attention(q, k, v, causal=True)
    q, k, v, _ = inputs(6, 2)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="causal=True"):
        reference_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, causal=True, window=0)
    # the sequence-parallel callers know no window: nothing is built
    with pytest.raises(TypeError, match="window"):
        ring_attention(q, k, v, axis_name="sp", causal=True, window=8)


def test_backward_vmem_at_the_cell_shapes():
    """What the backward call states at T 8192 and heads of 128 in
    bfloat16: q, dO and dq of the whole head double-buffered (3 x 2 x 2
    MiB), the head's float32 dq (4 MiB), and with grouped heads the
    float32 dk and dv of the whole key-value head (2 x 4 MiB): inside
    the v5e's 128 MiB."""
    plain = fa._bwd_vmem_bytes(8192, 128, 128, 512, 512, 2)
    grouped = fa._bwd_vmem_bytes(8192, 128, 128, 512, 512, 2, group=9)
    assert (12 + 4) << 20 < plain < 64 << 20
    assert grouped - plain == (8 << 20) + (8 << 20) // 4
    assert grouped < 128 << 20
