"""Softmax attention linearised by chunk (``ops/chunk_attention.py``) and
the third shape of key set of the flash kernels (``stairs``), in
interpret mode at small sizes: the two kernel calls joined by their
``lse`` against a dense masked softmax over the joined keys, values and
all five gradients; what a query may and may not read; the pooling
against its reshape-and-softmax form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.chunk_attention import (SAVED_NAMES, allowed_keys,
                                             chunk_summary_attention,
                                             pool_chunks,
                                             reference_chunk_summary_attention)
from horovod_tpu.ops.pallas.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import reference_attention

B, H, D = 2, 2, 8


def operands(t, chunk, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (B, t, H, D)) for key in keys[:3])
    kt, vt = (jax.random.normal(key, (B, t // chunk, H, D))
              for key in keys[3:])
    return q, k, v, kt, vt


def dense(q, k, v, kt, vt, window, chunk):
    """Written out here: one softmax a query over the positions of its
    own window up to itself and the summaries of the windows before."""
    t = q.shape[1]
    out = np.zeros(q.shape, np.float32)
    q, k, v, kt, vt = (np.asarray(u, np.float64) for u in (q, k, v, kt, vt))
    for i in range(t):
        start = i // window * window
        keys = np.concatenate([k[:, start:i + 1], kt[:, :start // chunk]], 1)
        values = np.concatenate([v[:, start:i + 1], vt[:, :start // chunk]],
                                1)
        s = np.einsum("bhd,bkhd->bhk", q[:, i], keys) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, i] = np.einsum("bhk,bkhd->bhd", p, values)
    return out


@pytest.mark.parametrize("t,window,chunk", [
    (32, 32, 4), (96, 32, 4), (64, 16, 4), (48, 16, 16), (24, 32, 4)],
    ids=["one_window", "three_windows", "window_of_4_chunks",
         "chunk_is_window", "under_one_window"])
def test_values_and_five_gradients_against_a_dense_masked_softmax(
        t, window, chunk):
    args = operands(t, chunk)
    weights = jax.random.normal(jax.random.PRNGKey(9), (B, t, H, D))

    def both(fn):
        def scalar(*a):
            return jnp.sum(fn(*a, window=window, chunk=chunk) * weights)
        return fn(*args, window=window, chunk=chunk), jax.grad(
            scalar, range(5))(*args)

    got, got_grads = both(chunk_summary_attention)
    want, want_grads = both(reference_chunk_summary_attention)
    np.testing.assert_allclose(got, dense(*args, window, chunk), atol=2e-6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-6)
    if t <= window:
        # no summary is visible: plain causal attention, and no gradient
        # reaches a summary
        np.testing.assert_allclose(
            got, reference_attention(*args[:3], causal=True), atol=2e-6)
        assert not np.any(got_grads[3]) and not np.any(got_grads[4])
    else:
        # the last window's summaries are read by no query
        last = (t - window) // chunk
        assert not np.any(got_grads[3][:, last:])
        assert np.all(np.any(np.asarray(got_grads[3][:, :last]) != 0, -1))


def test_allowed_keys_counts_the_pairs_by_hand():
    """3 windows of 16 in chunks of 4: a window's queries read 1 .. 16
    positions and 0, 4 or 8 summaries."""
    allowed = np.asarray(allowed_keys(48, 16, 4))
    assert allowed.shape == (48, 48 + 12)
    assert allowed[:, :48].sum() == 3 * 16 * 17 // 2
    assert allowed[:, 48:].sum() == 16 * 4 + 16 * 8
    assert not allowed[15, 48:].any() and allowed[16, 48:52].all()
    assert not allowed[16, :16].any() and not allowed[16, 52:].any()


def test_nothing_after_a_query_reaches_it_and_a_window_reads_itself_exactly():
    """Through the pooling: a change at position ``j`` moves no output
    before ``j``; it moves the queries of ``j``'s own window from ``j``
    on through the exact part alone (the window's summaries are hidden
    from it: changing them moves nothing there) and every later window
    through the summaries alone."""
    t, window, chunk = 64, 16, 4
    q, k, v, _, _ = operands(t, chunk, seed=1)
    phi, mu = (jax.random.normal(key, (H, D))
               for key in jax.random.split(jax.random.PRNGKey(2)))

    def layer(k, v, bump=None):
        kt, vt = pool_chunks(k, v, phi, mu, chunk, D ** -0.5)
        if bump is not None:
            kt, vt = kt.at[:, bump].add(1.0), vt.at[:, bump].add(1.0)
        return np.asarray(chunk_summary_attention(
            q, k, v, kt, vt, window=window, chunk=chunk))

    base = layer(k, v)
    j = 21                                  # window 1, chunk 5
    moved = np.abs(layer(k.at[:, j].add(1.0), v.at[:, j].add(1.0))
                   - base).max((0, 2, 3)) > 1e-7
    assert not moved[:j].any() and moved[j:].all()
    # window 1's own summaries (chunks 4 .. 7): hidden up to its end
    for chunk_index in range(4, 8):
        moved = np.abs(layer(k, v, bump=chunk_index)
                       - base).max((0, 2, 3)) > 1e-7
        assert not moved[:32].any() and moved[32:].all()
    # and the exact part stops at the window's end: with the summaries
    # held, a change at j moves nothing past position 31
    kt, vt = pool_chunks(k, v, phi, mu, chunk, D ** -0.5)
    held = [np.asarray(chunk_summary_attention(
        q, kk, vv, kt, vt, window=window, chunk=chunk))
        for kk, vv in ((k, v), (k.at[:, j].add(1.0), v.at[:, j].add(1.0)))]
    moved = np.abs(held[1] - held[0]).max((0, 2, 3)) > 1e-7
    assert moved[j:32].all() and not moved[:j].any() and not moved[32:].any()


def test_pooling_against_its_reshape_and_softmax_form():
    t, chunk = 32, 4
    _, k, v, _, _ = operands(t, chunk, seed=3)
    phi, mu = (jax.random.normal(key, (H, D))
               for key in jax.random.split(jax.random.PRNGKey(4)))
    kt, vt = pool_chunks(k, v, phi, mu, chunk, D ** -0.5)
    k_c, v_c = (np.asarray(u).reshape(B, t // chunk, chunk, H, D)
                for u in (k, v))
    a = np.einsum("bcjhd,hd->bcjh", k_c, np.asarray(phi)) / np.sqrt(D)
    p = np.exp(a - a.max(2, keepdims=True))
    p /= p.sum(2, keepdims=True)
    np.testing.assert_allclose(
        kt, np.einsum("bcjh,bcjhd->bchd", p, k_c) + np.asarray(mu),
        atol=1e-6)
    np.testing.assert_allclose(
        vt, np.einsum("bcjh,bcjhd->bchd", p, v_c), atol=1e-6)
    # bfloat16 in, bfloat16 out, float32 in between
    kt16, _ = pool_chunks(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                          phi, mu, chunk, D ** -0.5)
    assert kt16.dtype == jnp.bfloat16
    np.testing.assert_allclose(kt16.astype(jnp.float32), kt, atol=0.05)


def test_the_summaries_carry_their_names_under_a_checkpoint():
    t, chunk = 32, 4
    _, k, v, _, _ = operands(t, chunk)
    phi = mu = jnp.ones((H, D))
    text = str(jax.make_jaxpr(
        lambda k, v: pool_chunks(k, v, phi, mu, chunk, 1.0))(k, v))
    for name in SAVED_NAMES:
        assert f"name={name}" in text


def test_stairs_alone_against_a_mask_and_the_first_stair_reads_nothing():
    """``flash_attention(stairs=(8, 2))`` over 12 keys: query i reads the
    keys ``j < 2 (i // 8)``; the first stair's rows are 0 and their
    ``lse`` the finite floor; blocks divide the steps whatever is asked."""
    t, t_kv = 32, 12
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, t, H, D))
    k, v = (jax.random.normal(key, (B, t_kv, H, D)) for key in keys[1:])
    out, lse = flash_attention(q, k, v, stairs=(8, 2), return_lse=True,
                               block_q=512, block_k=512)
    seen = (np.arange(t_kv)[None, :] < 2 * (np.arange(t)[:, None] // 8))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    s = np.where(seen, s, -np.inf)
    with np.errstate(invalid="ignore", divide="ignore"):
        top = np.where(seen.any(-1), s.max(-1), 0.0)[..., None]
        p = np.exp(s - top)
        total = p.sum(-1, keepdims=True)
        want = np.einsum("bhqk,bkhd->bqhd",
                         p / np.where(total > 0, total, 1.0), v)
        want_lse = (top + np.log(np.where(total > 0, total, 1.0)))[..., 0]
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse[:, :, 8:], want_lse[:, :, 8:], atol=2e-6)
    assert not np.any(out[:, :8]) and np.all(lse[:, :, :8] == -1e30)

    def scalar(q, k, v):
        return jnp.sum(flash_attention(q, k, v, stairs=(8, 2)) ** 2)

    dq, dk, dv = jax.grad(scalar, (0, 1, 2))(q, k, v)
    assert not np.any(dq[:, :8]) and np.all(np.isfinite(dq))
    # keys past the last stair's reach (j >= 6) are read by no query
    assert not np.any(dk[:, 6:]) and not np.any(dv[:, 6:])
    assert np.any(np.asarray(dk[:, :6]) != 0)


@pytest.mark.parametrize("kwargs", [
    dict(stairs=(8, 2), causal=True), dict(stairs=(8, 0)),
    dict(stairs=(0, 2))])
def test_stairs_are_a_key_set_of_their_own(kwargs):
    q = jnp.zeros((1, 16, 1, 8))
    with pytest.raises(ValueError, match="stairs"):
        flash_attention(q, q[:, :4], q[:, :4], **kwargs)


@pytest.mark.parametrize("t,window,chunk,summaries", [
    (40, 16, 4, 10), (48, 16, 4, 11), (32, 16, 5, 8)])
def test_a_sequence_that_is_no_whole_windows_is_refused_by_name(
        t, window, chunk, summaries):
    q = jnp.zeros((1, t, 1, 8))
    kt = jnp.zeros((1, summaries, 1, 8))
    for fn in (chunk_summary_attention, reference_chunk_summary_attention):
        with pytest.raises(ValueError, match="chunk_summary_attention"):
            fn(q, q, q, kt, kt, window=window, chunk=chunk)
