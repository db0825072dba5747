"""``ops/ssd.py``: Mamba-2's scan by chunks against the recurrence it
computes (a position at a time) and against the quadratic form ``(L o C
B^T)(dt xs)`` over the whole sequence, values and the gradient of every
operand; a T the chunk does not divide is padded; the state stays
float32 under bfloat16 operands, and the comparison is tight enough that
a bfloat16 state fails it; nothing loops over positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd

NAMES = ("xs", "dt", "A", "Bm", "Cm", "D")


def operands(seed=0, b=2, t=40, heads=4, p=8, groups=2, n=16,
             dtype=jnp.float32):
    """Seeded operands in the ranges the mixer hands over: step sizes of
    a softplus, ``A`` in ``-[1, 16]``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (b, t, heads, p), dtype),
            jax.nn.softplus(jax.random.normal(keys[1], (b, t, heads)) - 1),
            -jnp.exp(jax.random.uniform(keys[2], (heads,), maxval=2.77)),
            jax.random.normal(keys[3], (b, t, groups, n), dtype),
            jax.random.normal(keys[4], (b, t, groups, n), dtype),
            jax.random.normal(keys[5], (heads,)))


def recurrence(xs, dt, A, Bm, Cm, D):
    """The definition, a position at a time (``lax.scan``) with the
    state ``[B, H, P, N]`` in float32."""
    b, t, heads, p = xs.shape
    per = heads // Bm.shape[2]

    def step(h, at):
        x, d, b_t, c_t = at
        b_t, c_t = (jnp.repeat(u.astype(jnp.float32), per, axis=1)
                    for u in (b_t, c_t))                    # [B, H, N]
        x = x.astype(jnp.float32)
        h = (jnp.exp(d * A)[..., None, None] * h
             + (d[..., None] * x)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + D[:, None] * x

    _, y = jax.lax.scan(
        step, jnp.zeros((b, heads, p, Bm.shape[3]), jnp.float32),
        tuple(jnp.moveaxis(u, 1, 0) for u in (xs, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).astype(xs.dtype)


def quadratic(xs, dt, A, Bm, Cm, D):
    """``y = (L o (C B^T)) (dt xs) + D xs`` over the WHOLE sequence with
    ``L[i, j] = exp(sum_{j < k <= i} dt[k] A)`` for ``j <= i``: the
    scan's other closed form (no state, ``T^2`` pairs a head)."""
    t, per = xs.shape[1], xs.shape[2] // Bm.shape[2]
    cum = jnp.cumsum(dt * A, axis=1)                          # [B, T, H]
    decay = jnp.exp(jnp.where(
        (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[None, :, :, None],
        cum[:, :, None] - cum[:, None, :], -jnp.inf))         # [B, i, j, H]
    pairs = jnp.repeat(jnp.einsum("bign,bjgn->bijg", Cm, Bm), per, axis=-1)
    return (jnp.einsum("bijh,bjhp->bihp", decay * pairs,
                       dt[..., None] * xs) + D[:, None] * xs)


def weighed_sum(fn, chunk=None):
    """A scalar of ``fn``'s result that weighs every entry differently."""
    kwargs = {} if chunk is None else {"chunk": chunk}

    def loss(*ops):
        y = fn(*ops, **kwargs)
        w = np.random.RandomState(9).standard_normal(y.shape)
        return jnp.sum(y.astype(jnp.float32) * w.astype(np.float32))

    return loss


@pytest.mark.parametrize("chunk", [8, 16, 40, 128],
                         ids=["5-chunks", "padded-to-48", "one-chunk",
                              "chunk-cut-to-T"])
@pytest.mark.parametrize("other", [recurrence, quadratic],
                         ids=["recurrence", "quadratic"])
def test_values_and_every_gradient(chunk, other):
    """Chunks of 8 carry a state four times; 16 does not divide 40, so
    the sequence is padded with positions of ``dt`` 0 and cut back; one
    chunk of 40 has no pass across chunks; a chunk longer than T is cut
    to T."""
    ops = operands()
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd(*ops, chunk=chunk)
        want = other(*ops)
        got_g = jax.grad(weighed_sum(ssd.ssd, chunk), range(6))(*ops)
        want_g = jax.grad(weighed_sum(other), range(6))(*ops)
    assert got.shape == want.shape == ops[0].shape
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=3e-6 * scale, rtol=1e-5)
    for name, g, w in zip(NAMES, got_g, want_g):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))), rtol=1e-4,
            err_msg=name)


def test_a_sequence_the_chunk_does_not_divide_is_padded_not_refused():
    """T 37 under chunks of 8: five chunks, the last padded by three
    positions that pass the state on; the result has T's own length and
    is the recurrence's."""
    ops = operands(seed=3, t=37)
    got = ssd.ssd(*ops, chunk=8)
    assert got.shape == (2, 37, 4, 8)
    np.testing.assert_allclose(got, recurrence(*ops), atol=2e-4,
                               rtol=1e-4)


def test_groups_must_divide_heads():
    xs, dt, A, Bm, Cm, D = operands(groups=3, heads=4)
    with pytest.raises(ValueError, match="3 groups do not divide 4 heads"):
        ssd.ssd(xs, dt, A, Bm, Cm, D)


def _bfloat16_state(xs, dt, A, Bm, Cm, D):
    """The recurrence with its state rounded to bfloat16 a position."""
    per = xs.shape[2] // Bm.shape[2]

    def step(h, at):
        x, d, b_t, c_t = at
        b_t, c_t = (jnp.repeat(u, per, axis=1) for u in (b_t, c_t))
        h = (jnp.exp(d * A)[..., None, None] * h.astype(jnp.float32)
             + (d[..., None] * x)[..., None] * b_t[:, :, None, :]).astype(
                 jnp.bfloat16)
        return h, (jnp.einsum("bhpn,bhn->bhp", h.astype(jnp.float32), c_t)
                   + D[:, None] * x)

    _, y = jax.lax.scan(
        step, jnp.zeros(xs.shape[:1] + xs.shape[2:] + Bm.shape[3:],
                        jnp.bfloat16),
        tuple(jnp.moveaxis(u, 1, 0) for u in (xs, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def test_the_state_is_float32_and_a_bfloat16_state_fails():
    """Under bfloat16 operands the states every chunk is entered with
    are float32 (read off the function that carries them); and at
    float32 operands the comparison above would see a state kept in
    bfloat16: such a recurrence is off by three hundred times what the
    chunked scan is."""
    xs, dt, A, Bm, Cm, D = operands(dtype=jnp.bfloat16)
    entered, cum = jax.eval_shape(
        lambda *ops: ssd.entry_states(*ops, 8), xs, dt, A, Bm)
    assert entered.dtype == cum.dtype == jnp.float32
    assert entered.shape == (2, 5, 4, 8, 16)
    assert ssd.ssd(xs, dt, A, Bm, Cm, D, chunk=8).dtype == jnp.bfloat16
    ops = operands()
    want = recurrence(*ops)
    scale = float(jnp.max(jnp.abs(want)))
    ours = float(jnp.max(jnp.abs(ssd.ssd(*ops, chunk=8) - want))) / scale
    rounded = float(jnp.max(jnp.abs(_bfloat16_state(*ops) - want))) / scale
    assert ours < 3e-6 < 1e-3 < rounded


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    """Products with bfloat16 operands and float32 sums: off the float32
    recurrence of the same (rounded) operands by rounding alone."""
    ops = operands(dtype=jnp.bfloat16)
    got = ssd.ssd(*ops, chunk=8).astype(jnp.float32)
    want = recurrence(*(u.astype(jnp.float32) for u in ops))
    assert float(jnp.max(jnp.abs(got - want))) < 0.02 * float(
        jnp.max(jnp.abs(want)))


def test_no_loop_forward_or_backward():
    """The program of the value and of all six gradients holds no
    ``while``: no loop over positions and none over chunks (ROADMAP's
    lesson of PR 46)."""
    ops = operands()
    text = jax.jit(jax.value_and_grad(weighed_sum(ssd.ssd, 8), range(6))
                   ).lower(*ops).compile().as_text()
    assert " while(" not in text
    looped = jax.jit(weighed_sum(recurrence)).lower(
        *ops).compile().as_text()
    assert " while(" in looped  # the check can see one


def test_kept_under_a_checkpoint_by_name():
    """Under ``save_only_these_names(*SAVED_NAMES)`` the result is a
    residual and the gradients are those of the plain call;
    ``saved_bytes`` is that array's size."""
    ops = operands()
    kept = jax.checkpoint(
        weighed_sum(ssd.ssd, 8),
        policy=jax.checkpoint_policies.save_only_these_names(
            *ssd.SAVED_NAMES))
    plain = jax.grad(weighed_sum(ssd.ssd, 8), range(6))(*ops)
    for g, w in zip(jax.grad(kept, range(6))(*ops), plain):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    from jax._src.ad_checkpoint import saved_residuals

    shapes = [aval.shape for aval, why in saved_residuals(kept, *ops)
              if "from the argument" not in why]
    assert shapes == [(2, 40, 4, 8)]
    assert ssd.saved_bytes(2, 40, 4, 8, jnp.float32) == {
        ssd.SAVED_Y: 2 * 40 * 4 * 8 * 4}
    assert ssd.saved_bytes(2, 8192, 64, 64, jnp.bfloat16)[
        ssd.SAVED_Y] == 134_217_728
