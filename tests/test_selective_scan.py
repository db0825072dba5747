"""``horovod_tpu/ops/selective_scan.py``: the scan's two kernels (in
Pallas interpret mode here) against the recurrence stepped one position
at a time (values and all six gradients), at block lengths that do and do
not divide T and that the wrapper has to round, at a T and a ``d`` no
block divides, under bfloat16 activations, with a state that has to
survive a block's edge and must not reach the next sequence, in float32
state whatever the activations are, and what it names for a checkpoint's
policy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.selective_scan import (SAVED_NAMES, SAVED_STATES,
                                            SAVED_Y, saved_bytes,
                                            selective_scan)

BATCH, T, CHANNELS, STATE = 2, 24, 8, 4
NAMES = ("c", "delta", "A", "B", "C", "D")


def operands(seed=0, t=T, dtype=jnp.float32, channels=CHANNELS):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(keys[0], (BATCH, t, channels))
    delta = jax.nn.softplus(jax.random.normal(keys[1], (BATCH, t, channels)))
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (channels, STATE)))
    b = jax.random.normal(keys[3], (BATCH, t, STATE))
    c2 = jax.random.normal(keys[4], (BATCH, t, STATE))
    d = jax.random.normal(keys[5], (channels,))
    return (c.astype(dtype), delta, a, b.astype(dtype), c2.astype(dtype), d)


def stepped(c, delta, a, b, c2, d):
    """The recurrence as written: a state ``[B, d, N]``, one position at
    a time."""
    def step(h, x):
        c_t, delta_t, b_t, c2_t = x
        h = (jnp.exp(delta_t[..., None] * a) * h
             + (delta_t * c_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c2_t) + d * c_t

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (c, delta, b, c2))
    _, y = jax.lax.scan(step, jnp.zeros((c.shape[0],) + a.shape), xs)
    return jnp.moveaxis(y, 0, 1)


CASES = {
    # 5 does not divide 24 and is no whole tile of [T, d] (the wrapper
    # rounds it to 8), 8 does, 64 is longer than the sequence
    "chunk1": dict(chunk=1), "chunk5": dict(chunk=5),
    "chunk8": dict(chunk=8), "chunk24": dict(chunk=24),
    "chunk64": dict(chunk=64),
    # a T no block divides: the tail is padded with positions of delta 0
    "t27": dict(chunk=8, t=27),
    # a d no channel block divides: two blocks, the second six channels
    "d1030": dict(chunk=8, channels=1030),
    # bfloat16 c, B, C and cotangent beside a float32 delta
    "bfloat16": dict(chunk=16, t=48, dtype=jnp.bfloat16),
    # a long memory: the first sequence ends on a large state and a large
    # adjoint, and the second must start from none
    "two-sequences": dict(chunk=8, slow=0.02),
}


@pytest.mark.parametrize("case", list(CASES.values()), ids=list(CASES))
def test_values_and_all_six_gradients_against_position_by_position(case):
    chunk, t = case["chunk"], case.get("t", T)
    dtype = case.get("dtype", jnp.float32)
    args = operands(t=t, dtype=dtype, channels=case.get("channels", CHANNELS))
    args = (args[0], case.get("slow", 1.0) * args[1]) + args[2:]
    # weights the activation dtype holds: the cotangent is the same number
    # on both sides
    weights = jax.random.normal(
        jax.random.PRNGKey(9), args[0].shape).astype(dtype).astype(
            jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weights)

    want, want_grads = jax.value_and_grad(loss(stepped), range(6))(*args)
    got, got_grads = jax.value_and_grad(loss(
        lambda *a: selective_scan(*a, chunk=chunk)),
        range(6))(*args)
    # a bfloat16 result is the float32 one rounded once
    rounded = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0
    y = selective_scan(*args, chunk=chunk)
    assert y.dtype == dtype
    np.testing.assert_allclose(
        y.astype(jnp.float32), stepped(*args), rtol=1e-5 + rounded,
        atol=1e-5 + rounded)
    np.testing.assert_allclose(got, want, rtol=1e-5 + rounded)
    for name, g, w in zip(NAMES, got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        in_bfloat16 = rounded if g.dtype == jnp.bfloat16 else 0.0
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32),
            rtol=2e-4 + in_bfloat16,
            atol=2e-5 + in_bfloat16 * float(jnp.max(jnp.abs(w))),
            err_msg=name)


def test_the_state_survives_a_chunks_edge():
    """One impulse at position 0 and a slow decay: every later position
    reads it, across five chunk edges, as ``exp(t delta A)``."""
    t, decay = 24, 0.05
    c = jnp.zeros((1, t, 1)).at[0, 0, 0].set(1.0)
    delta = jnp.ones((1, t, 1))
    ones = jnp.ones((1, t, 1))
    y = selective_scan(c, delta, jnp.full((1, 1), -decay), ones, ones,
                       jnp.zeros((1,)), chunk=4)
    np.testing.assert_allclose(y[0, :, 0], np.exp(-decay * np.arange(t)),
                               rtol=1e-5)


def test_state_and_sums_are_float32_under_bfloat16_activations():
    """bfloat16 ``c``, ``B``, ``C`` go in as they are and ``y`` comes
    back in bfloat16, but the recurrence runs in float32: against the
    float32 recurrence on the same rounded operands it is off by the
    rounding of ``y`` alone, where a bfloat16 state would drift."""
    args = operands(seed=1, t=512, dtype=jnp.bfloat16)
    slow = (args[0], 0.02 * args[1]) + args[2:]   # a long memory
    got = selective_scan(*slow, chunk=64)
    assert got.dtype == jnp.bfloat16
    want = stepped(*slow)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < (
        2 ** -8 * scale)
    grads = jax.grad(lambda *a: jnp.sum(selective_scan(*a).astype(
        jnp.float32)), range(6))(*slow)
    assert [g.dtype for g in grads] == [a.dtype for a in slow]


def test_a_policy_that_keeps_the_two_names_drops_the_forward_recurrence():
    """Under ``jax.checkpoint`` with ``save_only_these_names``: the
    backward pass is handed ``y`` and the chunks' entry states, at the
    bytes ``saved_bytes`` gives, and no ``[T, B, N, d]`` of states."""
    from jax._src.ad_checkpoint import saved_residuals

    args = operands()

    def loss(*a):
        return jnp.sum(selective_scan(*a, chunk=8) ** 2)

    kept = jax.checkpoint(loss, policy=jax.checkpoint_policies
                          .save_only_these_names(*SAVED_NAMES))
    shapes = [aval.shape for aval, why in saved_residuals(kept, *args)
              if "from the argument" not in why]
    assert sorted(shapes) == sorted([(BATCH, T, CHANNELS),
                                     (T // 8, BATCH, STATE, CHANNELS)])
    assert saved_bytes(BATCH, T, CHANNELS, STATE, jnp.float32, chunk=8) == {
        SAVED_Y: BATCH * T * CHANNELS * 4,
        SAVED_STATES: (T // 8) * BATCH * STATE * CHANNELS * 4}
    np.testing.assert_allclose(
        jax.grad(kept)(*args), jax.grad(loss)(*args), rtol=1e-6)
