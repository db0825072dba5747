"""The rotary turn as one pass over whole heads (``turn`` in
``models/transformer.py``: ``x c + (x P) s``) against the plain form
``rope`` and ``rotate`` had before it, kept here: halves cut at a
column, turned and joined.  Eager on the CPU, so nothing is contracted
into a fused multiply-add on one side and not the other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (
    Rotary, _inv_freq, rope, rotary_operands, rotary_table, rotate, turn)


def plain_rope(x, theta=10000.0, pairs=False):
    t, d = x.shape[-3], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)        # [T, 1, D / 2]
    x32 = x.astype(jnp.float32)
    if pairs:
        x32 = x32.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = x32[..., 0], x32[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def plain_rotate(x, recipe):
    t, d = x.shape[-3], x.shape[-1]
    dim = int(d * recipe.fraction)
    half = dim // 2
    inv_freq, factor = rotary_table(recipe, dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., dim:]],
        axis=-1).astype(x.dtype)


# Laguna's full layers: YaRN over the first half of a head, with its factor
YARN = Rotary(theta=500000.0, fraction=0.5, factor=128.0, original_len=8192,
              attention_factor=1.4852)
WHOLE = Rotary(theta=1e6)
NOPE, ROPE = 128, 64  # latent attention's head: [128 that pass | 64 turned]


def latent_operands(x):
    return rotary_operands(x, _inv_freq(32e6, ROPE), start=NOPE, pairs=True)


# name: (head width, the plain form, the turn, its operands, turned columns)
RECIPES = {
    "whole-head-of-128": (
        128, plain_rope, rope,
        lambda x: rotary_operands(x, _inv_freq(10000.0, 128)), range(128)),
    "whole-head-of-64": (
        64, lambda x: plain_rotate(x, WHOLE), lambda x: rotate(x, WHOLE),
        lambda x: rotary_operands(x, *rotary_table(WHOLE, 64)), range(64)),
    "yarn-first-half-of-128": (
        128, lambda x: plain_rotate(x, YARN), lambda x: rotate(x, YARN),
        lambda x: rotary_operands(x, *rotary_table(YARN, 64)), range(64)),
    "neighbours-over-64": (
        64, lambda x: plain_rope(x, 32e6, pairs=True),
        lambda x: rope(x, 32e6, pairs=True),
        lambda x: rotary_operands(x, _inv_freq(32e6, 64), pairs=True),
        range(64)),
    "last-64-of-192": (
        NOPE + ROPE,
        lambda x: jnp.concatenate(
            [x[..., :NOPE], plain_rope(x[..., NOPE:], 32e6, pairs=True)],
            axis=-1),
        lambda x: turn(x, *latent_operands(x)), latent_operands,
        range(NOPE, NOPE + ROPE)),
}


def _draw(key, d, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), (2, 300, 3, d),
                             jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("recipe", RECIPES)
def test_turn_is_the_plain_rotation_bit_for_bit(recipe, dtype):
    """Forward bit for bit in both dtypes; the gradient bit for bit in
    bfloat16 and within one unit in the last place in float32."""
    d, plain, turned, _, _ = RECIPES[recipe]
    x, g = _draw(1, d, dtype), _draw(2, d, dtype)
    want, want_vjp = jax.vjp(plain, x)
    got, got_vjp = jax.vjp(turned, x)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    (dx,), (want_dx,) = got_vjp(g), want_vjp(g)
    assert dx.dtype == dtype
    dx, want_dx = (np.asarray(u, np.float32) for u in (dx, want_dx))
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(dx, want_dx)
    else:
        assert np.all(np.abs(dx - want_dx) <= np.spacing(np.abs(want_dx)))


@pytest.mark.parametrize("recipe", RECIPES)
def test_pairing_is_antisymmetric_with_one_entry_a_turned_column(recipe):
    """``P.T == -P`` with one +1 or -1 in each turned column and none in a
    column that passes, where ``c`` is 1 and ``s`` 0; ``s`` is the same
    on both columns of a pair: what makes the backward the same pass
    with ``-P``."""
    d, _, _, operands, turned = RECIPES[recipe]
    c, s, p = (np.asarray(u, np.float32)
               for u in operands(_draw(1, d, jnp.bfloat16)))
    assert c.shape == s.shape == (300, 1, d) and p.shape == (d, d)
    np.testing.assert_array_equal(p.T, -p)
    assert set(np.unique(p)) == {-1.0, 0.0, 1.0}
    passes = sorted(set(range(d)) - set(turned))
    np.testing.assert_array_equal(np.count_nonzero(p, axis=0)[list(turned)], 1)
    assert not p[:, passes].any() and not p[passes].any()
    np.testing.assert_array_equal(c[..., passes], 1.0)
    np.testing.assert_array_equal(s[..., passes], 0.0)
    # column j's partner is the row of its one entry
    np.testing.assert_array_equal(s, s[..., np.abs(p).argmax(axis=0)]
                                  * (np.abs(p).sum(axis=0) > 0))
