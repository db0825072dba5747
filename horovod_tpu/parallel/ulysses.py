"""Ulysses (DeepSpeed-style) sequence parallelism via all-to-all.

The second long-context strategy alongside ring attention (the reference
framework has neither — SURVEY §5).  Instead of rotating K/V blocks, two
``all_to_all`` collectives re-shard the activations: inbound, the layout
flips from sequence-sharded ``[B, T/P, H, D]`` to head-sharded
``[B, T, H/P, D]`` so each device computes *exact* full-sequence attention
on its subset of heads; outbound, the flip is reversed.  On TPU the
all-to-all is an XLA collective over ICI; total bytes moved are
``2 * B*T*H*D/P`` per direction — independent of sequence length per hop,
which favors Ulysses when H >= P and the attention kernel (e.g. the Pallas
flash kernel) wants the whole sequence locally.

Constraint: the head count must be divisible by the axis size (classic
Ulysses).  For H < P use ring attention instead.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel._compat import shard_map
from horovod_tpu.parallel.ring_attention import reference_attention


def seq_to_heads(x, axis_name):
    """[B, T/P, H, D] -> [B, T, H/P, D] via all_to_all over ``axis_name``."""
    # split the head dim across the axis, concat the sequence dim
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def heads_to_seq(x, axis_name):
    """[B, T, H/P, D] -> [B, T/P, H, D] — inverse of :func:`seq_to_heads`."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, *, axis_name, causal=False, scale=None,
                      attn_fn=None):
    """Exact attention with sequence-sharded inputs via head re-sharding.

    Runs inside ``shard_map``.  q/k/v per shard: ``[B, T/P, H, D]``; output
    has the same layout.  ``attn_fn(q, k, v, causal=..., scale=...)`` is the
    local full-sequence attention kernel (defaults to the dense reference;
    pass the Pallas flash kernel on real TPU).
    """
    if attn_fn is None:
        attn_fn = reference_attention
    h = q.shape[2]
    p_size = lax.axis_size(axis_name)
    if h % p_size != 0:
        raise ValueError(
            f"Ulysses needs heads ({h}) divisible by axis size ({p_size}); "
            "use ring_attention for few-head long-context models")
    qh = seq_to_heads(q, axis_name)
    kh = seq_to_heads(k, axis_name)
    vh = seq_to_heads(v, axis_name)
    oh = attn_fn(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(oh, axis_name)


def ulysses_self_attention(q, k, v, mesh, *, axis_name="sp", causal=False,
                           scale=None, attn_fn=None):
    """Global-array convenience wrapper (mirrors ``ring_self_attention``)."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, scale=scale, attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return fn(q, k, v)
