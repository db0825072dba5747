"""GPT-style decoder-only transformer — the long-context flagship model.

The reference ships only example CNNs (SURVEY §6); this model family is
what exercises the framework's TPU-first parallel subsystems together:

- **dp**: batch sharding + gradient psum (``DistributedOptimizer``)
- **tp**: weight shardings from
  :func:`horovod_tpu.parallel.tensor_parallel.transformer_sharding_rules`
  (module/param names here are chosen to match those rules)
- **sp**: attention is pluggable — dense, ring
  (:func:`~horovod_tpu.parallel.ring_attention.ring_attention`) or Ulysses
- **ep**: optional switch-MoE FFN layers
  (:func:`~horovod_tpu.parallel.moe.switch_moe`); the dropless top-k
  layer (:func:`~horovod_tpu.parallel.moe.topk_moe`) keeps all experts
  on one device for now
- **pp**: :class:`Block` is shape-preserving, so the block stack drops into
  ``horovod_tpu.parallel.pipeline.pipeline_apply`` unchanged

The block is described by data: a :class:`BlockSpec` on the
configuration says which norm, position scheme and feed-forward a block
has.  The default is the GPT-2 block (LayerNorm, learned positions,
GELU); ``BlockSpec(norm="rms", positions="rope", qk_norm=True,
ffn="moe_topk")`` is OLMoE's.

bfloat16 activations by default (MXU-native), fp32 layernorm/softmax.
"""

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.parallel.ring_attention import reference_attention


NORMS = ("layer", "rms")
POSITIONS = ("learned", "rope")
FFNS = ("gelu", "moe_switch", "moe_topk")


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What a block is made of.  ``norm``: ``"layer"`` (LayerNorm with
    a bias, the fused kernel on TPU) or ``"rms"`` (RMSNorm, scale
    only).  ``positions``: ``"learned"`` (a table added to the
    embedding) or ``"rope"`` (rotary, applied to q and k before the
    attention function; no table).  ``qk_norm``: a norm of the block's
    kind over the whole q and k projections, before the split into
    heads.  ``ffn``: ``"gelu"`` (dense up-GELU-down), ``"moe_switch"``
    (:func:`~horovod_tpu.parallel.moe.switch_moe`) or ``"moe_topk"``
    (:func:`~horovod_tpu.parallel.moe.topk_moe`)."""
    norm: str = "layer"
    positions: str = "learned"
    qk_norm: bool = False
    ffn: str = "gelu"

    def __post_init__(self):
        for value, known in ((self.norm, NORMS),
                             (self.positions, POSITIONS), (self.ffn, FFNS)):
            if value not in known:
                raise ValueError(f"BlockSpec: {value!r} is none of {known}")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    n_layers: int = 4
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    # attn_fn(q, k, v, causal=..., scale=...) — swap in ring/ulysses/pallas
    attn_fn: Optional[Callable] = None
    block: BlockSpec = BlockSpec()
    # every k-th block uses a switch-MoE FFN whatever ``block.ffn``
    # says (0 = every block as ``block`` has it)
    moe_every: int = 0
    n_experts: int = 8
    # sizes only some blocks read: a head's width (None: d_model /
    # n_heads), experts a token and an expert's width (None: d_ff) for
    # ``moe_topk``, the rotary base, the norms' epsilon
    head_dim: Optional[int] = None
    experts_per_token: int = 2
    d_expert: Optional[int] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # rematerialize each block's activations in backward (jax.checkpoint):
    # trades ~1/3 more FLOPs for O(layers) less activation HBM — the
    # lever for pushing per-chip batch (and usually MFU) once
    # activations, not weights, bound the batch size
    remat: bool = False


def default_attention():
    """The hot-path kernel: Pallas flash attention on TPU (O(T) memory,
    MXU-tiled blocks — ``ops/pallas/flash_attention.py``); the dense
    reference path elsewhere (interpret-mode Pallas on CPU is far slower
    than XLA's fused softmax for test-sized problems)."""
    if jax.default_backend() == "tpu":
        from horovod_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention
    return reference_attention


def rope(x, theta=10000.0):
    """Rotary position embedding of ``x [..., T, H, D]`` in the
    rotate-half form: ``x * cos + rotate_half(x) * sin`` with
    ``rotate_half([x1, x2]) = [-x2, x1]`` on the two halves of D and
    ``angle(t, i) = t * theta^(-2i / D)`` for i < D / 2, the same angles
    for both halves.  Computed in float32, returned in ``x.dtype``."""
    t, d = x.shape[-3], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)        # [T, 1, D / 2]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2, -1) + eps) * scale`` in float32, returned
    in ``x.dtype``.  Plain ``jax.numpy``: XLA fuses it."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        out = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (out * scale).astype(x.dtype)


def make_norm(cfg, name):
    """The norm ``cfg.block`` names."""
    cls = RMSNorm if cfg.block.norm == "rms" else FusedLayerNorm
    return cls(eps=cfg.norm_eps, name=name)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = cfg.n_heads
        d = cfg.head_dim or cfg.d_model // cfg.n_heads
        qkv = nn.DenseGeneral((3, h, d), use_bias=False, dtype=cfg.dtype,
                              name="qkv")(x)
        q, k, v = (qkv[..., i, :, :] for i in range(3))
        if cfg.block.qk_norm:
            with jax.named_scope("attn/qk_norm"):
                # over the whole projection, not per head
                q, k = (make_norm(cfg, name)(
                    u.reshape(u.shape[:-2] + (h * d,))).reshape(u.shape)
                    for u, name in ((q, "q_norm"), (k, "k_norm")))
        if cfg.block.positions == "rope":
            with jax.named_scope("attn/rope"):
                q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        attn = cfg.attn_fn or default_attention()
        o = attn(q, k, v, causal=True)
        o = o.reshape(o.shape[:-2] + (h * d,))
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out")(o)


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     name="up")(x)
        x = nn.gelu(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down")(x)


class MoeMlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.parallel.moe import (
            moe_kernel_init, moe_param_shapes, switch_moe)

        cfg = self.cfg
        shapes = moe_param_shapes(cfg.d_model, cfg.d_ff, cfg.n_experts)
        params = {name: {"kernel": self.param(
            f"{name}_kernel", moe_kernel_init, shape)}
            for name, shape in shapes.items()}
        out, aux = switch_moe(x, params)
        self.sow("intermediates", "moe_aux_loss", aux)
        return out


class TopkMoeMlp(nn.Module):
    """The dropless top-k expert layer (``parallel/moe.py:topk_moe``):
    ``cfg.n_experts`` gated experts of width ``cfg.d_expert``,
    ``cfg.experts_per_token`` a token.  Sows its load-balancing loss
    (``moe_aux_loss``), its router z-loss (``moe_z_loss``) and the
    counter ``moe_tokens_per_expert`` for :func:`apply_with_aux`."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.parallel.moe import (
            moe_kernel_init, moe_param_shapes, topk_moe)

        cfg = self.cfg
        shapes = moe_param_shapes(cfg.d_model, cfg.d_expert or cfg.d_ff,
                                  cfg.n_experts, gated=True)
        params = {name: {"kernel": self.param(
            f"{name}_kernel", moe_kernel_init, shape)}
            for name, shape in shapes.items()}
        out, aux = topk_moe(x, params, k=cfg.experts_per_token)
        self.sow("intermediates", "moe_aux_loss", aux["load_balancing"])
        self.sow("intermediates", "moe_z_loss", aux["router_z"])
        self.sow("intermediates", "moe_tokens_per_expert",
                 aux["tokens_per_expert"])
        return out


class FusedLayerNorm(nn.Module):
    """LayerNorm through the fused Pallas kernel on TPU
    (``ops/pallas/layer_norm.py``: one HBM pass per direction); the
    XLA reference path elsewhere.  Parameter names/shapes match
    ``nn.LayerNorm`` so checkpoints and the TP sharding rules are
    unaffected."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,),
                          jnp.float32)
        if jax.default_backend() == "tpu":
            from horovod_tpu.ops.pallas.layer_norm import layer_norm
            return layer_norm(x, scale, bias, self.eps)
        from horovod_tpu.ops.pallas.layer_norm import layer_norm_reference
        return layer_norm_reference(x, scale, bias, self.eps)


# a feed-forward by its name in BlockSpec: the module and the name its
# parameters live under (the sharding rules read "mlp" and "moe")
FEED_FORWARDS = {"gelu": (Mlp, "mlp"), "moe_switch": (MoeMlp, "moe"),
                 "moe_topk": (TopkMoeMlp, "moe")}


class Block(nn.Module):
    cfg: TransformerConfig
    # this block's feed-forward where it is not ``cfg.block.ffn``
    ffn: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = make_norm(cfg, "ln1")(x)
        x = x + Attention(cfg, name="attn")(y.astype(cfg.dtype))
        y = make_norm(cfg, "ln2")(x)
        module, name = FEED_FORWARDS[self.ffn or cfg.block.ffn]
        return x + module(cfg, name=name)(y.astype(cfg.dtype))


def lm_loss(logits, tokens):
    """Mean next-token cross-entropy — the LM training loss.

    On TPU this is the fused Pallas kernel
    (``ops/pallas/softmax_xent.py``: no materialized ``[rows, vocab]``
    log-softmax; the logits walked in tiles sized by what fits VMEM,
    whatever the vocabulary divides by); the XLA/optax lowering
    elsewhere."""
    labels = jnp.roll(tokens, -1, axis=-1)
    if jax.default_backend() == "tpu":
        from horovod_tpu.ops.pallas.softmax_xent import softmax_xent
        return jnp.mean(softmax_xent(logits, labels))
    from horovod_tpu.ops.pallas.softmax_xent import softmax_xent_reference
    return jnp.mean(softmax_xent_reference(logits, labels))


def apply_with_aux(model, params, tokens):
    """Forward pass returning ``(logits, aux)``.

    MoE blocks ``sow`` their auxiliary losses and counters into the
    ``intermediates`` collection, which plain ``model.apply`` drops;
    training code for MoE configs must use this helper (or pass
    ``mutable=["intermediates"]`` itself) and add the terms to the loss
    with the job's weights, or the router receives no balancing
    gradient.  ``aux`` holds, summed over the MoE blocks,
    ``load_balancing`` and ``router_z`` (0 where no block has one),
    beside ``moe_layers`` (how many blocks were summed, for a mean) and
    the counter ``tokens_per_expert [layers, E]`` of the top-k blocks
    (``None`` without one).
    """
    logits, state = model.apply({"params": params}, tokens,
                                mutable=["intermediates"])
    sown = {"moe_aux_loss": [], "moe_z_loss": [],
            "moe_tokens_per_expert": []}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            state.get("intermediates", {}))[0]:
        for k in path:
            if getattr(k, "key", None) in sown:
                sown[k.key].append(leaf)
    counts = sown["moe_tokens_per_expert"]
    return logits, {
        "load_balancing": sum(sown["moe_aux_loss"],
                              jnp.zeros((), jnp.float32)),
        "router_z": sum(sown["moe_z_loss"], jnp.zeros((), jnp.float32)),
        "moe_layers": len(sown["moe_aux_loss"]),
        "tokens_per_expert": jnp.stack(counts) if counts else None,
    }


class Transformer(nn.Module):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (causal LM)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="embed")(tokens)
        if cfg.block.positions == "learned":
            x = x + nn.Embed(
                cfg.max_len, cfg.d_model, dtype=cfg.dtype,
                name="pos_embed")(jnp.arange(tokens.shape[-1]))
        block_cls = nn.remat(Block) if cfg.remat else Block
        for i in range(cfg.n_layers):
            switch = cfg.moe_every and (i + 1) % cfg.moe_every == 0
            x = block_cls(cfg, ffn="moe_switch" if switch else None,
                          name=f"block_{i}")(x)
        x = make_norm(cfg, "ln_f")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x.astype(cfg.dtype))
