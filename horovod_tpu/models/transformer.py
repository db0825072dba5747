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
configuration says which norm, position scheme, attention and
feed-forward a block has.  The default is the GPT-2 block (LayerNorm,
learned positions, GELU); ``BlockSpec(norm="rms", positions="rope",
qk_norm=True, ffn="moe_topk")`` is OLMoE's; a block may also have
``attention=LatentAttention(...)``, ``positions="rope_pairs"`` and
``ffn=TopkExperts(scoring="sigmoid", ...)``, behind
``TransformerConfig.leading_dense`` dense SwiGLU layers and with a
:class:`NextTokenModule` beside the model; or
``norm_placement="sandwich"`` (a norm after each branch too), in a
stack that runs ``TransformerConfig.passes`` times with one set of
weights and, with ``exit_gate``, gives every pass's exit to
:func:`looped_lm_loss`.  A model whose layers are not all of one kind
gives ``TransformerConfig.pattern``, a period of specs: layer ``l`` is
built from ``pattern[l % len(pattern)]``; with
``attention=GroupedAttention(...)`` a kind of layer has its own count
of query heads over grouped key-value heads, a sliding window or none,
its own rotary recipe (:class:`Rotary`), a norm over each head of q
and k and a gate a head on the attention's output (or no rotation at
all: ``rotary=None``); an expert layer's router may read the block's
input and decide before the mixer runs (``TopkExperts(route_from=
"input")``) and its experts may gate by ReLU.  A block's mixer need
not be attention: ``attention=ShortConv(...)`` is a doubly gated causal
convolution of a few taps along the sequence (:class:`ShortConvMixer`),
``attention=SelectiveScan(...)`` a mixer with a state carried along the
sequence (:class:`SelectiveScanMixer`, Mamba's: a vector a channel),
``attention=Mamba2(...)`` one whose state is a matrix a head, computed
by chunks as matrix products (:class:`Mamba2Mixer`, ``ops/ssd.py``),
``attention=MemoryUnit(...)`` a gate on what an earlier block made.  A
block may be ONE branch: ``attention=None`` is a layer that is its
feed-forward alone (``x + ffn(norm(x))``), ``ffn=None`` one that is its
mixer alone; an expert layer's experts may have no gate
(``TopkExperts(activation="relu2")``).  Attention may
be the difference of two softmaxes over paired heads
(:class:`DifferentialAttention`), with its own keys and values or an
earlier block's, or softmax attention linearised by chunk
(:class:`ChunkSummaryAttention`: a query reads its own window exactly and
every earlier one through pooled summaries).  What one block hands a
later one (``memory``,
``keys``) travels beside ``x`` through the stack as one small pytree.
``positions="none"`` is a model with no position encoding at all, and
``TransformerConfig.tie_head`` one whose head is its embedding.  The
residual stream may be carried in another dtype than the products'
(``residual_dtype``), the RMSNorm's scale may start at 0 and count from 1
(``norm_unit_offset``), and the head may predict several positions ahead
from one hidden state (``head_outputs``, :func:`multi_offset_lm_loss`).

bfloat16 activations by default (MXU-native), fp32 layernorm/softmax.
"""

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.core import FrozenDict
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.parallel.ring_attention import reference_attention
from horovod_tpu.utils.logging import get_logger


NORMS = ("layer", "rms")
NORM_PLACEMENTS = ("pre", "sandwich")
POSITIONS = ("learned", "rope", "rope_pairs", "none")
ATTENTIONS = ("full",)
FFNS = ("gelu", "swiglu", "moe_switch", "moe_topk")


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): the
    queries come through a normed latent of ``q_rank``, keys and values
    through one of ``kv_rank`` beside ONE rotated key of ``rope_dim``
    that all heads share.  A head's query and key are ``[nope_dim |
    rope_dim]`` wide, its value ``v_dim``."""
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A rotary recipe in the rotate-half pairing: the FIRST
    ``fraction`` of a head's columns is turned (the rest pass), by the
    frequencies ``theta^(-2i / D')`` over those ``D'`` columns.  With
    ``factor`` they are YaRN's (arXiv:2309.00071, as Hugging Face's
    ``_compute_yarn_parameters`` reads the keys): between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_len`` positions a linear ramp goes from the frequency as
    it is to the frequency over ``factor``, and cos and sin are
    multiplied by ``attention_factor``."""
    theta: float = 10000.0
    fraction: float = 1.0
    factor: Optional[float] = None
    original_len: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class GroupedAttention:
    """Attention with grouped key-value heads: ``heads`` query heads of
    ``head_dim`` read ``kv_heads`` keys and values (head ``h`` reads
    ``h // (heads / kv_heads)``), through a q projection and a k/v
    projection of their own.  ``window``: a query sees that many keys,
    itself included (``None``: every key before it).  ``rotary``: the
    recipe q and k are turned by (``None``: they are read as projected,
    a layer without positions, and no table is built).  ``gate``:
    ``"softplus"`` scales every
    head's output by ``softplus(x W_g)``, one scalar a head and position
    in float32, from the input the projections read (``None``: no
    gate).  ``qk_norm``: an RMSNorm over the ``head_dim`` columns of
    EVERY head of q and of k, one scale of ``head_dim`` each shared by
    the heads, before the rotation (``BlockSpec.qk_norm`` norms the
    whole projection, which is another function)."""
    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None
    rotary: Optional[Rotary] = Rotary()
    gate: Optional[str] = None
    qk_norm: bool = False

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(
                f"GroupedAttention: {self.kv_heads} key-value heads do not "
                f"divide {self.heads} query heads")
        if self.gate not in (None, "softplus"):
            raise ValueError(f"GroupedAttention: gate {self.gate!r} is "
                             f"neither None nor 'softplus'")
        if not isinstance(self.rotary, (Rotary, type(None))):
            raise ValueError(f"GroupedAttention: rotary {self.rotary!r} is "
                             f"neither None nor a Rotary")


@dataclasses.dataclass(frozen=True)
class ShortConv:
    """A mixer that is no attention (:class:`ShortConvMixer`): a causal
    depthwise convolution of ``taps`` taps along the sequence between
    two gates.  A position reads itself and the ``taps - 1`` before it;
    no state but those, no kernel, no positions."""
    taps: int = 3

    def __post_init__(self):
        if self.taps < 1:
            raise ValueError(f"ShortConv: {self.taps} taps")


@dataclasses.dataclass(frozen=True)
class SelectiveScan:
    """A mixer with a state carried along the sequence
    (:class:`SelectiveScanMixer`; Mamba, arXiv:2312.00752): ``d_inner``
    channels, each with a state of ``state`` numbers that decays and is
    fed at a rate chosen a position (through a bottleneck of
    ``dt_rank``), behind a causal depthwise convolution of ``taps``
    taps.  ``publishes``: the scan's output, before the mixer's gate, is
    handed to later blocks as ``memory``."""
    d_inner: int
    dt_rank: int
    state: int = 16
    taps: int = 4
    publishes: bool = False


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """A mixer with a MATRIX state a head carried along the sequence
    (:class:`Mamba2Mixer`; Mamba-2, arXiv:2405.21060): ``heads`` heads
    of ``head_dim`` channels, each with a state ``[head_dim, state]``
    that decays by one scalar a head and position and is fed through
    ``B``, read through ``C``, which the ``heads / groups`` heads of a
    group share; behind a causal depthwise convolution of ``taps`` taps
    over the channels, ``B`` and ``C`` together, and ahead of a gated
    RMSNorm over each group's channels.  ``chunk``: the positions a
    chunk of ``ops/ssd.py`` holds."""
    heads: int
    head_dim: int
    groups: int
    state: int = 128
    taps: int = 4
    chunk: int = 128

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(f"Mamba2: {self.groups} groups do not divide "
                             f"{self.heads} heads")

    @property
    def d_inner(self):
        return self.heads * self.head_dim

    @property
    def in_width(self):
        """Columns of the first product: the gate ``z``, the channels
        with ``B`` and ``C`` (what the convolution reads) and ``dt``."""
        return 2 * self.d_inner + 2 * self.groups * self.state + self.heads


@dataclasses.dataclass(frozen=True)
class MemoryUnit:
    """A mixer that gates what an earlier block made
    (:class:`MemoryUnitMixer`; a gated memory unit, arXiv:2507.06607):
    ``(silu(x W_1) * m) W_2`` with ``m [..., T, d_inner]`` the
    ``memory`` a :class:`SelectiveScan` block published.  No state, no
    kernel, no positions of its own."""
    d_inner: int


KEYS = ("own", "published", "read")


@dataclasses.dataclass(frozen=True)
class DifferentialAttention:
    """Attention that is the difference of two softmaxes
    (:func:`differential_attention`; arXiv:2410.05258).  The ``heads``
    query heads and ``kv_heads`` key heads of ``head_dim`` pair up as
    neighbours ``(2i, 2i + 1)``; a pair of query heads reads pair ``i //
    (heads / kv_heads)`` of the keys, one softmax each, and both read
    that pair's two value heads side by side (values ``2 head_dim``
    wide); the second softmax is taken off the first times ``lambda``,
    learned around ``lambda_init``.  ``window``: a query sees that many
    keys, itself included (``None``: every key before it).  No
    rotation.  ``keys``: ``"own"`` (a k/v projection of its own),
    ``"published"`` (its own, also handed to later blocks as ``keys``)
    or ``"read"`` (no k/v projection: the ``keys`` an earlier block
    published)."""
    heads: int
    kv_heads: int
    head_dim: int
    lambda_init: float
    window: Optional[int] = None
    keys: str = "own"

    def __post_init__(self):
        if self.heads % self.kv_heads or self.kv_heads % 2:
            raise ValueError(
                f"DifferentialAttention: {self.kv_heads} key-value heads "
                f"are not pairs that divide {self.heads} query heads")
        if self.keys not in KEYS:
            raise ValueError(f"DifferentialAttention: keys {self.keys!r} "
                             f"is none of {KEYS}")


@dataclasses.dataclass(frozen=True)
class ChunkSummaryAttention:
    """Softmax attention linearised by chunk
    (:class:`ChunkSummaryAttentionMixer`, ``ops/chunk_attention.py``;
    arXiv:2302.04542): ``heads`` heads of ``head_dim`` for q, k and v
    alike.  The sequence is cut into windows of ``window`` positions
    that do not slide; a query reads the keys of its own window up to
    itself exactly and every EARLIER window through one learned summary
    of k and v every ``chunk`` positions, in one softmax.  ``rotary``:
    the recipe q and k are turned by, before the pooling."""
    heads: int
    head_dim: int
    window: int
    chunk: int
    rotary: Rotary = Rotary()

    def __post_init__(self):
        if self.chunk < 1 or self.window % self.chunk:
            raise ValueError(
                f"ChunkSummaryAttention: chunks of {self.chunk} do not "
                f"divide a window of {self.window}")


MIXERS = (LatentAttention, GroupedAttention, ShortConv, SelectiveScan,
          Mamba2, MemoryUnit, DifferentialAttention, ChunkSummaryAttention)
ROUTES_FROM = ("ffn_input", "input")


@dataclasses.dataclass(frozen=True)
class TopkExperts:
    """A top-k expert layer (:func:`~horovod_tpu.parallel.moe.topk_moe`)
    told more than ``"moe_topk"`` says.  ``scoring``, ``renormalize``
    and ``scale`` are :func:`~horovod_tpu.parallel.moe.topk_route`'s
    (its ``bias`` is the row of ``router_bias`` the caller hands the
    model); ``shared``: that many experts of the routed ones' form and
    width (of ``shared_width`` each where one is given) every token
    goes through, beside the routed ones; ``held``:
    ``(first, count)``, the routed experts this device holds of the
    router's ``n_experts``.  ``route_from``: the array the router
    reads: ``"ffn_input"``, what the experts read (the block's second
    norm's output), or ``"input"``, the BLOCK's input ahead of its first
    norm: :class:`Block` then decides before its mixer runs, under the
    scope ``route_ahead``, and the experts are handed the decision.
    ``activation``: the routed experts' gate function, ``"silu"``
    (SwiGLU) or ``"relu"`` (ReGLU), or ``"relu2"``: experts with NO
    gate, ``relu(x W_up)^2 W_down``, two grouped products for three.
    The shared experts are SwiGLU beside SwiGLU experts and of the same
    non-gated form beside ``"relu2"`` ones."""
    scoring: str = "softmax"
    renormalize: bool = False
    scale: float = 1.0
    shared: int = 0
    held: Optional[Tuple[int, int]] = None
    route_from: str = "ffn_input"
    activation: str = "silu"
    shared_width: Optional[int] = None

    def __post_init__(self):
        from horovod_tpu.parallel.moe import EXPERT_ACTIVATIONS

        if self.route_from not in ROUTES_FROM:
            raise ValueError(f"TopkExperts: route_from {self.route_from!r} "
                             f"is none of {ROUTES_FROM}")
        if self.activation not in EXPERT_ACTIVATIONS:
            raise ValueError(f"TopkExperts: activation {self.activation!r} "
                             f"is none of {sorted(EXPERT_ACTIVATIONS)}")
        if self.shared and self.activation == "relu":
            raise ValueError(
                f"TopkExperts: the {self.shared} shared experts are SwiGLU "
                f"or have no gate, the routed ones are asked for "
                f"{self.activation!r}")

    @property
    def gated(self):
        """Whether an expert has a gate (three products) or none (two)."""
        from horovod_tpu.parallel.moe import ACTIVATIONS

        return self.activation in ACTIVATIONS

    def shared_columns(self, width):
        """The columns of the shared experts' hidden layer, side by side,
        beside routed experts of ``width``."""
        return self.shared * (self.shared_width or width)


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What a block is made of.  ``norm``: ``"layer"`` (LayerNorm with
    a bias, the fused kernel on TPU) or ``"rms"`` (RMSNorm, scale
    only).  ``norm_placement``: ``"pre"`` (one norm before each branch,
    ``x + f(norm(x))``) or ``"sandwich"`` (one before and one after,
    ``x + norm(f(norm(x)))``: four norms a block).  ``positions``:
    ``"learned"`` (a table added to the embedding), ``"rope"`` (rotary
    in the rotate-half pairing, applied to q and k before the attention
    function; no table) or ``"rope_pairs"`` (rotary over the pairs
    ``(2i, 2i + 1)``) or ``"none"`` (the model has no position encoding).
    ``qk_norm``: a norm of the block's kind over the
    whole q and k projections, before the split into heads.  ``attention``:
    the block's mixer: ``"full"`` (one fused q, k, v projection, heads of
    one width), a :class:`LatentAttention`, a :class:`GroupedAttention`,
    a :class:`DifferentialAttention`, a :class:`ChunkSummaryAttention`,
    or a :class:`ShortConv`, :class:`SelectiveScan`, :class:`Mamba2` or
    :class:`MemoryUnit`, which are no attention; or ``None``: the block
    has no mixer and is ``x + ffn(norm(x))`` alone (its norm is ``ln2``).
    ``ffn`` (``None``: the block has no feed-forward and is ``x +
    mixer(norm(x))`` alone, its norm ``ln1``; one of the two branches is
    always there):
    ``"gelu"`` (dense up-GELU-down), ``"swiglu"`` (dense gated, ``silu(x
    gate) * (x up)`` down), ``"moe_switch"``
    (:func:`~horovod_tpu.parallel.moe.switch_moe`), ``"moe_topk"``
    (:func:`~horovod_tpu.parallel.moe.topk_moe` as OLMoE has it) or a
    :class:`TopkExperts`."""
    norm: str = "layer"
    positions: str = "learned"
    qk_norm: bool = False
    ffn: Union[None, str, TopkExperts] = "gelu"
    attention: Union[(None, str) + MIXERS] = "full"
    norm_placement: str = "pre"

    def __post_init__(self):
        for value, known, cls in (
                (self.norm, NORMS, ()), (self.positions, POSITIONS, ()),
                (self.norm_placement, NORM_PLACEMENTS, ()),
                (self.ffn, FFNS + (None,), TopkExperts),
                (self.attention, ATTENTIONS + (None,), MIXERS)):
            if value not in known and not isinstance(value, cls):
                raise ValueError(f"BlockSpec: {value!r} is none of {known}")
        if self.ffn is None and self.attention is None:
            raise ValueError("BlockSpec: a block with neither a mixer nor a "
                             "feed-forward")


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
    # a period of specs for a model whose layers are not all of one
    # kind: layer l is ``pattern[l % len(pattern)]`` (empty: every layer
    # is ``block``).  The specs agree on what the model has once: the
    # norm's kind and whether positions are a learned table
    pattern: Tuple[BlockSpec, ...] = ()
    # every k-th block uses a switch-MoE FFN whatever ``block.ffn``
    # says (0 = every block as ``block`` has it)
    moe_every: int = 0
    # the first blocks whose feed-forward is a dense SwiGLU of width
    # ``d_ff`` whatever ``block.ffn`` says (dense layers before expert
    # layers)
    leading_dense: int = 0
    n_experts: int = 8
    # sizes only some blocks read: a head's width (None: d_model /
    # n_heads), experts a token and an expert's width (None: d_ff) for
    # ``moe_topk``, the rotary base, the norms' epsilon
    head_dim: Optional[int] = None
    experts_per_token: int = 2
    d_expert: Optional[int] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # recompute in the backward pass what does not fit (jax.checkpoint
    # around every block): trades up to ~1/3 more FLOPs for O(layers)
    # less activation HBM, the lever for pushing per-chip batch (and
    # usually MFU) once activations, not weights, bound the batch size.
    # What a block keeps is planned a layer from bytes (``kept_plan``:
    # the shapes, the parameters, the device's memory; no option).  Every
    # block keeps its input and what ``kept_names`` lists (``kept_bytes``
    # gives the bytes): the flash kernel's output and lse; with one pass
    # also the sum after attention (``B T d_model x itemsize``), the
    # kernel's q, k and v as a set where none is larger than its output
    # (``B T (H + 2 G) d x itemsize``; not 192 over 128), latent
    # attention's two narrow first products and what a routed layer
    # decided (experts, sorted order).  A layer the device has room for
    # keeps the results of its products as well (``kept_products``).
    # What made a kept array is not run again
    remat: bool = False
    # the stack of blocks runs this many times with ONE set of weights,
    # the final norm closing each pass: its output is that pass's exit
    # and the next pass's input (1: every block once)
    passes: int = 1
    # one ``Linear(d_model, 1)`` over every pass's exit, shared by the
    # passes; the model then returns the logits of EVERY exit, for
    # :func:`looped_lm_loss`
    exit_gate: bool = False
    # the head is the embedding: logits = norm_f(x) E^T with E read in
    # the activation dtype, one parameter with the gradient of both uses
    tie_head: bool = False
    # the dtype the residual stream is embedded, summed and carried in
    # between the blocks (None: ``dtype``); the norms read it in float32
    # anyway and every product still takes ``dtype``
    residual_dtype: Any = None
    # the RMSNorms' scale starts at 0 and multiplies as ``1 + scale``
    norm_unit_offset: bool = False
    # the head gives this many rows of ``vocab_size`` logits a position,
    # ``[..., T, head_outputs * vocab_size]`` from one hidden state:
    # output r is asked for token ``t + 1 + r``
    # (:func:`multi_offset_lm_loss`)
    head_outputs: int = 1
    # the dtype the head's product is summed and returned in (None:
    # ``dtype``); its operands are ``dtype`` either way
    logits_dtype: Any = None

    def __post_init__(self):
        if self.norm_unit_offset and any(
                spec.norm != "rms" for spec in self.pattern or (self.block,)):
            raise ValueError("TransformerConfig.norm_unit_offset: only an "
                             "RMSNorm has one")
        if self.head_outputs != 1 and (self.tie_head or self.exit_gate):
            raise ValueError("TransformerConfig.head_outputs: a tied head "
                             "or an exit gate has one output a position")
        once = {(spec.norm, spec.positions == "learned")
                for spec in self.pattern}
        if len(once) > 1:
            raise ValueError(
                "TransformerConfig.pattern: the specs differ in the norm's "
                "kind or in whether positions are learned, which the model "
                "has once")
        if self.pattern and self.block != self.pattern[0]:
            # what the model reads once (final norm, position table) it
            # reads off ``block``
            object.__setattr__(self, "block", self.pattern[0])

    def at(self, layer):
        """The configuration block ``layer`` is built from: this one
        with the layer's spec of the pattern as its ``block``."""
        if not self.pattern:
            return self
        return dataclasses.replace(
            self, block=self.pattern[layer % len(self.pattern)], pattern=())

    def ffn_of(self, layer):
        """The feed-forward of block ``layer``: the per-layer pattern."""
        if layer < self.leading_dense:
            return "swiglu"
        if self.moe_every and (layer + 1) % self.moe_every == 0:
            return "moe_switch"
        return self.at(layer).block.ffn


def default_attention():
    """The hot-path kernel: Pallas flash attention on TPU (O(T) memory,
    MXU-tiled blocks — ``ops/pallas/flash_attention.py``); the dense
    reference path elsewhere (interpret-mode Pallas on CPU is far slower
    than XLA's fused softmax for test-sized problems)."""
    if jax.default_backend() == "tpu":
        from horovod_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention
    return reference_attention


# What a block names for the policy of ``keeping``: results the
# backward pass reads (or that stand between it and what it reads), cheap
# to hold and dear to make again.  Outside a checkpoint a name is the
# identity.
KEPT_SUM = "block_after_attention"   # x + attention(x): ln2's input
KEPT_Q_A = "latent_q_a"              # latent attention's x W_qa
KEPT_KV_A = "latent_kv_a"            # latent attention's x W_kva
KEPT_NAMES = (KEPT_SUM, KEPT_Q_A, KEPT_KV_A)
# The products a recomputed block makes again carry these; no block keeps
# them but one that ``kept_plan`` found room for (``kept_products``).
KEPT_GATE = "mlp_gate"               # x W_gate of a SwiGLU, shared or not
KEPT_UP = "mlp_up"                   # x W_up of a SwiGLU, a GELU layer or
#                                      a shared expert without a gate
KEPT_IN = "mixer_in"                 # a NO_ATTENTION mixer's first product
KEPT_Q_B = "latent_q_b"              # latent attention's norm(c_q) W_qb
KEPT_KV_B = "latent_kv_b"            # latent attention's norm(c_kv) W_kvb
# chunk-summary attention's x W_q, x W_k, x W_v
KEPT_QKV = ("chunk_q", "chunk_k", "chunk_v")
NO_ATTENTION = (ShortConv, SelectiveScan, Mamba2, MemoryUnit)
# A recomputed step is planned to this share of the device's memory
BUDGET_SHARE = 0.95


def kept_names(cfg):
    """The names EVERY recomputed block of ``cfg`` keeps from its
    forward pass, room or none (rung 0 of ``kept_plan``; what a layer
    keeps beyond them is the plan's): the flash kernel's ``SAVED_NAMES``
    (its output and lse), its ``SAVED_INPUT_NAMES`` (q, k and v as it
    reads them, which the kernel's call carries only where they are no
    larger than its output) and the model's own ``KEPT_NAMES``.

    Under a loop over passes (``cfg.passes > 1``) ``SAVED_NAMES`` alone:
    there every kept array is stacked once a pass and the loop's tuple
    holds the stack twice, and the sandwich norm such a model has after
    its attention reads the output projection's result anyway.  That is
    how the program is built, which the code reads off its own
    configuration; it is no model's name.

    Where no block of ``cfg`` is attention (every mixer a
    :class:`ShortConv`, :class:`SelectiveScan` or :class:`MemoryUnit`;
    ``cfg.at(layer)`` is such a configuration for such a layer of a
    mixed pattern) there is nothing of the flash kernel to name: the sum
    after the mixer and, of a :class:`SelectiveScan`, what its scan
    hands its backward pass (``ops/selective_scan.py``'s
    ``SAVED_NAMES``: its output and the state every chunk is entered
    with) or of a :class:`Mamba2` its scan's output (``ops/ssd.py``'s
    ``SAVED_NAMES``).  One list serves a mixed pattern: a name no block
    sets keeps nothing.  A layer that has no mixer (``attention=None``)
    has nothing of a kernel to name and sets no ``KEPT_SUM``: its one
    sum is its output, the next layer's input, which every checkpoint
    keeps; nor does a layer that is its mixer alone.

    With one pass, whatever the mixers, also what a routed layer decided
    (``parallel/moe.py``'s ``SAVED_NAMES``: the experts chosen and the
    sorted order of the slots, under a hundred bytes a token): ``top_k``
    and the sorts are not made again.

    Where a block is a :class:`ChunkSummaryAttention` also its summaries
    (``ops/chunk_attention.py``'s ``SAVED_NAMES``, ``1 / chunk`` of k
    and v), and NOT the kernels' inputs: its two calls read q in two
    layouts, so q, k and v as the kernels read them would be four arrays
    of ``out``'s size a layer; what makes them again is three products
    of ``d_model x d_model``, whose results are the plan's to keep."""
    from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                        SAVED_NAMES)
    from horovod_tpu.parallel.moe import SAVED_NAMES as routed
    if cfg.passes > 1:
        return SAVED_NAMES
    mixers = [spec.attention for spec in cfg.pattern or (cfg.block,)
              if spec.attention is not None]
    scanned = ()
    if any(isinstance(m, SelectiveScan) for m in mixers):
        from horovod_tpu.ops.selective_scan import SAVED_NAMES as scanned
    if any(isinstance(m, Mamba2) for m in mixers):
        from horovod_tpu.ops.ssd import SAVED_NAMES as by_chunks
        scanned += by_chunks
    if all(isinstance(m, NO_ATTENTION) for m in mixers):
        return scanned + (KEPT_SUM,) + routed
    if any(isinstance(m, ChunkSummaryAttention) for m in mixers):
        from horovod_tpu.ops.chunk_attention import SAVED_NAMES as summaries
        return SAVED_NAMES + summaries + scanned + KEPT_NAMES + routed
    return SAVED_NAMES + SAVED_INPUT_NAMES + scanned + KEPT_NAMES + routed


def keeping(block, names):
    """``block`` under ``jax.checkpoint``: its forward pass runs again in
    the backward pass, all but what made the arrays ``names`` name, which
    are kept.  A block that sets none of the names keeps nothing, as
    under a plain ``nn.remat``.  With ``kept_names(cfg)`` (rung 0 of
    ``kept_plan``) what is not made again is the flash forward kernel,
    the output projection and, where the kernel's inputs are kept,
    everything ahead of the kernel but the norm; a layer the plan found
    room for keeps the results of its products too."""
    return nn.remat(block, policy=jax.checkpoint_policies
                    .save_only_these_names(*names))


def kept_products(cfg, layer=0):
    """``[(names, worth)]``: the products recomputed block ``layer``
    makes again whose results carry a name, in the order the block makes
    them, and what keeping a byte of each saves: the product's
    contracting width (a ``[rows, K] x [K, N]`` result buys ``K``
    multiply-adds a number kept) times the share of the result's rows
    that exist (all, but in a routed layer's buffer of held experts:
    ``moe.product_bytes``).  Names that one gradient reads together are
    one entry (a SwiGLU's ``gate`` and ``up``).  A layer of one branch
    has the products of that branch alone, and experts without a gate no
    ``gate``.  None under
    ``cfg.passes > 1``, for the reason ``kept_names`` gives.  Not here:
    what ``kept_names`` keeps already (attention's q, k and v where the
    kernel names them)."""
    from horovod_tpu.parallel import moe

    if cfg.passes > 1:
        return []
    ffn = cfg.ffn_of(layer)
    cfg = cfg.at(layer)
    spec = cfg.block.attention
    found = []
    if isinstance(spec, NO_ATTENTION):
        found.append(((KEPT_IN,), cfg.d_model))
    if isinstance(spec, LatentAttention):
        found += [((KEPT_Q_B,), spec.q_rank), ((KEPT_KV_B,), spec.kv_rank)]
    if isinstance(spec, ChunkSummaryAttention):
        found += [((name,), cfg.d_model) for name in KEPT_QKV]
    if ffn == "gelu":
        found.append(((KEPT_UP,), cfg.d_model))
    gated = _gated(ffn)
    if ffn == "swiglu" or getattr(ffn, "shared", 0):
        found.append(((KEPT_GATE, KEPT_UP) if gated else (KEPT_UP,),
                      cfg.d_model))
    if ffn == "moe_topk" or isinstance(ffn, TopkExperts):
        gate, up, down = moe.PRODUCT_NAMES
        _, share = _routed_products(cfg, ffn, 1, 1)
        found += [((gate, up) if gated else (up,), cfg.d_model * share),
                  ((down,), (cfg.d_expert or cfg.d_ff) * share)]
    return found


def _gated(ffn):
    """Whether the experts of feed-forward ``ffn`` have a gate: all but a
    :class:`TopkExperts` that says otherwise."""
    return getattr(ffn, "gated", True)


def _routed_products(cfg, ffn, batch, seq):
    """``moe.product_bytes`` of the routed layer ``ffn`` of ``cfg``."""
    from horovod_tpu.parallel import moe

    held = getattr(ffn, "held", None)
    return moe.product_bytes(
        batch * seq, cfg.experts_per_token, cfg.d_model,
        cfg.d_expert or cfg.d_ff, jnp.dtype(cfg.dtype).itemsize,
        held and held + (cfg.n_experts,), gated=_gated(ffn))


def kept_bytes(cfg, batch, seq, layer=0, names=None):
    """``{name: bytes}`` of what ONE application of recomputed block
    ``layer`` keeps by name on ``[batch, seq]`` tokens through the flash
    kernel (its input, which every checkpoint keeps, is ``batch seq
    d_model x itemsize`` more): of ``kept_names(cfg)``, what every
    recomputed block keeps, or of ``names`` (``kept_products``'s: the
    results of the layer's products, ``rows x columns x itemsize``
    each; a name this layer has no bytes for is a ``KeyError``, so a
    product listed there and forgotten here is not planned as free).
    A layer whose mixer is no attention
    has no kernel and no q, k or v: the sum after the mixer is all it
    names, and a :class:`SelectiveScan`'s what its scan keeps.  A
    :class:`DifferentialAttention` calls the kernel twice (half the
    heads each, the values twice as wide), so every name of the kernel
    is there twice and counts twice; so does a
    :class:`ChunkSummaryAttention` longer than one window (the local and
    the remote call, ``out`` and ``lse`` of q's size each), beside its
    summaries.  The sum after the mixer is in the residual stream's
    dtype.  A layer whose feed-forward is
    routed (``"moe_topk"`` or a :class:`TopkExperts`) keeps what its
    routing decided (``parallel/moe.py:saved_bytes``).  A layer of one
    branch has that branch's names alone and no sum after the mixer: a
    layer with no mixer nothing of a kernel or a scan, a layer with no
    feed-forward no product of one."""
    from horovod_tpu.ops.pallas.flash_attention import saved_bytes
    from horovod_tpu.parallel import moe

    ffn = cfg.ffn_of(layer)
    cfg = cfg.at(layer)
    spec = cfg.block.attention
    heads, groups, calls = cfg.n_heads, cfg.n_heads, 1
    d_qk = d_v = cfg.head_dim or cfg.d_model // cfg.n_heads
    if isinstance(spec, GroupedAttention):
        heads, groups, d_qk, d_v = (spec.heads, spec.kv_heads,
                                    spec.head_dim, spec.head_dim)
    if isinstance(spec, DifferentialAttention):
        heads, groups, calls = spec.heads // 2, spec.kv_heads // 2, 2
        d_qk, d_v = spec.head_dim, 2 * spec.head_dim
    itemsize = jnp.dtype(cfg.dtype).itemsize
    row = batch * seq * itemsize  # a column of a product's result
    kept = {}
    if spec is not None and ffn is not None:
        kept[KEPT_SUM] = batch * seq * cfg.d_model * jnp.dtype(
            cfg.residual_dtype or cfg.dtype).itemsize
    if isinstance(spec, ChunkSummaryAttention):
        from horovod_tpu.ops.chunk_attention import SAVED_NAMES as summaries

        heads = groups = spec.heads
        d_qk = d_v = spec.head_dim
        calls = 1 if seq <= spec.window else 2
        kept.update(dict.fromkeys(
            summaries, batch * seq // spec.chunk * heads * d_qk * itemsize))
        kept.update(dict.fromkeys(KEPT_QKV, row * heads * d_qk))
    if ffn == "moe_topk" or isinstance(ffn, TopkExperts):
        kept.update(moe.saved_bytes(batch * seq, cfg.experts_per_token,
                                    getattr(ffn, "held", None)))
        kept.update(_routed_products(cfg, ffn, batch, seq)[0])
    # a dense feed-forward's width, or the shared experts' (of the
    # routed ones' form: a SwiGLU, or no gate)
    width = (cfg.d_ff if ffn in ("gelu", "swiglu") else
             ffn.shared_columns(cfg.d_expert or cfg.d_ff)
             if isinstance(ffn, TopkExperts) else 0)
    if width:
        kept[KEPT_UP] = row * width
        if ffn != "gelu" and _gated(ffn):
            kept[KEPT_GATE] = row * width
    if isinstance(spec, SelectiveScan):
        from horovod_tpu.ops import selective_scan

        kept.update(selective_scan.saved_bytes(
            batch, seq, spec.d_inner, spec.state, cfg.dtype))
    if isinstance(spec, Mamba2):
        from horovod_tpu.ops import ssd

        kept.update(ssd.saved_bytes(batch, seq, spec.heads, spec.head_dim,
                                    cfg.dtype))
    if isinstance(spec, NO_ATTENTION):
        kept[KEPT_IN] = row * (
            3 * cfg.d_model if isinstance(spec, ShortConv) else
            2 * spec.d_inner if isinstance(spec, SelectiveScan) else
            spec.in_width if isinstance(spec, Mamba2) else
            spec.d_inner)
    if isinstance(spec, LatentAttention):
        d_qk, d_v = spec.nope_dim + spec.rope_dim, spec.v_dim
        kept[KEPT_Q_A] = row * spec.q_rank
        kept[KEPT_KV_A] = row * (spec.kv_rank + spec.rope_dim)
        kept[KEPT_Q_B] = row * cfg.n_heads * d_qk
        kept[KEPT_KV_B] = row * cfg.n_heads * (spec.nope_dim + d_v)
    if spec is not None and not isinstance(spec, NO_ATTENTION):
        q, k, v = (jax.ShapeDtypeStruct((batch, seq, h, d), cfg.dtype)
                   for h, d in ((heads, d_qk), (groups, d_qk), (groups, d_v)))
        kept.update({name: calls * n
                     for name, n in saved_bytes(q, k, v).items()})
    if names is None:  # one list serves every layer: some it does not set
        return {name: kept[name] for name in kept_names(cfg) if name in kept}
    return {name: kept[name] for name in names}


@jax.custom_vjp
def turn(x, c, s, p):
    """A rotary turn of ``x [..., T, H, D]`` as ONE pass over whole heads,

        turn(x) = (x32 * c + (x @ p) * s).astype(x.dtype)

    with ``x32`` ``x`` in float32 and the product summed in float32.
    ``p [D, D]`` (in ``x.dtype``) holds one +1 or -1 in each turned
    column, so ``x @ p`` puts every column's partner in its place with
    its sign and only moves values; ``c`` and ``s`` ``[T, 1, D]``
    (float32) are the cosines and sines at every column, 1 and 0 where a
    column passes (:func:`rotary_operands` makes all three).  The
    cotangent is turned back by the same pass with ``-p``: ``p.T = -p``
    and ``s`` is the same on both columns of a pair, so ``(g * s) @ p.T
    = (g @ -p) * s`` and the product's operands stay in ``x.dtype``.
    Nothing is kept for it but the tables."""
    # float32 operands would go through the MXU in one bfloat16 pass
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    partner = jax.lax.dot_general(
        x, p, (((x.ndim - 1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * c + partner * s).astype(x.dtype)


def _turn_fwd(x, c, s, p):
    return turn(x, c, s, p), (c, s, p)


def _turn_bwd(tables, g):
    c, s, p = tables
    return turn(g, c, s, -p), None, None, None


turn.defvjp(_turn_fwd, _turn_bwd)


def rotary_operands(x, inv_freq, factor=1.0, start=0, pairs=False):
    """``(c, s, p)`` of :func:`turn` for ``x [..., T, H, D]``: the ``2
    len(inv_freq)`` columns from ``start`` on are turned, position ``t``
    by the angles ``t * inv_freq`` with cos and sin times ``factor``;
    the rest pass.  The i-th pair is the columns ``(i, i + len(inv_freq))``
    of those (the rotate-half pairing) or, with ``pairs``, the
    neighbours ``(2i, 2i + 1)``."""
    t, d, half = x.shape[-3], x.shape[-1], inv_freq.shape[0]
    i = np.arange(half)
    first, second = ((start + 2 * i, start + 2 * i + 1) if pairs
                     else (start + i, start + half + i))
    # every column's frequency: its pair's; 0, and no turn, where it passes
    freq = jnp.pad(jnp.repeat(inv_freq, 2) if pairs else jnp.tile(inv_freq, 2),
                   (start, d - start - 2 * half))
    turned = np.zeros(d, bool)
    turned[first] = turned[second] = True
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq  # [T, 1, D]
    c = jnp.where(turned, jnp.cos(angle) * factor, 1.0)
    s = jnp.where(turned, jnp.sin(angle) * factor, 0.0)
    # (x p)[first] = -x[second], (x p)[second] = +x[first]
    p = np.zeros((d, d), np.float32)
    p[second, first], p[first, second] = -1, 1
    return c, s, jnp.asarray(p, x.dtype)


def _inv_freq(theta, dim):
    return theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2 / dim)


def rope(x, theta=10000.0, pairs=False):
    """Rotary position embedding of ``x [..., T, H, D]``: each pair of
    columns ``(x1, x2)`` is turned by ``angle(t, i) = t * theta^(-2i /
    D)``, i < D / 2, to ``(x1 cos - x2 sin, x2 cos + x1 sin)``.  The
    i-th pair is columns ``(i, i + D / 2)`` (the rotate-half form: ``x *
    cos + rotate_half(x) * sin``) or, with ``pairs``, the neighbours
    ``(2i, 2i + 1)``.  Computed in float32, returned in ``x.dtype``
    (:func:`turn`)."""
    return turn(x, *rotary_operands(x, _inv_freq(theta, x.shape[-1]),
                                    pairs=pairs))


def rotary_table(recipe, dim):
    """The ``dim // 2`` frequencies (float32) a head's first ``dim``
    columns are turned by under ``recipe`` (a :class:`Rotary`), and the
    factor on cos and sin."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    inv_freq = recipe.theta ** (-2 * i / dim)
    if recipe.factor is None:
        return inv_freq, 1.0

    def turns(beta):
        # the dimension that turns ``beta`` times over the original length
        return (dim * math.log(recipe.original_len / (beta * 2 * math.pi))
                / (2 * math.log(recipe.theta)))

    low = max(math.floor(turns(recipe.beta_fast)), 0)
    high = min(math.ceil(turns(recipe.beta_slow)), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0, 1)
    return (inv_freq * (1 - ramp) + inv_freq / recipe.factor * ramp,
            recipe.attention_factor)


def rotate(x, recipe):
    """``x [..., T, H, D]`` turned as ``recipe`` (a :class:`Rotary`)
    says: the first ``fraction`` of the columns in the rotate-half
    pairing within them, scaled by the recipe's factor; the rest pass.
    Computed in float32, returned in ``x.dtype`` (:func:`turn`)."""
    dim = int(x.shape[-1] * recipe.fraction)
    return turn(x, *rotary_operands(x, *rotary_table(recipe, dim)))


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2, -1) + eps) * scale`` in float32, returned
    in ``x.dtype``.  With ``unit_offset`` the parameter starts at 0 and
    the norm multiplies by ``1 + scale``.  Plain ``jax.numpy``: XLA
    fuses it."""
    eps: float = 1e-6
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.unit_offset
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.unit_offset:
            scale = 1 + scale
        x32 = x.astype(jnp.float32)
        out = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (out * scale).astype(x.dtype)


def make_norm(cfg, name):
    """The norm ``cfg.block`` names."""
    if cfg.block.norm == "rms":
        return RMSNorm(eps=cfg.norm_eps, unit_offset=cfg.norm_unit_offset,
                       name=name)
    return FusedLayerNorm(eps=cfg.norm_eps, name=name)


def full_qkv(cfg, x):
    """q, k, v ``[..., T, H, D]`` of one fused projection (submodules of
    the :class:`Attention` that calls it)."""
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
    if cfg.block.positions in ("rope", "rope_pairs"):
        with jax.named_scope("attn/rope"):
            q, k = (rope(u, cfg.rope_theta,
                         pairs=cfg.block.positions == "rope_pairs")
                    for u in (q, k))
    return q, k, v


def latent_qkv(cfg, x):
    """q, k ``[..., T, H, nope_dim + rope_dim]`` and v ``[..., T, H,
    v_dim]`` of latent attention (submodules of the :class:`Attention`
    that calls it):

        c_q = norm(x W_qa);  q = c_q W_qb = [q_nope | q_rope] a head
        x W_kva = [c_kv | k_rope];  norm(c_kv) W_kvb = [k_nope | v] a head
        q = [q_nope | rope(q_rope)];  k = [k_nope | rope(k_rope)]

    with the one ``k_rope`` turned once and shared by every head."""
    spec, h = cfg.block.attention, cfg.n_heads
    pairs = cfg.block.positions == "rope_pairs"

    def dense(features, name):
        return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                               name=name)

    with jax.named_scope("attn/latent"):
        # the norms are made again from the two narrow results kept
        c_q = make_norm(cfg, "q_a_norm")(checkpoint_name(
            dense(spec.q_rank, "q_a")(x), KEPT_Q_A))
        q = checkpoint_name(
            dense((h, spec.nope_dim + spec.rope_dim), "q_b")(c_q), KEPT_Q_B)
        kv_a = checkpoint_name(
            dense(spec.kv_rank + spec.rope_dim, "kv_a")(x), KEPT_KV_A)
        c_kv = make_norm(cfg, "kv_a_norm")(kv_a[..., :spec.kv_rank])
        kv = checkpoint_name(
            dense((h, spec.nope_dim + spec.v_dim), "kv_b")(c_kv), KEPT_KV_B)
        k_rope = rope(kv_a[..., None, spec.kv_rank:], cfg.rope_theta, pairs)
        # the whole head in one pass: its first nope_dim columns pass
        q = turn(q, *rotary_operands(
            q, _inv_freq(cfg.rope_theta, spec.rope_dim),
            start=spec.nope_dim, pairs=pairs))
        k = jnp.concatenate(
            [kv[..., :spec.nope_dim],
             jnp.broadcast_to(k_rope, kv.shape[:-1] + (spec.rope_dim,))],
            axis=-1)
    return q, k, kv[..., spec.nope_dim:]


def grouped_attention(cfg, x):
    """Attention of a :class:`GroupedAttention` on ``x [..., T, d]``
    (submodules of the :class:`Attention` that calls it).  Under the
    scope ``attn/window`` where the kind has a window and
    ``attn/global`` where it has none, the norm of the heads under
    ``qk_norm``, the rotation under ``rope`` (no such scope where the
    kind's ``rotary`` is ``None``: nothing is turned) and the attention
    function's call under ``flash`` inside it, and the gate under
    ``attn/gate``."""
    spec = cfg.block.attention

    def kind():
        return jax.named_scope(
            "attn/global" if spec.window is None else "attn/window")

    def dense(features, name):
        return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                               name=name)

    with kind():
        q = dense((spec.heads, spec.head_dim), "q")(x)
        kv = dense((2, spec.kv_heads, spec.head_dim), "kv")(x)
        k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        if spec.qk_norm:
            with jax.named_scope("qk_norm"):
                # over each head's columns, one scale shared by the heads
                q = RMSNorm(eps=cfg.norm_eps, name="q_norm")(q)
                k = RMSNorm(eps=cfg.norm_eps, name="k_norm")(k)
        if spec.rotary is not None:
            with jax.named_scope("rope"):
                q, k = rotate(q, spec.rotary), rotate(k, spec.rotary)
        attn = cfg.attn_fn or default_attention()
        with jax.named_scope("flash"):
            # a function that knows no window is not asked for one
            window = {} if spec.window is None else {"window": spec.window}
            o = attn(q, k, v, causal=True, **window)
    if spec.gate:
        with jax.named_scope("attn/gate"):
            gate = jax.nn.softplus(nn.Dense(
                spec.heads, use_bias=False, dtype=jnp.float32,
                name="gate")(x))
            o = (o * gate[..., None]).astype(o.dtype)
    with kind():
        return dense(cfg.d_model, "out")(o.reshape(o.shape[:-2] + (-1,)))


class Attention(nn.Module):
    """Causal self-attention of the kind ``cfg.block.attention`` names;
    the attention function is handed heads of the scores' width for q
    and k and of the values' width for v (and, by a
    :class:`GroupedAttention`, fewer heads of k and v than of q and its
    window)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if isinstance(cfg.block.attention, GroupedAttention):
            return grouped_attention(cfg, x)
        latent = isinstance(cfg.block.attention, LatentAttention)
        q, k, v = (latent_qkv if latent else full_qkv)(cfg, x)
        attn = cfg.attn_fn or default_attention()
        o = attn(q, k, v, causal=True)
        o = o.reshape(o.shape[:-2] + (-1,))
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out")(o)


def _taps_init(key, shape, dtype=jnp.float32):
    """Uniform in ``+- 1 / sqrt(taps)``: a depthwise ``Conv1d``'s
    default start (fan-in = the taps)."""
    bound = 1 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def causal_taps(g, w, dtype):
    """``s[t] = sum_j w[j] * g[t - (taps - 1) + j]`` in float32 for ``g
    [..., T, d]`` and ``w [taps, d]`` (a depthwise causal convolution
    along ``T``: the LAST tap weighs the position itself, rows before 0
    are zero): that many shifted multiply-adds over ``g`` padded ahead
    with zero rows, the operands in ``dtype`` as a Dense's kernel, the
    products summed in float32; JAX differentiates them."""
    taps, t = w.shape[0], g.shape[-2]
    ahead = [(0, 0)] * (g.ndim - 2) + [(taps - 1, 0), (0, 0)]
    g = jnp.pad(g, ahead)
    w = w.astype(dtype).astype(jnp.float32)
    return sum(g[..., j:j + t, :].astype(jnp.float32) * w[j]
               for j in range(taps))


class ShortConvMixer(nn.Module):
    """The mixer of a :class:`ShortConv` block on ``x [..., T, d]``: no
    attention, no bias, no non-linearity but two gates,

        (b, c, h) = split3(x W_in);  g = b * h
        s[t] = sum_j w[j] * g[t - (taps - 1) + j]     (rows before 0: zero)
        y = (c * s) W_out

    with ``W_in [d, 3 d]``, ``w [taps, d]`` (a depthwise causal
    convolution along ``T``: the LAST tap weighs the position itself, the
    first the one ``taps - 1`` before it; :func:`causal_taps`) and
    ``W_out [d, d]``.  All of it runs under the scope
    ``mixer/conv``: the two products under ``in`` and ``out``, the
    gates and the taps (elementwise, bound by HBM) under ``gate_conv``."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, taps = self.cfg, self.cfg.block.attention.taps
        d = cfg.d_model

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        with jax.named_scope("mixer/conv"):
            bch = checkpoint_name(dense(3 * d, "in")(x), KEPT_IN)
            w = self.param("kernel", _taps_init, (taps, d), jnp.float32)
            with jax.named_scope("gate_conv"):
                b, c, h = (bch[..., i * d:(i + 1) * d] for i in range(3))
                y = c * causal_taps(b * h, w, cfg.dtype).astype(cfg.dtype)
            return dense(d, "out")(y)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Step sizes log-uniform in [0.001, 0.1] through the inverse of
    softplus (Mamba's start, arXiv:2312.00752 section 3.6)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1, 2, ..., N)`` for every channel (S4D-real)."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class SelectiveScanMixer(nn.Module):
    """The mixer of a :class:`SelectiveScan` block on ``x [B, T, d]``
    (Mamba, arXiv:2312.00752); returns ``(out, y)``:

        (a, z) = split2(x W_in);  c = silu(conv(a) + b_c)
        (r, B, C) = split(c W_x);  delta = softplus(r W_dt + b_dt)
        h[t] = exp(delta[t] A) * h[t-1] + (delta[t] * c[t]) B[t]
        y[t] = h[t] C[t] + D * c[t];   out = (y * silu(z)) W_out

    with ``W_in [d, 2 d_inner]``, ``conv`` the causal depthwise
    convolution of :func:`causal_taps`, ``W_x [d_inner, dt_rank + 2
    N]``, ``W_dt [dt_rank, d_inner]``, ``A = -exp(A_log) [d_inner, N]``
    and ``W_out [d_inner, d]``.  ``delta``, ``A`` and the state are
    float32 (``ops/selective_scan.py``).  ``y``, the scan's output before
    the gate, is what a block that ``publishes`` hands on as ``memory``.
    All of it runs under the scope ``mixer/ssm``: ``in``, ``conv``,
    ``proj`` (``W_x``, ``W_dt``, softplus), ``scan`` and ``gate_out``
    (the gate and ``W_out``) inside it."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.selective_scan import selective_scan

        cfg, spec = self.cfg, self.cfg.block.attention
        inner, n, rank = spec.d_inner, spec.state, spec.dt_rank

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        def param(name, init, *shape):
            return self.param(name, init, shape, jnp.float32)

        with jax.named_scope("mixer/ssm"):
            az = checkpoint_name(dense(2 * inner, "in")(x), KEPT_IN)
            with jax.named_scope("conv"):
                w = param("conv_kernel", _taps_init, spec.taps, inner)
                bias = param("conv_bias", nn.initializers.zeros, inner)
                c = nn.silu(causal_taps(az[..., :inner], w, cfg.dtype)
                            + bias).astype(cfg.dtype)
            with jax.named_scope("proj"):
                rbc = dense(rank + 2 * n, "x")(c)
                w_dt = param("dt_kernel", nn.initializers.variance_scaling(
                    1 / 3, "fan_in", "uniform"), rank, inner)
                # operands in the activation dtype, the sum and what
                # follows in float32
                delta = jax.nn.softplus(jax.lax.dot_general(
                    rbc[..., :rank], w_dt.astype(cfg.dtype),
                    (((x.ndim - 1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    + param("dt_bias", _dt_bias_init, inner))
            with jax.named_scope("scan"):
                y = selective_scan(
                    c, delta, -jnp.exp(param("A_log", _a_log_init, inner, n)),
                    rbc[..., rank:rank + n], rbc[..., rank + n:],
                    param("D", nn.initializers.ones, inner))
            with jax.named_scope("gate_out"):
                return dense(cfg.d_model, "out")(
                    y * nn.silu(az[..., inner:])), y


def _a_log_uniform_init(key, shape, dtype=jnp.float32):
    """``A = -uniform[1, 16]`` a head (Mamba-2's start)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """The mixer of a :class:`Mamba2` block on ``x [B, T, d]`` (Mamba-2,
    arXiv:2405.21060), ``H`` heads of ``P``, ``G`` groups, state ``N``:

        (z, xBC, dt) = split(x W_in) at H P, H P + 2 G N, H
        xBC = silu(conv(xBC) + b_c);  (xs, B, C) = split(xBC)
        dt = softplus(dt + b_dt);  A = -exp(A_log) [H]
        h[t] = exp(dt[t] A) h[t-1] + (dt[t] xs[t]) (outer) B[t]   a head
        y[t] = h[t] C[t] + D xs[t]
        out = (group_rms(y * silu(z)) * w) W_out

    with ``conv`` the causal depthwise convolution of
    :func:`causal_taps` over the channels, ``B`` and ``C`` together, a
    head reading the ``B`` and ``C`` of group ``h // (H / G)``, and
    ``group_rms`` an RMSNorm over each group's ``H P / G`` channels,
    after the gate.  ``dt``, ``A`` and the state ``[P, N]`` are float32
    (``ops/ssd.py``, which computes the scan by chunks of
    ``spec.chunk`` positions as matrix products).  No biases but the
    convolution's and ``dt``'s.  All of it runs under the scope
    ``mixer/ssm``: ``in``, ``conv``, ``proj`` (``dt``'s bias and
    softplus), ``scan`` (``intra`` and ``inter`` inside it) and
    ``gate_out`` (the gate, the norm and ``W_out``)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.ssd import ssd

        cfg, spec = self.cfg, self.cfg.block.attention
        heads, groups, n = spec.heads, spec.groups, spec.state
        inner, bc = spec.d_inner, spec.groups * spec.state
        lead = x.shape[:-1]

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        def param(name, init, *shape):
            return self.param(name, init, shape, jnp.float32)

        with jax.named_scope("mixer/ssm"):
            zxd = checkpoint_name(dense(spec.in_width, "in")(x), KEPT_IN)
            z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * bc],
                          zxd[..., 2 * inner + 2 * bc:])
            with jax.named_scope("conv"):
                w = param("conv_kernel", _taps_init, spec.taps,
                          inner + 2 * bc)
                bias = param("conv_bias", nn.initializers.zeros,
                             inner + 2 * bc)
                xbc = nn.silu(causal_taps(xbc, w, cfg.dtype)
                              + bias).astype(cfg.dtype)
            with jax.named_scope("proj"):
                dt = jax.nn.softplus(dt.astype(jnp.float32)
                                     + param("dt_bias", _dt_bias_init, heads))
            with jax.named_scope("scan"):
                y = ssd(xbc[..., :inner].reshape(lead + (heads, -1)), dt,
                        -jnp.exp(param("A_log", _a_log_uniform_init, heads)),
                        xbc[..., inner:inner + bc].reshape(
                            lead + (groups, n)),
                        xbc[..., inner + bc:].reshape(lead + (groups, n)),
                        param("D", nn.initializers.ones, heads),
                        chunk=spec.chunk)
            with jax.named_scope("gate_out"):
                # the gate first, then the norm over each group's channels
                gated = (y.reshape(lead + (inner,)) * nn.silu(z)).astype(
                    jnp.float32).reshape(lead + (groups, -1))
                normed = gated * jax.lax.rsqrt(jnp.mean(
                    jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps)
                scale = param("norm_scale", nn.initializers.ones, inner)
                return dense(cfg.d_model, "out")(
                    (normed.reshape(lead + (inner,)) * scale).astype(
                        cfg.dtype))


class MemoryUnitMixer(nn.Module):
    """The mixer of a :class:`MemoryUnit` block on ``x [B, T, d]`` and
    the ``memory [B, T, d_inner]`` an earlier block published: ``(silu(x
    W_1) * memory) W_2``, no biases, under the scope ``mixer/gmu``."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg, inner = self.cfg, self.cfg.block.attention.d_inner

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        with jax.named_scope("mixer/gmu"):
            gate = checkpoint_name(dense(inner, "in")(x), KEPT_IN)
            return dense(cfg.d_model, "out")(
                nn.silu(gate) * memory.astype(cfg.dtype))


class DifferentialAttentionMixer(nn.Module):
    """Attention of a :class:`DifferentialAttention` on ``x [B, T, d]``;
    returns ``(out, (k, v))``.  With q ``[B, T, H / 2, 2, D]`` (a
    projection with a bias), k and v ``[B, T, G / 2, 2, D]`` (a
    projection with a bias of this block, or the ``keys`` an earlier
    block published) and ``vv`` the two value heads of a pair side by
    side, ``[B, T, G / 2, 2 D]``:

        a_s = attention(q[..., s, :], k[..., s, :], vv)        s = 0, 1
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        out = (rms(a_0 - lambda a_1) * w * (1 - lambda_init)) W_o + b_o

    each attention causal, under the kind's window, through the
    attention function at ``D`` / ``2 D`` with grouped key-value heads;
    ``lq``, ``lk`` four learned vectors of ``D``, the norm over the ``2
    D`` columns of a pair.  Under the scope ``attn/window``,
    ``attn/global`` or, where the keys are read, ``attn/cross``: the two
    calls under ``flash`` inside it, the subtraction, the norm and the
    scale under ``diff``."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, keys=None):
        cfg, spec = self.cfg, self.cfg.block.attention
        pairs, kv_pairs, dim = spec.heads // 2, spec.kv_heads // 2, \
            spec.head_dim
        kind = ("attn/cross" if spec.keys == "read" else
                "attn/global" if spec.window is None else "attn/window")

        def dense(features, name):
            return nn.DenseGeneral(features, dtype=cfg.dtype, name=name)

        with jax.named_scope(kind):
            q = dense((pairs, 2, dim), "q")(x)
            if spec.keys == "read":
                k, v = keys
            else:
                kv = dense((2, kv_pairs, 2, dim), "kv")(x)
                k, v = kv[..., 0, :, :, :], kv[..., 1, :, :, :]
            vv = v.reshape(v.shape[:-2] + (2 * dim,))
            attn = cfg.attn_fn or default_attention()
            with jax.named_scope("flash"):
                window = {} if spec.window is None else {
                    "window": spec.window}
                first, second = (attn(q[..., s, :], k[..., s, :], vv,
                                      causal=True, **window) for s in (0, 1))
            with jax.named_scope("diff"):
                lq1, lk1, lq2, lk2 = (self.param(
                    name, nn.initializers.normal(0.1), (dim,), jnp.float32)
                    for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                                 "lambda_k2"))
                lam = (jnp.exp(jnp.sum(lq1 * lk1))
                       - jnp.exp(jnp.sum(lq2 * lk2)) + spec.lambda_init)
                o = RMSNorm(eps=cfg.norm_eps, name="subln")(
                    first.astype(jnp.float32)
                    - lam * second.astype(jnp.float32))
                o = (o * (1 - spec.lambda_init)).astype(cfg.dtype)
            return nn.Dense(cfg.d_model, dtype=cfg.dtype, name="out")(
                o.reshape(o.shape[:-2] + (-1,))), (k, v)


def _summary_init(key, shape, dtype=jnp.float32):
    """``clip(normal, -1, 1) / sqrt(head_dim)``: the start of the two
    learned vectors a head of a :class:`ChunkSummaryAttention`."""
    return (jnp.clip(jax.random.normal(key, shape, dtype), -1, 1)
            / math.sqrt(shape[-1]))


def default_chunk_attention():
    """The attention of a :class:`ChunkSummaryAttention`: the flash
    kernels joined by their ``lse`` on TPU, the dense masked softmax
    elsewhere (as :func:`default_attention`)."""
    from horovod_tpu.ops import chunk_attention
    if jax.default_backend() == "tpu":
        return chunk_attention.chunk_summary_attention
    return chunk_attention.reference_chunk_summary_attention


class ChunkSummaryAttentionMixer(nn.Module):
    """Attention of a :class:`ChunkSummaryAttention` on ``x [B, T, d]``:

        q, k, v = x W_q, x W_k, x W_v  [H heads of D], q and k turned
        (kt, vt) = pool_chunks(k, v, phi, mu)        phi, mu [H, D] learned
        out = chunk_summary_attention(q, k, v, kt, vt) W_o

    (``ops/chunk_attention.py``), no biases.  All of it runs under the
    scope ``attn/eva``: ``qkv``, ``rope``, ``pool``, ``flash`` (the two
    kernel calls under ``local`` and ``remote`` and their ``join``
    inside it) and ``out``."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.chunk_attention import pool_chunks

        cfg, spec = self.cfg, self.cfg.block.attention
        scale = 1 / math.sqrt(spec.head_dim)

        def dense(features, name):
            return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                                   name=name)

        with jax.named_scope("attn/eva"):
            with jax.named_scope("qkv"):
                q, k, v = (checkpoint_name(
                    dense((spec.heads, spec.head_dim), name)(x), kept)
                    for name, kept in zip(("q", "k", "v"), KEPT_QKV))
            with jax.named_scope("rope"):
                q, k = rotate(q, spec.rotary), rotate(k, spec.rotary)
            with jax.named_scope("pool"):
                phi, mu = (self.param(name, _summary_init,
                                      (spec.heads, spec.head_dim),
                                      jnp.float32) for name in ("phi", "mu"))
                kt, vt = pool_chunks(k, v, phi, mu, spec.chunk, scale)
            with jax.named_scope("flash"):
                o = default_chunk_attention()(
                    q, k, v, kt, vt, window=spec.window, chunk=spec.chunk,
                    scale=scale)
            with jax.named_scope("out"):
                return dense(cfg.d_model, "out")(
                    o.reshape(o.shape[:-2] + (-1,)))


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = checkpoint_name(nn.Dense(cfg.d_ff, use_bias=False,
                                     dtype=cfg.dtype, name="up")(x), KEPT_UP)
        x = nn.gelu(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down")(x)


class SwigluMlp(nn.Module):
    """``(silu(x gate) * (x up)) down``, no biases."""
    cfg: TransformerConfig
    width: Optional[int] = None  # None: cfg.d_ff

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        width = self.width or cfg.d_ff
        # a pair: the backward of ``silu(g) * u`` reads both
        gate = checkpoint_name(dense(width, "gate")(x), KEPT_GATE)
        up = checkpoint_name(dense(width, "up")(x), KEPT_UP)
        hidden = nn.silu(gate) * up
        return dense(cfg.d_model, "down")(hidden)


class UngatedMlp(nn.Module):
    """``act(x up) down`` with ``act`` one of ``parallel/moe.py``'s
    ``UNGATED`` (``"relu2"``: the square of ReLU), no gate, no biases."""
    cfg: TransformerConfig
    width: int
    activation: str = "relu2"

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.parallel.moe import UNGATED

        cfg = self.cfg
        up = checkpoint_name(nn.Dense(self.width, use_bias=False,
                                      dtype=cfg.dtype, name="up")(x), KEPT_UP)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down")(UNGATED[self.activation](up))


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
    a router over ``cfg.n_experts`` experts of width
    ``cfg.d_expert``, ``cfg.experts_per_token`` a token, as ``spec``
    (a :class:`TopkExperts`; the default is ``"moe_topk"``) says: gated
    experts or, under ``activation="relu2"``, experts with no gate (no
    ``wg``), the
    weights of the ``spec.held`` experts alone, ``spec.shared`` experts
    of the same form every token goes through, the choice made through
    ``router_bias [E]`` where one is given.  The choice is made here, from ``x``,
    unless the caller hands a ``decision``: what :meth:`route` gave it
    for another array of the same tokens (``spec.route_from``).  Sows
    its load-balancing loss (``moe_aux_loss``), its router z-loss
    (``moe_z_loss``) and the counter ``moe_tokens_per_expert`` for
    :func:`apply_with_aux`."""
    cfg: TransformerConfig
    spec: TopkExperts = TopkExperts()

    def setup(self):
        from horovod_tpu.parallel.moe import moe_kernel_init, moe_param_shapes

        cfg, spec = self.cfg, self.spec
        width = cfg.d_expert or cfg.d_ff
        held = spec.held[1] if spec.held else cfg.n_experts
        shapes = moe_param_shapes(cfg.d_model, width, held,
                                  gated=spec.gated)
        shapes["router"] = (cfg.d_model, cfg.n_experts)
        self.kernels = {name: {"kernel": self.param(
            f"{name}_kernel", moe_kernel_init, shape)}
            for name, shape in shapes.items()}
        if spec.shared and spec.gated:
            self.shared = SwigluMlp(cfg, width=spec.shared_columns(width))
        elif spec.shared:
            self.shared = UngatedMlp(cfg, spec.shared_columns(width),
                                     spec.activation)

    def route(self, x, router_bias=None):
        """The router's decision on ``x [..., d_model]``
        (``parallel/moe.py:route_tokens``)."""
        from horovod_tpu.parallel.moe import route_tokens

        return route_tokens(
            x, self.kernels["router"]["kernel"], self.cfg.experts_per_token,
            **self._route(router_bias))

    def _route(self, router_bias):
        """``topk_route``'s keyword arguments."""
        spec = self.spec
        return dict(scoring=spec.scoring, bias=router_bias,
                    renormalize=spec.renormalize, scale=spec.scale)

    def __call__(self, x, router_bias=None, decision=None):
        from horovod_tpu.parallel.moe import topk_moe

        cfg, spec = self.cfg, self.spec
        route = {} if decision is not None else self._route(router_bias)
        out, aux = topk_moe(
            x, self.kernels, k=cfg.experts_per_token, held=spec.held,
            activation=spec.activation, decision=decision, **route)
        if spec.shared:
            with jax.named_scope("moe/shared"):
                out = out + self.shared(x)
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
FEED_FORWARDS = {"gelu": (Mlp, "mlp"), "swiglu": (SwigluMlp, "mlp"),
                 "moe_switch": (MoeMlp, "moe"),
                 "moe_topk": (TopkMoeMlp, "moe")}


class Block(nn.Module):
    cfg: TransformerConfig
    # this block's feed-forward where it is not ``cfg.block.ffn``
    ffn: Union[None, str, TopkExperts] = None

    @nn.compact
    def __call__(self, x, router_bias=None, shared=FrozenDict()):
        """``router_bias [E]``: the balancing bias of this block's
        router, for a :class:`TopkExperts`.  ``shared``: what earlier
        blocks published for later ones (``{"memory": ..., "keys": (k,
        v)}``, empty before the first publisher).  Returns ``(x,
        shared)`` with what this block's own spec publishes put in, so
        under :func:`keeping` it is an input of the block that reads
        it and a result of the block that made it; empty, it is no
        operand and no result of the compiled block.  A block of one
        branch (``attention=None`` or ``ffn=None`` in its spec) is ``x +
        ffn(ln2(x))`` or ``x + mixer(ln1(x))`` alone."""
        cfg, mixer = self.cfg, self.cfg.block.attention
        sandwich = cfg.block.norm_placement == "sandwich"
        ffn = self.ffn or cfg.block.ffn
        experts = decision = None
        if isinstance(ffn, TopkExperts):
            experts = TopkMoeMlp(cfg, ffn, name="moe")
            if ffn.route_from == "input":
                # decided from what the block was handed, ahead of its
                # first norm; the mixer stands between decision and use
                with jax.named_scope("route_ahead"):
                    decision = experts.route(x, router_bias)
        if mixer is not None:
            y = make_norm(cfg, "ln1")(x).astype(cfg.dtype)
            if isinstance(mixer, ShortConv):
                y = ShortConvMixer(cfg, name="mixer")(y)
            elif isinstance(mixer, Mamba2):
                y = Mamba2Mixer(cfg, name="mixer")(y)
            elif isinstance(mixer, SelectiveScan):
                y, memory = SelectiveScanMixer(cfg, name="mixer")(y)
                if mixer.publishes:
                    shared = {**shared, "memory": memory}
            elif isinstance(mixer, MemoryUnit):
                y = MemoryUnitMixer(cfg, name="mixer")(y, shared["memory"])
            elif isinstance(mixer, DifferentialAttention):
                y, keys = DifferentialAttentionMixer(cfg, name="attn")(
                    y, shared.get("keys"))
                if mixer.keys == "published":
                    shared = {**shared, "keys": keys}
            elif isinstance(mixer, ChunkSummaryAttention):
                y = ChunkSummaryAttentionMixer(cfg, name="attn")(y)
            else:
                y = Attention(cfg, name="attn")(y)
            if sandwich:
                y = make_norm(cfg, "ln1_post")(y)
            x = x + y
        if ffn is None:  # the mixer alone: the sum is what the caller keeps
            return x, shared
        if mixer is not None:
            x = checkpoint_name(x, KEPT_SUM)
        y = make_norm(cfg, "ln2")(x).astype(cfg.dtype)
        if experts is not None:
            y = experts(y, router_bias, decision)
        else:
            module, name = FEED_FORWARDS[ffn]
            y = module(cfg, name=name)(y)
        if sandwich:
            y = make_norm(cfg, "ln2_post")(y)
        return x + y, shared


def lm_loss(logits, tokens):
    """Mean next-token cross-entropy — the LM training loss.

    On TPU this is the fused Pallas kernel
    (``ops/pallas/softmax_xent.py``: no materialized ``[rows, vocab]``
    log-softmax; the logits walked in tiles sized by what fits VMEM,
    whatever the vocabulary divides by); the XLA/optax lowering
    elsewhere."""
    with jax.named_scope("loss"):
        return jnp.mean(_token_losses(logits,
                                      jnp.roll(tokens, -1, axis=-1)))


def multi_offset_lm_loss(logits, tokens, outputs):
    """The loss of a head with ``outputs`` rows of logits a position
    (``TransformerConfig.head_outputs``): ``logits [..., T, outputs *
    V]`` read as ``[..., T, outputs, V]``, output ``r`` at position ``t``
    asked for token ``t + 1 + r`` (the roll of :func:`lm_loss`, by ``1 +
    r``: the last ``1 + r`` positions are asked for the first tokens),
    the mean over positions and outputs with equal weights; through the
    same kernel on ``[... T outputs, V]`` rows.  With one output it is
    :func:`lm_loss`."""
    with jax.named_scope("loss"):
        labels = jnp.stack([jnp.roll(tokens, -(1 + r), axis=-1)
                            for r in range(outputs)], axis=-1)
        return jnp.mean(_token_losses(
            logits.reshape(logits.shape[:-1] + (outputs, -1)), labels))


def _token_losses(logits, labels):
    """Per-token cross-entropy ``[...]`` in float32 of ``logits [...,
    V]``: the fused kernel on TPU, the XLA lowering elsewhere."""
    if jax.default_backend() == "tpu":
        from horovod_tpu.ops.pallas.softmax_xent import softmax_xent
        return softmax_xent(logits, labels)
    from horovod_tpu.ops.pallas.softmax_xent import softmax_xent_reference
    return softmax_xent_reference(logits, labels)


def looped_lm_loss(logits, gate_logits, tokens, beta):
    """The training loss of a model with an exit gate (a looped language
    model, arXiv:2510.25741): ``(loss, aux)`` from the logits of every
    exit ``[R, B, T, V]`` and the gate's logits ``[R, B, T]``.

    With ``lambda^(r) = sigmoid(gate^(r))`` a token leaves at exit r
    with probability ``p^(r) = lambda^(r) prod_{s<r} (1 - lambda^(s))``,
    the last exit taking what is left (its own gate is not read), and

        loss = mean_i [ sum_r p^(r)_i l^(r)_i - beta H(p_i) ]

    with ``l^(r)_i`` the next-token cross-entropy of exit r at position
    i (labels as :func:`lm_loss` has them) and ``H`` the entropy of the
    exit distribution.  All in float32, the probabilities through their
    logarithms.  ``aux``: ``exit_probability [R]`` (the mean of
    ``p^(r)`` over the tokens), ``exit_losses [R]`` (the mean
    cross-entropy of each exit) and ``exit_entropy``."""
    with jax.named_scope("loss"), jax.named_scope("exit_loss"):
        labels = jnp.broadcast_to(jnp.roll(tokens, -1, axis=-1),
                                  gate_logits.shape)
        losses = _token_losses(logits, labels)
        gate = gate_logits.astype(jnp.float32)
        log_stay = jax.nn.log_sigmoid(-gate)          # log(1 - lambda)
        stayed = jnp.cumsum(log_stay, axis=0) - log_stay  # over s < r
        log_p = stayed + jax.nn.log_sigmoid(gate).at[-1].set(0.0)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * losses, axis=0) - beta * entropy)
    tokens_axes = tuple(range(1, p.ndim))
    return loss, {"exit_probability": jnp.mean(p, axis=tokens_axes),
                  "exit_losses": jnp.mean(losses, axis=tokens_axes),
                  "exit_entropy": jnp.mean(entropy)}


def _sown(state):
    """What the MoE blocks of one ``apply`` sowed, by name, each list in
    the order of the blocks (``block_2`` before ``block_10``)."""
    found = {"moe_aux_loss": [], "moe_z_loss": [],
             "moe_tokens_per_expert": []}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            state.get("intermediates", {}))[0]:
        keys = [k.key for k in path if hasattr(k, "key")]
        block = keys[0].rpartition("_")[2]
        for name in found.keys() & set(keys):
            found[name].append((int(block) if block.isdigit() else 0, leaf))
    return {name: [leaf for _, leaf in sorted(leaves, key=lambda e: e[0])]
            for name, leaves in found.items()}


def apply_with_aux(model, params, tokens, *, router_bias=None,
                   next_token=None):
    """Forward pass returning ``(logits, aux)``.

    MoE blocks ``sow`` their auxiliary losses and counters into the
    ``intermediates`` collection, which plain ``model.apply`` drops;
    training code for MoE configs must use this helper (or pass
    ``mutable=["intermediates"]`` itself) and add the terms to the loss
    with the job's weights, or the router receives no balancing
    gradient.  ``aux`` holds, summed over the MoE blocks,
    ``load_balancing`` and ``router_z`` (0 where no block has one),
    beside ``moe_layers`` (how many blocks were summed, for a mean) and
    the counter ``tokens_per_expert [layers, E]`` of the top-k blocks in
    the order of the layers (``None`` without one).  A model with an
    exit gate returns the logits of every exit, and
    ``aux["exit_gate_logits"] [R, B, T]`` is what
    :func:`looped_lm_loss` takes beside them.

    ``router_bias [layers, E]``: the balancing biases of the
    :class:`TopkExperts` blocks, a row a block in the counter's order; the caller moves them after the step
    (:func:`~horovod_tpu.parallel.moe.balance_bias` of the bias and the
    counter) and carries them beside the parameters.

    ``next_token``: a :class:`NextTokenModule`, applied to the model's
    last hidden state with ``params["next_token"]``, the model's
    embedding and its head; its logits (for token ``i + 2`` at position
    ``i``) come back as ``aux["next_token_logits"]``, its expert layer
    is the last row of the counter and reads the last row of
    ``router_bias``.
    """
    out, state = model.apply(
        {"params": params}, tokens, router_bias=router_bias,
        return_hidden=next_token is not None, mutable=["intermediates"])
    sown = _sown(state)
    aux = {}
    if next_token is not None:
        out, hidden = out
        with jax.named_scope("mtp"):
            aux["next_token_logits"], state = next_token.apply(
                {"params": params["next_token"]}, hidden, tokens,
                params["embed"]["embedding"], params["lm_head"]["kernel"],
                None if router_bias is None else router_bias[-1],
                mutable=["intermediates"])
        for name, leaves in _sown(state).items():
            sown[name] += leaves
    counts = sown["moe_tokens_per_expert"]
    if "exit_gate_logits" in state.get("intermediates", {}):
        aux["exit_gate_logits"], = state["intermediates"]["exit_gate_logits"]
    aux.update(
        load_balancing=sum(sown["moe_aux_loss"], jnp.zeros((), jnp.float32)),
        router_z=sum(sown["moe_z_loss"], jnp.zeros((), jnp.float32)),
        moe_layers=len(sown["moe_aux_loss"]),
        tokens_per_expert=jnp.stack(counts) if counts else None)
    return out, aux


def device_memory_bytes():
    """``(bytes_limit, bytes_in_use)`` of ``memory_stats`` of THIS
    process's first device, read while the step is traced: the memory
    the device a step runs on has, and what is placed on it at that
    moment.  In a loop that places its state before it lowers the step
    (``benchmark/loops/spmd*.py``, ``examples/``) the second is the
    parameters, the optimizer's WHOLE state (an accumulator, a third
    moment) and the inputs that wait: what stays resident through the
    step, which :func:`planned_blocks` hands :func:`kept_plan`.  A
    number the backend does not tell is ``None``, as both are on the
    CPU.  So a step lowered on a CPU host for a described topology is
    planned for no limit (rung 0 everywhere, the program of before), not
    for the chip it describes, unless the caller stands in for this
    function as ``tests/test_chip_compile.py`` does."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit"), stats.get("bytes_in_use")


@functools.lru_cache(maxsize=None)
def parameter_bytes(cfg, seq, next_token=False):
    """``(whole, blocks)``: the bytes of ``Transformer(cfg)``'s parameter
    tree as it is initialized for sequences of ``seq`` (with
    ``next_token`` a :class:`NextTokenModule`'s beside it), and of that
    tree the bytes of each block's own parameters, in the order of the
    layers (the module's block last); from shapes alone."""
    cfg = dataclasses.replace(cfg, remat=False)
    key = jax.random.PRNGKey(0)
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)

    def nbytes(tree):
        return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree))

    model = jax.eval_shape(Transformer(cfg, parent=None).init, key,
                           tokens)["params"]
    whole = nbytes(model)
    blocks = [nbytes(model[f"block_{i}"]) for i in range(cfg.n_layers)]
    if next_token:
        table = jax.ShapeDtypeStruct((cfg.vocab_size, cfg.d_model),
                                     jnp.float32)
        module = jax.eval_shape(
            NextTokenModule(cfg, parent=None).init, key,
            jax.ShapeDtypeStruct((1, seq, cfg.d_model), cfg.dtype), tokens,
            table, jax.ShapeDtypeStruct(table.shape[::-1], jnp.float32)
        )["params"]
        whole += nbytes(module)
        blocks.append(nbytes(module["block"]))
    return whole, tuple(blocks)


@dataclasses.dataclass(frozen=True)
class KeptPlan:
    """What :func:`kept_plan` found, a layer an entry (the next-token
    module's block last): ``names``, what the layer keeps beside
    ``kept_names(cfg)`` (empty: rung 0); ``kept``, the bytes the layer
    holds from its forward pass to its backward pass (its input and
    everything it keeps by name); ``params``, the bytes of the
    parameter tree as the plan reckons it from shapes, ``budget``, what
    the plan was filled to, ``resident``, what it took to stay on the
    device through the whole step, and ``moments``, the bytes predicted
    for each moment of the step in its order (``"head"``, ``"block
    <l>"`` as the backward pass enters block ``l``, from the last to the
    first, ``"end"``); ``None`` and none where no limit is known and
    nothing is predicted."""
    names: Tuple[Tuple[str, ...], ...]
    kept: Tuple[int, ...]
    params: Optional[int] = None
    budget: Optional[int] = None
    resident: Optional[int] = None
    moments: Tuple[Tuple[str, int], ...] = ()

    @property
    def rungs(self):
        """0 where a layer keeps ``kept_names(cfg)`` alone, 1 where its
        products' results too."""
        return tuple(int(bool(names)) for names in self.names)

    @property
    def moment(self):
        """The name of the moment the predicted peak stands at (the
        first of the largest)."""
        return max(self.moments, key=lambda m: m[1])[0] if self.moments else (
            None)

    @property
    def peak(self):
        """The step's predicted peak: the largest of its moments."""
        return max(n for _, n in self.moments) if self.moments else None

    def __str__(self):
        def gib(n):
            return "unknown" if n is None else f"{n / 2 ** 30:.3f} GiB"

        layers = ", ".join(
            f"{i}: {rung}" + (f" (+ {' '.join(names)})" if names else "")
            for i, (rung, names) in enumerate(zip(self.rungs, self.names)))
        return (f"kept_plan: rung of each layer [{layers}], kept "
                f"{gib(sum(self.kept))}, predicted peak {gib(self.peak)} "
                f"at {self.moment or 'no moment'} of a budget of "
                f"{gib(self.budget)}, resident {gib(self.resident)}")


def kept_plan(cfg, batch, seq, device_bytes, next_token=False,
              resident=None):
    """What every layer of a recomputed model (``cfg.remat``) holds for
    its backward pass on ``[batch, seq]`` tokens a device, planned from
    bytes: a :class:`KeptPlan` over the ``cfg.n_layers`` blocks and,
    with ``next_token``, the block of a :class:`NextTokenModule` beside
    them.  A pure function of its arguments.

    Every layer starts on rung 0, today's names (``kept_names``).  Rung
    1 adds the results of the layer's products (``kept_products``), the
    one whose byte saves most first, ties in the order of the layers,
    while the predicted peak of the step stays within ``BUDGET_SHARE``
    of ``device_bytes``; what does not fit is passed over for what
    does.  (A rung 2, a block not recomputed at all, is not built: what
    the compiler holds of such a block is no closed form of shapes.)

    The predicted peak is the largest of the step's moments, each
    counting what exists at THAT moment beside what stays on the device
    through the whole step: ``resident`` as the caller read it, or the
    float32 parameter tree three times (a parameter and Adam's two
    moments) where that is more or nothing was read (``None``).
    With the blocks differentiated from the last to the first:

    - the head's moment: what every layer keeps (its input and its
      names' ``kept_bytes``), the stack's output and its norm, the
      logits and their gradient (with a next-token module both heads')
      and four statistics of a row, 512 bytes each;
    - the backward pass entering block ``l``: the gradients that exist
      by then (those of the blocks after ``l`` and of everything that is
      no block: the head, the embedding, the final norm, the next-token
      module's own), what the blocks up to ``l`` keep, and of block
      ``l`` itself every result it has a name for, made again, and as
      much for the cotangents, less what it keeps on rung 0 (what rung 1
      keeps is counted whole, the safe side), the stream's cotangent in
      and out, and the logits' gradient and the head's input, which wait
      for the head's weight gradient (to the end of the backward pass
      where the head is the embedding); in the module's block also the
      model's own logits, which wait for their turn;
    - the end: the whole gradient tree and nothing kept.

    A gradient is counted as held from the moment it is made to the end
    of the step: as a step has them that holds its gradients
    (``clip_by_global_norm``, an all-reduce of the tree as
    ``DistributedOptimizer`` makes), and more than one has whose
    optimizer eats a weight's gradient where it is made (one fused
    ``optax.adam`` under one ``jit``), which compiles to less by the
    gradients of the blocks already differentiated.  The gradients and
    what is kept are arithmetic.  What waits for what at a block's
    moment is the compiler's schedule, read off the live ranges XLA
    dumps of compiled steps (``tests/xla_live.py``;
    ``tests/test_chip_compile.py`` holds a toy's step to the head's
    moment and the first block's, and the cells' steps to the peak), and
    the plan counts no fragments
    of the allocator, no transient of a block's forward pass and no
    moment inside the head's backward (eight cotangents of the stream
    under ``head_outputs=8``): a moment of the plan is an upper bound of
    the compiled steps it was read from, not of every step.
    ``BUDGET_SHARE`` is the room for the rest, and a step over it fails
    where it is compiled.  What ``resident``
    leaves out the plan cannot see: it reads ``cfg``, the shapes and two
    numbers.  ``planned_blocks`` reads it off the device
    (``device_memory_bytes``), so an optimizer with more state than two
    moments or a resident accumulator
    (``DistributedOptimizer(backward_passes_per_step > 1)``) is counted
    wherever the state is placed before the step is lowered; a step
    lowered from shapes before its state exists is planned for Adam, and
    one that accumulates then fails at compile, loudly, not at run time.

    With ``device_bytes`` ``None`` (a backend that tells no limit) and
    under ``cfg.passes > 1`` (``kept_names`` says why) every layer
    stays on rung 0 and nothing is predicted."""
    layers = [(cfg, i) for i in range(cfg.n_layers)]
    if next_token:  # its block is built from ``cfg`` as it is
        layers.append((dataclasses.replace(
            cfg, pattern=(), leading_dense=0, moe_every=0), 0))
    rows = batch * seq
    x = rows * cfg.d_model * jnp.dtype(
        cfg.residual_dtype or cfg.dtype).itemsize
    kept = [x + sum(kept_bytes(of, batch, seq, i).values())
            for of, i in layers]
    names = [()] * len(layers)
    if device_bytes is None or cfg.passes > 1:
        return KeptPlan(tuple(names), tuple(kept))

    products = [(-worth, at, group, sum(kept_bytes(
        of, batch, seq, i, group).values()))
        for at, (of, i) in enumerate(layers)
        for group, worth in kept_products(of, i)]
    params, blocks = parameter_bytes(cfg, seq, next_token)
    resident = max(3 * params, resident or 0)
    logits = rows * cfg.head_outputs * cfg.vocab_size * jnp.dtype(
        cfg.logits_dtype or cfg.dtype).itemsize
    # the stack's output and its norm, which the final norm's and the
    # head's backward read, stand beside the logits and their gradient,
    # and four statistics of a row (the norm's two, the loss's log-sum
    # and its cotangent) as the kernels write them, a lane tile each
    head = 2 * logits + 2 * x + 4 * rows * 128 * 4
    # a block's own moment, on rung 0: what it keeps is there already,
    # its products' results are made again, and every named result has a
    # cotangent.  What a name keeps beyond that is counted whole on top:
    # whether the block would have held it at that moment anyway is the
    # compiler's schedule.  The stream's cotangent enters and leaves; the
    # logits' gradient and the head's input wait for the head's weight
    # gradient, which the compiler may make last (with a tied head it
    # does: the embedding's gradient has both uses)
    own = [k + 2 * sum(n for _, at, _, n in products if at == layer)
           + 3 * x + logits for layer, k in enumerate(kept)]
    if next_token:  # the model's logits wait while the module's are read
        own[-1] += logits
        head += 2 * rows * cfg.vocab_size * jnp.dtype(cfg.dtype).itemsize
    budget = int(BUDGET_SHARE * device_bytes)
    # the gradients that exist as the backward pass enters each block:
    # everything that is no block's, and the blocks' after it
    after = list(itertools.accumulate(
        reversed(blocks), initial=params - sum(blocks)))[-2::-1]

    def moments(kept):
        """``[(moment, bytes)]`` of the step with ``kept``: the head,
        the blocks from the last to the first, the end."""
        held = list(itertools.accumulate(kept))
        return ([("head", resident + held[-1] + head)]
                + [(f"block {layer}", resident + after[layer] + held[layer]
                    + own[layer]) for layer in reversed(range(len(layers)))]
                + [("end", resident + params)])

    for _, at, group, n in sorted(products, key=lambda p: p[:2]):
        with_it = kept[:at] + [kept[at] + n] + kept[at + 1:]
        if max(m for _, m in moments(with_it)) <= budget:
            kept = with_it
            names[at] += group
    return KeptPlan(tuple(names), tuple(kept), params, budget, resident,
                    tuple(moments(kept)))


def planned_blocks(module, tokens, next_token=False, say=False):
    """``(classes, plan)`` for ``module`` (a :class:`Transformer` or a
    :class:`NextTokenModule`) on ``tokens [..., T]``: the class every
    layer's block is made of (the next-token module's last): ``Block``
    itself where the model is not recomputed (``cfg.remat``: recompute
    what does not fit), else ``Block`` recomputed, keeping what
    :func:`kept_plan` says for the device's memory and what is placed on
    it (``device_memory_bytes``: its ``bytes_in_use`` is the plan's
    ``resident``; while the module is initialized, which differentiates
    nothing, no limit is told).  ``plan`` is ``None`` where nothing is
    recomputed.  With ``say`` the plan is logged (``kept_plan:``, at
    ``info``), and at ``warning`` that what was in use on the device made
    the layers keep less than the parameters and Adam's two moments
    alone would have.

    The plan is made where the step is traced, for the shapes the trace
    sees and ONE device of this process, FROM THE DEVICE'S STATE AT THAT
    MOMENT, which ``jit``'s cache does not key on: trace (and trace
    again) with the device at rest, holding the step's state and inputs
    and nothing else.  Arrays left over from other work, or a step in
    flight whose activations are still there, read as ``resident``: the
    blocks then keep less, the step is slower by what is made again, and
    the warning is the only sign.  (An accumulator or a third moment
    gives the same warning, rightly: the line says what was read.)
    Inside ``shard_map`` (the ``spmd`` loop's step) ``tokens`` is a
    device's own batch and the plan is that device's.  Under a plain
    ``jit`` over a sharded batch ``tokens.shape`` is the GLOBAL batch
    and the parameters count whole however they are sharded: the plan
    then reckons every device's activations on one, keeps less than
    there is room for and never more
    (``tests/test_transformer_kept.py`` compiles such a step)."""
    cfg = module.cfg
    if not cfg.remat:
        return [Block] * (cfg.n_layers + bool(next_token)), None
    limit, in_use = (None, None) if module.is_initializing() else (
        device_memory_bytes())
    shape = (math.prod(tokens.shape[:-1]), tokens.shape[-1], limit, next_token)
    plan = kept_plan(cfg, *shape, in_use)
    if say and not module.is_initializing():
        log = get_logger()
        log.info("%s", plan)
        alone = plan if in_use is None else kept_plan(cfg, *shape)
        if plan.names != alone.names:
            log.warning(
                "kept_plan: %.3f GiB were in use on the device while the "
                "step was traced, more than the parameters three times "
                "(%.3f GiB), and the layers keep %.3f GiB where they would "
                "have kept %.3f: right for an optimizer with more state "
                "than Adam's; otherwise trace the step with the device at "
                "rest", in_use / 2 ** 30, 3 * plan.params / 2 ** 30,
                sum(plan.kept) / 2 ** 30, sum(alone.kept) / 2 ** 30)
    made = {names: keeping(Block, kept_names(cfg) + names)
            for names in set(plan.names)}
    return [made[names] for names in plan.names], plan


class Transformer(nn.Module):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (causal LM).
    ``router_bias [layers, E]``: see :func:`apply_with_aux`.  With
    ``return_hidden`` also the last block's output before the final
    norm: ``(logits, hidden)``.

    With ``cfg.passes`` R > 1 the blocks are made once and the stack
    runs R times, ``h^(r) = norm_f(block_N(... block_1(h^(r-1))))``: a
    scan over the pass index with the N blocks in its body and the
    parameters broadcast, so the parameter tree is the one-pass model's
    and N block bodies are compiled, not R N.  With ``cfg.exit_gate``
    the logits are those of EVERY exit through the one head, ``[R, B,
    T, vocab]``, and the gate's logits ``[R, B, T]`` (float32) are sown
    as ``exit_gate_logits`` for :func:`apply_with_aux`.

    One pytree travels through the stack beside ``x``: every block is
    handed what the blocks before it published and returns it with its
    own (empty in a model none of whose blocks publishes).  With
    ``cfg.tie_head`` the logits are ``norm_f(x) E^T`` with ``E`` the
    embedding in the activation dtype (under the scope ``lm_head``), and
    the model has no ``lm_head`` of its own.  With ``cfg.head_outputs``
    R > 1 the logits are ``[B, T, R vocab]``, R rows a position
    (:func:`multi_offset_lm_loss`)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, router_bias=None, return_hidden=False):
        cfg = self.cfg
        # the stream's dtype: x + branch keeps it, the wider of the two
        stream = cfg.residual_dtype or cfg.dtype
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=stream,
                         name="embed")
        x = embed(tokens)
        if cfg.block.positions == "learned":
            x = x + nn.Embed(
                cfg.max_len, cfg.d_model, dtype=stream,
                name="pos_embed")(jnp.arange(tokens.shape[-1]))
        # ``return_hidden`` is how ``apply_with_aux`` says that it applies
        # a NextTokenModule to the result: that module's block is one
        # layer more of the plan, and the module plans with the same
        # arguments.  Another caller that asks for the hidden state has
        # a block counted that is not there, and keeps less
        blocks, _ = planned_blocks(self, tokens, next_token=return_hidden,
                                   say=True)

        def one_pass(mdl, carry, _):
            """The stack once, closed by the final norm; the carry is
            ``(exit, hidden before the norm)``."""
            x, _ = carry
            rows = 0  # blocks so far that read a row of router_bias
            shared = {}
            for i in range(cfg.n_layers):
                ffn = cfg.ffn_of(i)
                bias = None
                if router_bias is not None and isinstance(ffn, TopkExperts):
                    bias, rows = router_bias[rows], rows + 1
                block = blocks[i](cfg.at(i), ffn=ffn, name=f"block_{i}")
                x, shared = block(x, bias, shared)
            out = make_norm(cfg, "ln_f")(x)
            return (out, x), (out if cfg.exit_gate else None)

        if cfg.passes == 1:
            (x, hidden), exits = one_pass(self, (x, x), None)
            exits = None if exits is None else exits[None]
        else:
            if any(cfg.ffn_of(i) not in ("gelu", "swiglu")
                   for i in range(cfg.n_layers)):
                raise ValueError(
                    "passes > 1 over expert layers: what they sow is not "
                    "carried out of the scan; nothing is built for it")
            with jax.named_scope("loop"):
                (x, hidden), exits = nn.scan(
                    one_pass, variable_broadcast="params",
                    split_rngs={"params": False},
                    length=cfg.passes)(self, (x, x), None)
        if cfg.tie_head:
            def head(h):
                with jax.named_scope("lm_head"):
                    return embed.attend(h)
        else:
            summed = {} if cfg.logits_dtype is None else {
                "dot_general": functools.partial(
                    jax.lax.dot_general,
                    preferred_element_type=cfg.logits_dtype)}
            head = nn.Dense(cfg.head_outputs * cfg.vocab_size, use_bias=False,
                            dtype=cfg.dtype, name="lm_head", **summed)
        if cfg.exit_gate:
            with jax.named_scope("exit_gate"):
                self.sow("intermediates", "exit_gate_logits", nn.Dense(
                    1, dtype=jnp.float32, name="exit_gate")(exits)[..., 0])
            with jax.named_scope("exit_head"):
                logits = head(exits)
        else:
            logits = head(x.astype(cfg.dtype))
        return (logits, hidden) if return_hidden else logits


class NextTokenModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2), beside a :class:`Transformer` whose embedding and head
    it shares: at position ``i``

        h'_i = W_eh [norm_e(Emb(t_{i+1})) ; norm_h(z_i)]

    with ``z`` the model's last hidden state before its final norm, then
    one block of the model's kind, a final norm of its own and the
    model's head: logits for token ``i + 2``.  ``t_{i+1}`` is the roll
    of the tokens, as :func:`lm_loss` has its labels."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, hidden, tokens, embedding, head, router_bias=None):
        cfg = self.cfg
        following = embedding[jnp.roll(tokens, -1, axis=-1)]
        x = jnp.concatenate(
            [make_norm(cfg, "enorm")(following.astype(cfg.dtype)),
             make_norm(cfg, "hnorm")(hidden)], axis=-1)
        x = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                     name="eh_proj")(x)
        blocks, _ = planned_blocks(self, tokens, next_token=True)
        x, _ = blocks[-1](cfg, name="block")(x, router_bias)
        x = make_norm(cfg, "ln_f")(x).astype(cfg.dtype)
        return jnp.dot(x, head.astype(cfg.dtype))
