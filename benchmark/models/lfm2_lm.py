"""Family ``lfm2_lm``: a decoder most of whose mixers are no attention
(``model_type: lfm2_moe``) through the program's normal model:
``horovod_tpu.models.Transformer`` with a pattern of block specs, three
layers in four a doubly gated causal convolution of three taps
(``ShortConv``), the fourth attention over grouped key-value heads with
an RMSNorm on every head of q and k before the rotation
(``GroupedAttention(qk_norm=True)``); a leading dense SwiGLU layer, then
expert layers with a sigmoid router chosen through a balancing bias,
renormalised weights, no shared expert, and the chip's share of the
routed experts; ``apply_with_aux`` + ``lm_loss``.  Beside it: the
operations one sequence requires, what the flash kernels of a step
require, the shape by which ``loop_trace.py`` finds the flash calls,
and a plain float32 reference of the same equations.

The reference is written from the equations, not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel, no convolution
primitive, **no sort, no top-k primitive and no grouped product**.  With
``u = norm1(x)``, ``d`` = 2048, ``T`` positions:

    conv:  (b, c, h) = split3(u W_in);  g = b * h
           s[t] = w[0] g[t - 2] + w[1] g[t - 1] + w[2] g[t]   (g[< 0] = 0)
           mixer = (c * s) W_out
    full:  q = u W_q [32 heads of 64];  k, v = u W_k, u W_v [8 of 64]
           q[h], k[g] = rms(q[h]) * w_qn, rms(k[g]) * w_kn   over the 64
           q, k = rot(q), rot(k)        rotate-half, theta 1e6, whole head
           o[h] = softmax_j(q[h, i] . k[h // 4, j] / 8 where j <= i) v[h // 4]
           mixer = concat_h(o[h]) W_o

the taps an explicit sum over ``j`` of shifted copies of ``g``,
attention a head at a time.  The expert layer: ``p = sigmoid(u' W_r)``,
the 4 largest of ``p + bias`` found by taking the largest 4 times, ``w
= p / sum of the 4``, ``sum over the experts held here of w_e
expert_e(u')``: every held expert runs on every token, one at a time,
weighed by 0 where it is not among the token's 4; what the absent
experts would add is left out, as in the program.  It is computed in
blocks so that it fits beside a float32 AdamW step: a layer and a head
of attention at a time under ``jax.checkpoint``, the logits in blocks of
rows.  It reads the program's parameter tree (that layout is the one
thing it takes from the program).
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself: bfloat16 products
# with float32 sums against float32 at ``highest``, and a token whose
# 4th and next score are closer than the bfloat16 input resolves chooses
# another expert.  Each limit lies between two readings on the v5e at
# published widths, from a sweep of 12 seeds of THIS family (PERF.md
# section 6, PR 41), the system and the reference computed in bfloat16
# throughout (``perturb="bfloat16"``, the nearest precision below the
# stated one) on the same seeds.  Forward: the system is off by 1e-7 to
# 8.6e-5 on the forward loss and 4.9e-6 to 1.06e-4 on the group's (19
# seeds: at most 1.06e-4); the bfloat16 reference's loss has steps of
# 0.0625 at 9.5, so one of its two readings can come out small by
# chance (8.8e-5 once): the larger of its two is 6.2e-4 to 3.0e-3.  The
# limit has 2.8 times of room over the system's largest reading and the
# bfloat16 reference fails it in every seed.  Update, at the job's rate
# of 1e-5: the first AdamW step takes the repeated sequence's loss from
# 9.51 to 9.15 and the system is off by 2.6e-4 to 4.5e-3 of that change
# (median 2.0e-3); the bfloat16 reference reads 5.0e-2 to 1.4e-1 and
# comes out as not correct by this limit in every seed: the limit is
# the geometric middle of 4.5e-3 and 5.0e-2.
TOLERANCE = {"forward": 3e-4, "update": 0.015}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward of 486 M parameters
CHECK_GROUP = 1
# rows of the head's float32 logits held at once by the reference
LOSS_BLOCK_ROWS = 2048
KINDS = ("conv", "full_attention")


def _held(config):
    held = config["experts_held"]
    return held["first"], held["count"]


def _head_dim(config):
    """A head's width: the config gives none, so the family's
    ``hidden_size / num_attention_heads`` (64)."""
    return config["hidden_size"] // config["num_attention_heads"]


def _published(config):
    """The published per-layer list from the first layer that is here
    on."""
    return config["layer_types"][config["layers_here"]["first"]:]


def _layers(config):
    """The kinds of the layers that are here: ``num_hidden_layers``
    entries of the published list from ``layers_here.first`` on."""
    return _published(config)[:config["num_hidden_layers"]]


def _period(config):
    """The shortest period of the published list as the layers here
    meet it (4: conv, full_attention, conv, conv)."""
    whole = _published(config)
    return next(whole[:p] for p in range(1, len(whole) + 1)
                if all(whole[i] == whole[i % p] for i in range(len(whole))))


def _program_config(config):
    from horovod_tpu.models import (BlockSpec, GroupedAttention, Rotary,
                                    ShortConv, TopkExperts,
                                    TransformerConfig)

    assert not config["conv_bias"]
    assert config["use_expert_bias"]
    assert config["rope_parameters"]["rope_type"] == "default"
    assert set(config["layer_types"]) == set(KINDS)
    assert _held(config)[1] == config["num_experts"]
    assert config["layers_here"]["layer_types"] == _layers(config)
    experts = TopkExperts(
        scoring="sigmoid", renormalize=config["norm_topk_prob"],
        scale=float(config["routed_scaling_factor"]), held=_held(config))
    mixers = {
        "conv": ShortConv(taps=config["conv_L_cache"]),
        "full_attention": GroupedAttention(
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=_head_dim(config), qk_norm=True,
            rotary=Rotary(
                theta=float(config["rope_parameters"]["rope_theta"])))}
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=_head_dim(config),
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        max_len=config["max_position_embeddings"],
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        leading_dense=config["num_dense_layers"], remat=config["remat"],
        pattern=tuple(
            BlockSpec(norm="rms", positions="rope", ffn=experts,
                      attention=mixers[kind])
            for kind in _period(config)))


def _model(config):
    from horovod_tpu.models import Transformer

    return Transformer(_program_config(config))


def _expert_layers(config):
    return config["num_hidden_layers"] - config["num_dense_layers"]


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``;
    ``extra`` is the routers' balancing bias, zeros."""
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    return _model(config).init(key, tokens)["params"], {
        "router_bias": jnp.zeros(
            (_expert_layers(config), config["router_outputs"]),
            jnp.float32)}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens of the vocabulary's
    slice."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the next-token cross-entropy (no auxiliary
    term); ``(loss, extra)`` with the balancing bias moved by the
    step's counts."""
    from horovod_tpu.models import apply_with_aux, lm_loss
    from horovod_tpu.parallel.moe import balance_bias

    logits, aux = apply_with_aux(
        _model(config), params, batch, router_bias=extra["router_bias"])
    return lm_loss(logits, batch), {"router_bias": balance_bias(
        extra["router_bias"], aux["tokens_per_expert"],
        config["job"]["bias_update_rate"])}


def _matmul_params(config):
    """Parameters a token is multiplied with: ``(a conv mixer's, an
    attention layer's, a dense layer's feed-forward, an expert layer's
    feed-forward, the head's)``.  A tap is one: it weighs one position
    of one channel a token, a multiplication and an addition, as an
    entry of a matrix does (6,144 of a mixer's 16,783,360); the norms'
    scales are none.  Of the routed experts a token meets the held ones
    among its k: ``k * count / outputs`` of them at a uniform router
    (0.5 at 4 of 64 with 8 held)."""
    d, dim = config["hidden_size"], _head_dim(config)
    conv = d * 3 * d + d * d + config["conv_L_cache"] * d
    attention = (2 * d * config["num_attention_heads"] * dim
                 + 2 * d * config["num_key_value_heads"] * dim)
    met = (config["num_experts_per_tok"] * _held(config)[1]
           / config["router_outputs"])
    experts = (d * config["router_outputs"]
               + met * 3 * d * config["moe_intermediate_size"])
    return (conv, attention, 3 * d * config["intermediate_size"], experts,
            d * config["vocab_size"])


def causal_pairs(t):
    """Query-key pairs a sequence of ``t`` uses: ``j <= i``."""
    return t * (t + 1) // 2


def _attention_flops(config, batch, t):
    """Forward operations of attention in the full-attention layers: the
    causal pairs alone, ``2 head_dim`` for the score and ``2 head_dim``
    for the weighted sum each, a query head."""
    return (_layers(config).count("full_attention") * batch
            * config["num_attention_heads"] * 4 * _head_dim(config)
            * causal_pairs(t))


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), nothing recomputed, matrix
    products only: per token ``2 x`` the matmul parameters it meets, and
    attention over the causal pairs as counted above.  The gates, norms,
    rotary, the router's sigmoid, top-k and the sort are below 1%."""
    t = job["seq_len"]
    conv, attention, dense_ffn, experts, head = _matmul_params(config)
    kinds = _layers(config)
    per_token = (kinds.count("conv") * conv
                 + kinds.count("full_attention") * attention
                 + config["num_dense_layers"] * dense_ffn
                 + _expert_layers(config) * experts + head)
    return 3 * (round(2 * per_token * t) + _attention_flops(config, 1, t))


def flash_flops_per_step(config, job):
    """What ``flash_roofline`` divides: the operations the flash kernels
    of one chip's step require, forward and both gradients (3 x
    forward), the causal pairs only, nothing recomputed."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"])


def trace_shapes(config, job):
    """The shape by which ``loop_trace.py`` finds the flash custom calls
    in a device trace, as it stands in an instruction's text: q,
    ``[batch x query heads, T, head_dim]`` (k and v are ``[batch x 8, T,
    head_dim]``; nothing else in the step is shaped like either).  The
    layers' own metrics go by scope (``scope_trace.py``)."""
    b, t = job["per_chip_batch"], job["seq_len"]
    return {"flash": [f"[{b * config['num_attention_heads']},{t},"
                      f"{_head_dim(config)}]"]}


# ------------------------------------------------------------ reference
def _rms_norm(u, w, eps):
    return u / jnp.sqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                        + eps) * w


def _rotate(u, theta):
    """``u [H, T, D]``: column i and column i + D / 2 at position t are
    one pair, turned by ``t * theta^(-2i / D)``."""
    _, t, d = u.shape
    half = d // 2
    inv_freq = theta ** (-2 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle).astype(u.dtype), jnp.sin(angle).astype(u.dtype)
    lo, hi = u[..., :half], u[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _conv(h, w, config, perturb):
    """One normed sequence ``h [T, d]`` through a conv mixer.  The
    program keeps the taps as ``[taps, d]``, the LAST row weighing the
    position itself."""
    t, d = h.shape
    taps = config["conv_L_cache"]
    b, c, gate = jnp.split(h @ w["in"]["kernel"], 3, axis=-1)
    g = b * gate
    kernel = w["kernel"][::-1] if perturb == "tap_order" else w["kernel"]
    s = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the position ``back`` before t
        shifted = jnp.concatenate(
            [jnp.zeros((back, d), g.dtype), g[:t - back]]) if back else g
        s = s + kernel[j] * shifted
    return (c * s) @ w["out"]["kernel"]


def _attention(h, w, config, perturb):
    """One normed sequence ``h [T, d]`` through a full-attention layer,
    a head at a time; no biases."""
    dim, eps = _head_dim(config), config["norm_eps"]
    t, d = h.shape
    theta = float(config["rope_parameters"]["rope_theta"])
    q = jnp.einsum("td,dhk->htk", h, w["q"]["kernel"])        # [H, T, D]
    # the program's key-value projection is [d, 2, G, D]
    k, v = jnp.einsum("td,dcgk->cgtk", h, w["kv"]["kernel"])  # [G, T, D]
    if perturb != "head_norm":
        q = _rms_norm(q, w["q_norm"]["scale"], eps)
        k = _rms_norm(k, w["k_norm"]["scale"], eps)
    q, k = _rotate(q, theta), _rotate(k, theta)
    heads = q.shape[0]
    group = heads // k.shape[0]
    allowed = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        q_h, index = args
        k_h, v_h = k[index // group], v[index // group]
        scores = jnp.where(allowed, q_h @ k_h.T / math.sqrt(dim), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v_h

    mixed = jax.lax.map(head, (q, jnp.arange(heads)))         # [H, T, D]
    return jnp.einsum("htk,hkd->td", mixed,
                      w["out"]["kernel"].reshape(heads, dim, d))


def _swiglu(h, w):
    return ((jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"]))
            @ w["down"]["kernel"])


def _experts(h, w, bias, config):
    """All normed tokens ``h [N, d]``; returns ``(held routed experts,
    token-slots per expert [outputs])``."""
    k, outputs = config["num_experts_per_tok"], config["router_outputs"]
    first, count = _held(config)
    p = jax.nn.sigmoid(h @ w["router_kernel"])
    # the k largest of p + b, one at a time (a tie goes to the lower
    # index); b decides the choice and enters no weight
    left = p + bias
    chosen = jnp.zeros(p.shape, bool)
    for _ in range(k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), outputs, dtype=bool)
        chosen, left = chosen | best, jnp.where(best, -jnp.inf, left)
    weight = jnp.where(chosen, p, 0.0)
    if config["norm_topk_prob"]:
        # over all k, held here or not
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * config["routed_scaling_factor"]

    @jax.checkpoint
    def expert(wg, wu, wd, g):
        return ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd) * g[:, None]

    def add_expert(acc, weights):
        return acc + expert(*weights), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["wg_kernel"], w["wi_kernel"], w["wo_kernel"],
         weight.T[first:first + count]))
    return routed, jnp.sum(chosen, 0)


def _block(x, w, kind, bias, config, perturb):
    """One block on ``x [B, T, d]``; ``bias`` is ``None`` for a dense
    layer.  Returns ``(x, counts or None)``."""
    b, t, _ = x.shape
    eps = config["norm_eps"]

    def mixer(s):
        h = _rms_norm(s, w["ln1"]["scale"], eps)
        if kind == "conv":
            return _conv(h, w["mixer"], config, perturb)
        return _attention(h, w["attn"], config, perturb)

    x = x + jax.lax.map(mixer, x)       # a sequence at a time
    x = x.reshape(b * t, -1)
    h = _rms_norm(x, w["ln2"]["scale"], eps)
    if bias is None:
        return (x + _swiglu(h, w["mlp"])).reshape(b, t, -1), None
    out, counts = _experts(h, w["moe"], bias, config)
    return (x + out).reshape(b, t, -1), counts


def _cross_entropy(x, head, labels):
    """Mean of ``-log softmax(x head)[label]`` over the rows of ``x``,
    the logits made a block of rows at a time and made again in the
    backward pass."""
    rows = x.shape[0]
    block = math.gcd(rows, LOSS_BLOCK_ROWS)

    @jax.checkpoint
    def block_sum(args):
        xs, ys = args
        logp = jax.nn.log_softmax(xs @ head, -1)
        return -jnp.sum(jnp.take_along_axis(logp, ys[:, None], -1))

    sums = jax.lax.map(block_sum, (x.reshape(rows // block, block, -1),
                                   labels.reshape(rows // block, block)))
    return jnp.sum(sums) / rows


def reference_loss(config, params, extra, batch, perturb=None):
    """Float32 forward pass and loss; ``(loss, extra)`` with the bias
    moved by the rule.  ``perturb`` names something to get wrong on
    purpose (tests of the check only): ``"tap_order"`` reads the taps
    back to front (the first weighs the position itself); ``"head_norm"``
    leaves the norm off the heads of q and k; ``"bfloat16"`` computes
    everything, sums too, in bfloat16, the nearest precision below the
    one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    dense = config["num_dense_layers"]
    bias = extra["router_bias"]
    b, t = batch.shape
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][batch]         # rotary: no table
        counts = []
        for i, kind in enumerate(_layers(config)):
            block = jax.checkpoint(
                lambda x, w, bias, kind=kind: _block(
                    x, w, kind, bias, config, perturb))
            x, c = block(x, p[f"block_{i}"],
                         None if i < dense else bias[i - dense])
            counts += [] if c is None else [c]
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll)
        total = _cross_entropy(
            _rms_norm(x, p["ln_f"]["scale"],
                      config["norm_eps"]).reshape(b * t, -1),
            p["lm_head"]["kernel"], jnp.roll(batch, -1, axis=-1).reshape(-1))
    # b_e += rate * sign(mean(c) - c_e), the step's counts over all
    # outputs; no gradient
    c = jnp.stack(counts).astype(jnp.float32)
    moved = extra["router_bias"] + config["job"]["bias_update_rate"] * (
        jnp.sign(jnp.mean(c, -1, keepdims=True) - c))
    return total, {"router_bias": moved}
