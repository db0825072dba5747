"""Family ``joyai_lm``: a DeepSeek-V3-style decoder (``model_type:
joyai_llm_flash``; the keys are DeepSeek-V3's) through the program's
normal model: ``horovod_tpu.models.Transformer`` with latent attention,
rotary pairs, a leading dense SwiGLU layer, then expert layers with a
sigmoid router chosen through a balancing bias, a shared expert and the
chip's share of the routed experts, a multi-token-prediction module
beside it (``NextTokenModule``), ``apply_with_aux`` + ``lm_loss`` twice.
Beside it: the operations one sequence requires, what the flash kernels
of a step require, the shapes by which the trace readers find the
layers' instructions, and a plain float32 reference of the same
equations.

The reference is written from the equations (arXiv:2405.04434 section
2.1 for latent attention, arXiv:2412.19437 sections 2.1.2 and 2.2 for
the router, its bias and the module; the keys as Hugging Face's
``modeling_deepseek_v3`` reads them), not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel, **no sort, no
top-k primitive and no grouped product**: the k experts are found by
taking the largest k times, every held expert runs on every token, one
expert at a time, and is weighed by the router's weight where it is
among the token's k and by 0 elsewhere.  It is given the same share as
the program: the router has all its outputs, the experts ``first ...
first + count - 1`` are computed, what the absent ones would add is left
out.  It is computed in blocks so that it fits beside a float32 AdamW
step: a layer at a time and a head at a time under ``jax.checkpoint``,
the logits in blocks of rows.  It reads the program's parameter tree
(that layout is the one thing it takes from the program).
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself, as ``olmoe_lm`` has
# them and for its reasons: bfloat16 products with float32 sums against
# float32 at ``highest``, and a token whose k-th and next score are
# closer than the bfloat16 input resolves chooses another expert.  Each
# limit lies between two readings on the v5e at published widths
# (PERF.md section 6, PR 31).  Over 12 seeds the system is off by at
# most 8.6e-5 on the forward loss, 1.3e-4 on the group's (the 24
# readings look half-normal with a deviation of 6e-5) and 7.0e-3 on the
# update (median 1.6e-3; the first AdamW step at 2.2e-4 takes the
# repeated sequence's loss from 13.25 to 11.8, far outside the linear
# regime, and there the tokens routed otherwise show).  The reference
# computed in bfloat16 throughout (``perturb="bfloat16"``, the nearest
# precision below the stated one; its loss itself has steps of 0.0625
# at 13) is off by 9.4e-4, 3.9e-4 and 4.0e-2 and comes out as not
# correct by the first and the last.  The harness gives the reference's second
# forward the bias from before the step (zeros) where the system's
# second step chooses through +-0.001: with the rate at 0 the same seed
# reads 1.57e-4 on the update for 1.97e-4 with it, so the bias moves
# the reading by 4e-5, a hundredth of what the routing of near-ties
# does.
TOLERANCE = {"forward": 4e-4, "update": 0.03}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward of 680 M parameters
CHECK_GROUP = 1
# rows of the head's float32 logits held at once by the reference
LOSS_BLOCK_ROWS = 2048


def _held(config):
    held = config["experts_held"]
    return held["first"], held["count"]


def _program_config(config):
    from horovod_tpu.models import (BlockSpec, LatentAttention, TopkExperts,
                                    TransformerConfig)

    # the choice is not limited to groups of experts: nothing is built
    # for it
    assert config["n_group"] == config["topk_group"] == 1
    assert config["scoring_func"] == "sigmoid"
    assert config["qk_head_dim"] == (config["qk_nope_head_dim"]
                                     + config["qk_rope_head_dim"])
    assert _held(config)[1] == config["n_routed_experts"]
    assert config["moe_layer_freq"] == 1 and config["rope_scaling"] is None
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        max_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        leading_dense=config["first_k_dense_replace"],
        remat=config["remat"],
        block=BlockSpec(
            norm="rms",
            positions="rope_pairs" if config["rope_interleave"] else "rope",
            attention=LatentAttention(
                q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
                nope_dim=config["qk_nope_head_dim"],
                rope_dim=config["qk_rope_head_dim"],
                v_dim=config["v_head_dim"]),
            ffn=TopkExperts(
                scoring=config["scoring_func"],
                renormalize=config["norm_topk_prob"],
                scale=config["routed_scaling_factor"],
                shared=config["n_shared_experts"], held=_held(config))))


def _models(config):
    from horovod_tpu.models import NextTokenModule, Transformer

    assert config["num_nextn_predict_layers"] == 1
    program = _program_config(config)
    return Transformer(program), NextTokenModule(program)


def _expert_layers(config):
    """Blocks with a router: the main model's and the module's."""
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model and module from
    ``key``; ``extra`` is the routers' balancing bias, zeros."""
    model, module = _models(config)
    main_key, module_key = jax.random.split(key)
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    params = model.init(main_key, tokens)["params"]
    params["next_token"] = module.init(
        module_key,
        jnp.zeros(tokens.shape + (config["hidden_size"],),
                  jnp.dtype(config["activation_dtype"])),
        tokens, params["embed"]["embedding"],
        params["lm_head"]["kernel"])["params"]
    return params, {"router_bias": jnp.zeros(
        (_expert_layers(config), config["router_outputs"]), jnp.float32)}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens of the vocabulary's
    slice."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the main cross-entropy + the job's weight
    times the module's (no auxiliary term); ``(loss, extra)`` with the
    balancing bias moved by the step's counts."""
    from horovod_tpu.models import apply_with_aux, lm_loss
    from horovod_tpu.parallel.moe import balance_bias

    job = config["job"]
    model, module = _models(config)
    logits, aux = apply_with_aux(
        model, params, batch, router_bias=extra["router_bias"],
        next_token=module)
    total = lm_loss(logits, batch) + job["mtp_weight"] * lm_loss(
        aux["next_token_logits"], jnp.roll(batch, -1, axis=-1))
    return total, {"router_bias": balance_bias(
        extra["router_bias"], aux["tokens_per_expert"],
        job["bias_update_rate"])}


def _matmul_params(config):
    """Parameters a token is multiplied with: ``(latent attention's, a
    dense layer's feed-forward, an expert layer's feed-forward, the
    module's projection, the head's)``.  Of the routed experts a token
    meets the held ones among its k: ``k * count / outputs`` of them
    at a uniform router (0.5 at 8 of 256 with 16 held)."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    attention = (d * config["q_lora_rank"]
                 + config["q_lora_rank"] * heads * config["qk_head_dim"]
                 + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
                 + config["kv_lora_rank"] * heads
                 * (config["qk_nope_head_dim"] + config["v_head_dim"])
                 + heads * config["v_head_dim"] * d)
    expert = 3 * d * config["moe_intermediate_size"]
    met = (config["num_experts_per_tok"] * _held(config)[1]
           / config["router_outputs"])
    experts = (d * config["router_outputs"]
               + config["n_shared_experts"] * expert + met * expert)
    return (attention, 3 * d * config["intermediate_size"], experts,
            2 * d * d, d * config["vocab_size"])


def _attention_blocks(config):
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def _causal_attention_flops(config, batch, t):
    """Forward operations of causal attention in every block, the main
    model's and the module's: the ``T (T + 1) / 2`` query-key pairs
    that are used, ``2 d_qk`` for the score and ``2 d_v`` for the
    weighted sum each."""
    return (_attention_blocks(config) * batch
            * config["num_attention_heads"]
            * 2 * (config["qk_head_dim"] + config["v_head_dim"])
            * t * (t + 1) // 2)


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), **nothing recomputed** (the cell
    recomputes every block's forward pass in the backward, about a
    third more, and that shows as a lower ``mfu_required``), matrix
    products only: per token ``2 x`` the matmul parameters it meets
    (the head twice: the main loss and the module's), and causal
    attention as counted above.  Norms, rotary, the router's sigmoid,
    top-k and the sort are below 1%."""
    t = job["seq_len"]
    attention, dense_ffn, experts, module, head = _matmul_params(config)
    dense = config["first_k_dense_replace"]
    per_token = (_attention_blocks(config) * attention + dense * dense_ffn
                 + _expert_layers(config) * experts
                 + config["num_nextn_predict_layers"] * (module + head)
                 + head)
    return 3 * (round(2 * per_token * t)
                + _causal_attention_flops(config, 1, t))


def flash_flops_per_step(config, job):
    """What ``mla_flash_roofline`` divides: the operations the flash
    kernels of one chip's step require, forward and both gradients
    (3 x forward), the causal pairs only, nothing recomputed."""
    return 3 * _causal_attention_flops(config, job["per_chip_batch"],
                                       job["seq_len"])


def trace_shapes(config, job):
    """The shapes by which ``latent_trace.py`` finds the layers'
    instructions in a device trace, as they stand in an instruction's
    text (PERF.md section 3; read off the compiled step).  ``flash``: q
    and k of the three flash custom calls, ``[batch x heads, T, d_qk]``.
    ``latent``: what only latent attention makes, ``[batch, T, ...]``:
    the query's latent, the key-value latent with and without the
    rotated key, that key; a head's query or key, its key with its
    value, and each part alone, as ``[batch, T, heads, w]`` and, on the
    way into and out of the kernels, ``[batch, heads, T, w]``.
    ``experts``: the token-slots ``[N k`` and the router's ``[N,
    outputs]``."""
    b, t = job["per_chip_batch"], job["seq_len"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    value, rank = config["v_head_dim"], config["kv_lora_rank"]
    latents = {config["q_lora_rank"], rank + rope, rank, rope}
    per_head = {nope + rope, nope + value, nope, rope, value}
    return {
        "flash": [f"[{b * heads},{t},{nope + rope}]"],
        "latent": ([f"[{b},{t},{w}]" for w in sorted(latents)]
                   + [f"[{b},{t},{heads},{w}]" for w in sorted(per_head)]
                   + [f"[{b},{heads},{t},{w}]" for w in sorted(per_head)]),
        "experts": [f"[{b * t * config['num_experts_per_tok']}",
                    f"[{b * t},{config['router_outputs']}]"],
    }


# ------------------------------------------------------------ reference
def _rms_norm(u, w, eps):
    return u / jnp.sqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                        + eps) * w


def _rope_pairs(u, theta):
    """``u [T, ..., D]``: the pair ``(u[2i], u[2i + 1])`` at position t
    turned by ``t * theta^(-2i / D)`` (``rope_interleave: true``)."""
    t, d = u.shape[0], u.shape[-1]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-2 * i / d)
    angle = angle.reshape((t,) + (1,) * (u.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle).astype(u.dtype), jnp.sin(angle).astype(u.dtype)
    even, odd = u[..., 0::2], u[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return turned.reshape(u.shape)


def _latent_attention(x, w, config):
    """One sequence ``x [T, d]``; returns ``x + attention``."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    heads, nope = config["num_attention_heads"], config["qk_nope_head_dim"]
    rank = config["kv_lora_rank"]
    t, d = x.shape
    a = w["attn"]
    h = _rms_norm(x, w["ln1"]["scale"], eps)
    c_q = _rms_norm(h @ a["q_a"]["kernel"], a["q_a_norm"]["scale"], eps)
    q = jnp.einsum("tr,rhk->htk", c_q, a["q_b"]["kernel"])
    down = h @ a["kv_a"]["kernel"]
    c_kv = _rms_norm(down[:, :rank], a["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("tr,rhk->htk", c_kv, a["kv_b"]["kernel"])
    # ONE rotated key for all heads
    k_rope = _rope_pairs(down[:, rank:], theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = 1 / math.sqrt(config["qk_head_dim"])

    @jax.checkpoint
    def head(args):
        q_h, kv_h = args
        scores = (q_h[:, :nope] @ kv_h[:, :nope].T
                  + _rope_pairs(q_h[:, nope:], theta) @ k_rope.T) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ kv_h[:, nope:]

    mixed = jax.lax.map(head, (q, kv))                      # [H, T, v]
    out = a["out"]["kernel"].reshape(heads, config["v_head_dim"], d)
    return x + jnp.einsum("htv,hvd->td", mixed, out)


def _swiglu(h, w):
    return ((jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"]))
            @ w["down"]["kernel"])


def _experts(x, w, bias, config, perturb):
    """All tokens ``x [N, d]``; returns ``(x + shared + held routed
    experts, token-slots per expert [outputs])``."""
    k, outputs = config["num_experts_per_tok"], config["router_outputs"]
    first, count = _held(config)
    h = _rms_norm(x, w["ln2"]["scale"], config["rms_norm_eps"])
    logits = h @ w["moe"]["router_kernel"]
    s = jax.nn.sigmoid(logits)
    if perturb == "scoring_func":
        s = jax.nn.softmax(logits, -1)
    # the k largest of s + b, one at a time (a tie goes to the lower
    # index); b decides the choice and enters no weight
    left = s + bias
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), outputs, dtype=bool)
        chosen, left = chosen | best, jnp.where(best, -jnp.inf, left)
    gate = jnp.where(chosen, s, 0.0)
    gate = gate / jnp.sum(gate, -1, keepdims=True)  # over all k, held or not
    if perturb != "routed_scaling_factor":
        gate = gate * config["routed_scaling_factor"]

    @jax.checkpoint
    def expert(wg, wu, wd, g):
        return ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd) * g[:, None]

    def add_expert(acc, weights):
        return acc + expert(*weights), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (w["moe"]["wg_kernel"], w["moe"]["wi_kernel"],
         w["moe"]["wo_kernel"], gate.T[first:first + count]))
    return (x + _swiglu(h, w["moe"]["shared"]) + routed,
            jnp.sum(chosen, 0))


def _block(x, w, bias, config, perturb):
    """One block on ``x [B, T, d]``; ``bias`` is ``None`` for a dense
    layer.  Returns ``(x, counts or None)``."""
    b, t, _ = x.shape
    # attention a sequence at a time, a head at a time
    x = jax.lax.map(lambda s: _latent_attention(s, w, config), x)
    x = x.reshape(b * t, -1)
    if bias is None:
        h = _rms_norm(x, w["ln2"]["scale"], config["rms_norm_eps"])
        return (x + _swiglu(h, w["mlp"])).reshape(b, t, -1), None
    x, counts = _experts(x, w, bias, config, perturb)
    return x.reshape(b, t, -1), counts


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
    purpose (tests of the check only): ``"routed_scaling_factor"``
    leaves the factor 2.5 off the weights; ``"scoring_func"`` scores by
    softmax for sigmoid; ``"bfloat16"`` computes everything, sums too,
    in bfloat16, the nearest precision below the one the configuration
    states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    job, eps = config["job"], config["rms_norm_eps"]
    dense = config["first_k_dense_replace"]
    bias = extra["router_bias"]
    b, t = batch.shape
    block = jax.checkpoint(
        lambda x, w, bias: _block(x, w, bias, config, perturb))
    with jax.default_matmul_precision("highest"):
        embedding, head = p["embed"]["embedding"], p["lm_head"]["kernel"]
        x = embedding[batch]                       # rotary: no table
        counts = []
        for i in range(config["num_hidden_layers"]):
            x, c = block(x, p[f"block_{i}"],
                         None if i < dense else bias[i - dense])
            counts += [] if c is None else [c]
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll)
        following = jnp.roll(batch, -1, axis=-1)
        main = _cross_entropy(
            _rms_norm(x, p["ln_f"]["scale"], eps).reshape(b * t, -1), head,
            following.reshape(-1))
        # the module: [norm(Emb(t_{i+1})) ; norm(z_i)] through W_eh, one
        # expert block, its own final norm, the main head, against
        # t_{i+2} (the roll, twice)
        m = p["next_token"]
        z = jnp.concatenate(
            [_rms_norm(embedding[following], m["enorm"]["scale"], eps),
             _rms_norm(x, m["hnorm"]["scale"], eps)], -1)
        z, c = block(z @ m["eh_proj"]["kernel"], m["block"], bias[-1])
        counts.append(c)
        module = _cross_entropy(
            _rms_norm(z, m["ln_f"]["scale"], eps).reshape(b * t, -1), head,
            jnp.roll(following, -1, axis=-1).reshape(-1))
    # b_e += rate * sign(mean(c) - c_e), the step's counts over all
    # outputs; no gradient
    c = jnp.stack(counts).astype(jnp.float32)
    moved = extra["router_bias"] + job["bias_update_rate"] * jnp.sign(
        jnp.mean(c, -1, keepdims=True) - c)
    return main + job["mtp_weight"] * module, {"router_bias": moved}
